"""Rotary position embedding with YaRN-scaled frequencies.

Positions enter attention here and not the embedding: a query or key vector
of ``dim`` values is ``dim / 2`` pairs ``(x[2i], x[2i + 1])``, and pair ``i``
at position ``p`` is turned by the angle ``p * inv_freq[i]``.  The turned
vector is laid out as all first members, then all second members (the
half-split layout the published implementations leave it in); a score is a
dot product of two vectors turned the same way, so the layout cancels.

YaRN (Peng et al. 2023) stretches a model trained on
``original_max_position_embeddings`` positions by ``factor``: the fast
frequencies keep ``1 / f``, the slow ones take ``1 / (factor f)``, and
between the correction dimensions of ``beta_fast`` and ``beta_slow`` turns a
linear ramp blends the two.  ``yarn_mscale`` is the temperature the method
puts on the scores.
"""

from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

__all__ = ["rope_inv_freq", "yarn_mscale", "rope_angles", "apply_rope",
           "apply_rope_half"]


def _correction_dim(turns: float, dim: int, base: float, original: int):
    """The (fractional) pair index whose wavelength makes ``turns`` full
    turns over the ``original`` positions."""
    return dim * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(base))


def rope_inv_freq(dim: int, base: float = 10000.0, scaling=None) -> np.ndarray:
    """The ``dim / 2`` angular frequencies, float32.  ``scaling`` is the
    published ``rope_scaling`` group (``type`` yarn) or None for plain
    rotary."""
    freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return (1.0 / freq).astype(np.float32)
    if scaling.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {scaling.get('type')!r}: only "
                         "'yarn' is implemented")
    factor = float(scaling["factor"])
    original = int(scaling["original_max_position_embeddings"])
    low = max(math.floor(_correction_dim(scaling["beta_fast"], dim, base,
                                         original)), 0)
    high = min(math.ceil(_correction_dim(scaling["beta_slow"], dim, base,
                                         original)), dim - 1)
    if low == high:
        high += 0.001          # the published guard against a zero ramp
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1: the unscaled frequency, 0: the stretched
    return ((1.0 / (factor * freq)) * (1.0 - keep)
            + (1.0 / freq) * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is stretched)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_angles(pos, inv_freq):
    """cos and sin, float32 ``[..., dim / 2]``, of positions ``pos``."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """Turn ``x [..., dim]`` by angles ``cos, sin [..., dim / 2]`` (they
    broadcast against ``x``'s leading axes).  Float32 inside, ``x``'s dtype
    out."""
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def apply_rope_half(x, cos, sin):
    """``apply_rope`` for vectors whose pairs are ``(x[i], x[i + dim / 2])``
    going in as well as coming out (the ``rotate_half`` form of the
    Qwen and Llama families): ``out[i] = x[i] cos_i - x[i + dim / 2] sin_i``,
    ``out[i + dim / 2] = x[i + dim / 2] cos_i + x[i] sin_i``."""
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)
