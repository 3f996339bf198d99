"""Attention whose heads are rotated whole (``RotaryAttention``) and, under
a window, whose keys and values are a ring (``WindowAttention``): the two
kinds of layer of a model that mixes window and full attention (the Mellum 2
family: three sliding layers to one full one).

Both are ``MultiHeadAttention`` with the same leaves ``wq, wk, wv, wo`` and
the same projections, ``_attend`` and base ``decode_step``; what differs is
written here, so the plain layer's program is what it was.

* Rotary positions over the whole head, pairs ``(x[i], x[i + D / 2])``
  (``nn/rotary.apply_rope_half``), at frequencies ``rope_inv_freq(D, theta,
  scaling)``: plain, or YaRN-blended where ``scaling`` is the published
  group.  ``attention_factor`` multiplies cos and sin (YaRN's temperature:
  both the query and the key carry it, so a score carries its square).
* ``RotaryAttention``: every earlier key.  Its state is the base's (a key
  and a value a position, a leaf that grows).  A prefill attends in blocks
  of ``QUERY_BLOCK`` queries, each over the keys up to its own end, so the
  float32 scores are ``[heads, block, <= P]`` and never ``[heads, P, P]``.
* ``WindowAttention(window=W)``: a query at position ``p`` reads keys ``p -
  W + 1 .. p``.  What a decoder keeps is the last ``W`` positions' keys and
  values, a **ring** ``[rows, W, H_kv * D]`` of fixed size a row
  (``StateLeaf(..., length_axis=None)``): position ``p`` lives at ring row
  ``p mod W``, so ring row ``j`` at a step whose newest position is ``p``
  holds position ``p - ((p - j) mod W)``, which is real iff it is not
  negative.  A step writes one row and reads the ring under that mask
  (exact-zero weight where it is false).  A prefill attends under the band
  in blocks of queries over the key blocks the band reaches, cost ``P x W``
  and not ``P^2``, and writes a row's ring whole from the prompt's last
  ``min(length, W)`` *real* positions, zeros elsewhere: no pad and no
  earlier occupant's row is left in it.  ``_apply`` is the same band over
  ``[B, T, E]``.

The two reads carry the scope names ``full_attn`` and ``window_attn``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .attention import MultiHeadAttention, _write_rows
from .module import StateLeaf, prefill_rows, write_prompt_rows
from .rotary import apply_rope_half, rope_angles, rope_inv_freq

__all__ = ["RotaryAttention", "WindowAttention"]


class RotaryAttention(MultiHeadAttention):
    """Causal grouped-query attention over ``[B, T, E]`` whose query and key
    heads are rotated whole by their position (module docstring);
    ``rope_scaling`` is the published YaRN group or None, and
    ``attention_factor`` the factor on cos and sin."""

    #: queries of a prompt attend in blocks of this many
    QUERY_BLOCK = 512
    SCOPE = "full_attn"
    #: how many keys back a query reads, its own among them (None: all)
    window: Optional[int] = None

    def __init__(self, embed_dim: int, num_heads: int,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, rope_theta: float = 10000.0,
                 rope_scaling=None, attention_factor: float = 1.0):
        super().__init__(embed_dim, num_heads, causal=True,
                         with_bias=False, num_kv_heads=num_kv_heads,
                         head_dim=head_dim,
                         rope=(rope_theta, head_dim or embed_dim // num_heads))
        self.inv_freq = rope_inv_freq(self.head_dim, rope_theta, rope_scaling)
        self.attention_factor = float(attention_factor)

    def _shape(self, params, q, k, pos):
        """``q [..., H * D]`` and ``k [..., H_kv * D]`` at positions ``pos
        [...]``, every head turned whole; no gate."""
        H, G, D = self.num_heads, self.num_kv_heads, self.head_dim
        lead = q.shape[:-1]
        cos, sin = (a[..., None, :] * self.attention_factor
                    for a in rope_angles(pos, self.inv_freq))
        q = apply_rope_half(q.reshape(lead + (H, D)), cos, sin)
        k = apply_rope_half(k.reshape(lead + (G, D)), cos, sin)
        return q.reshape(lead + (H * D,)), k.reshape(lead + (G * D,)), None

    def _rotated(self, params, x):
        """x ``[B, T, E]``, whole sequences from position 0 -> q, k (both
        turned) and v."""
        q, k, v = (self._proj(params, x, n) for n in "qkv")
        q, k, _ = self._shape(params, q, k, jnp.arange(x.shape[1])[None])
        return q, k, v

    def _attend(self, q, k, v, mask, dtype):
        with jax.named_scope(self.SCOPE):
            return super()._attend(q, k, v, mask, dtype)

    def _band(self, q, k, v, dtype, length=None):
        """Whole sequences from position 0: ``q [B, T, H * D]`` over ``k, v
        [B, T, H_kv * D]`` under the mask ``0 <= q - k < window``, a block of
        queries at a time over the key blocks the band reaches.
        ``length`` (traced): no row has a real position at or past it;
        query blocks that hold none are not computed (zeros)."""
        T, n, W = q.shape[1], self.QUERY_BLOCK, self.window

        def attend(start, stop):
            lo = 0 if W is None else max(0, (start - W + 1) // n * n)
            at_q = jnp.arange(start, stop)[:, None]
            at_k = jnp.arange(lo, stop)[None, :]
            mask = at_k <= at_q
            if W is not None:
                mask &= at_q - at_k < W
            return self._attend(q[:, start:stop], k[:, lo:stop],
                                v[:, lo:stop], mask, dtype)

        if T <= n or T % n:
            return attend(0, T)
        blocks = []
        for start in range(0, T, n):
            if length is None or start == 0:
                blocks.append(attend(start, start + n))
            else:
                blocks.append(jax.lax.cond(
                    start < length,
                    lambda s=start: attend(s, s + n),
                    lambda: jnp.zeros((q.shape[0], n, q.shape[2]), dtype)))
        return jnp.concatenate(blocks, axis=1)

    def _kept(self, k, v, length):
        """What a prefill writes of prompts' keys and values ``[n, P, H_kv *
        D]``, row i's first ``length[i]`` real: here all P positions from
        position 0; the pads' rows are masked by every later step."""
        return k, v

    def decode_prefill(self, params, x, cache, slot, length):
        """x: [n, P, E], whole prompts from position 0 of which row i's
        first ``length[i]`` are real, row i entering cache row ``slot[i]``:
        the base's prefill with the scores in blocks (``_band``), over what
        the cache will hold: k and v in the cache's dtype."""
        slot, length = prefill_rows(x, slot, length)
        q, k, v = self._rotated(params, x)
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        new = {n: write_prompt_rows(cache[n], slot, a)
               for n, a in zip("kv", self._kept(k, v, length))}
        o = self._band(q, k, v, x.dtype, jnp.max(length))
        return self._proj(params, o, "o"), new


class WindowAttention(RotaryAttention):
    """``RotaryAttention`` under a window of ``window`` keys, the query's own
    among them; the decode state is a ring of ``window`` rows (module
    docstring)."""

    SCOPE = "window_attn"

    def __init__(self, embed_dim: int, num_heads: int, window: int,
                 **kwargs):
        super().__init__(embed_dim, num_heads, **kwargs)
        if window < 1:
            raise ValueError(f"window {window}")
        self.window = int(window)

    def _apply(self, params, x):
        q, k, v = self._rotated(params, x)
        return self._proj(params, self._band(q, k, v, x.dtype), "o")

    def decode_state(self, rows: int, length: int):
        """The last ``window`` positions' keys and values, ``[rows, window,
        H_kv * D]`` each whatever the length: position ``p`` at ring row ``p
        mod window``."""
        shape = (rows, self.window, self.num_kv_heads * self.head_dim)
        return {"k": StateLeaf(shape, None, "kv_cache"),
                "v": StateLeaf(shape, None, "kv_cache")}

    def _ring_row(self, pos):
        """The ring row that holds position ``pos``."""
        return pos % self.window

    def _ring_position(self, newest):
        """The position ring row ``j`` holds when ``newest [...]`` is the
        last position written: ``[..., window]``, negative where the row
        holds none yet."""
        newest = newest[..., None]
        return newest - self._ring_row(newest - jnp.arange(self.window))

    def _kept(self, k, v, length):
        """Row i's ring, whole: ring row ``j`` takes the key and value of
        the last real position that is ``j mod window``, zeros where there
        is none: no pad and no earlier occupant's row is left in it."""
        held = self._ring_position(length - 1)                 # [n, window]
        at = jnp.clip(held, 0, k.shape[1] - 1)[..., None]
        return tuple(jnp.where(held[..., None] >= 0,
                               jnp.take_along_axis(a, at, axis=1), 0)
                     for a in (k, v))

    def decode_step(self, params, x, cache, pos):
        """x: [S, 1, E], pos: [S]: each row's key and value land at ring row
        ``pos mod window`` by one scatter of S minor rows a leaf
        (``_write_rows``); the ring is read under the mask of the rows that
        hold a position, which ``pos`` alone decides."""
        pos = jnp.maximum(pos, 0)                 # an idle row: position 0
        q, k, v = (self._proj(params, x, n) for n in "qkv")
        q, k, _ = self._shape(params, q, k, pos[:, None])
        at = self._ring_row(pos)
        ck = _write_rows(cache["k"], at, k[:, 0])
        cv = _write_rows(cache["v"], at, v[:, 0])
        # rows that hold no position yet get EXACT zero weight; a prefill
        # wrote the ring whole, so no earlier occupant's row is in it
        mask = (self._ring_position(pos) >= 0)[:, None, None, None, :]
        o = self._attend(q, ck, cv, mask, x.dtype)
        return self._proj(params, o, "o"), {"k": ck, "v": cv}
