"""Gated DeltaNet mixer (Yang et al., "Gated Delta Networks", arXiv:2412.06464;
as ``modeling_qwen3_next.py`` computes it), the linear-attention layer of the
Qwen3-Next hybrids (``models/qwen3_next.py``).

For an input ``u [T, d_model]``, with ``Hk`` key heads of width ``dk``, ``Hv``
value heads of width ``dv`` (value head ``h`` reads key head ``h // (Hv /
Hk)``) and a causal depthwise convolution of ``K`` taps without a bias:

    [q | k | v | z] = u W_qkvz     widths Hk dk, Hk dk, Hv dv, Hv dv
    [b | a] = u W_ba               widths Hv, Hv
    [q | k | v] = silu(conv_K([q | k | v]))    over time, a channel at a time
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)      float32
    q = l2norm(q) / sqrt(dk),  k = l2norm(k)
    S' = exp(g_t) S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t
    out = concat_heads(o_t / sqrt(mean o_t^2 + eps) * w * silu(z_t)) W_out

``S`` is a ``[dk, dv]`` *matrix* a value head, corrected at every position by
a rank-one term that depends on what it already holds of ``k_t`` (the delta
rule), which ``Mamba2Mixer``'s diagonal decay is not.  With the
convolution's last ``K - 1`` inputs it is all the layer keeps of the past: a
state of fixed size, whatever the length.

A whole sequence (``_apply``, ``decode_prefill``) runs the chunked form, in
chunks of ``chunk`` positions (the source's 64).  Inside a chunk, with the
decays' running sum ``c_i`` and ``A[i, j] = beta_i (k_i . k_j) exp(c_i -
c_j)`` for ``j < i``, the corrections of all its positions solve one unit
lower triangular system, ``(I + A) [W | U] = [beta k exp(c) | beta v]``
(float32, by the inverse as a product: ``_unit_lower_inverse``); then a scan over the chunks carries ``S [Hv, dk, dv]``: ``v_new
= U - W S``, ``o = (q exp(c)) S + ((q k^T) * decay, causal) v_new``, ``S =
exp(c_last) S + (k exp(c_last - c))^T v_new``.  Decays, the system and the
state are float32; the other products take compute-dtype operands with
float32 accumulation.  The chunked form's operations carry the name
``gdn_chunk`` (``jax.named_scope``) in the compiled program's ``op_name``
(a profiler's device events do not show it: PERF.md Open question 30).
``decode_step``
is the recurrence itself in float32, one position a row.

``v_heads_held`` / ``k_heads_held``: a tensor-parallel share holds the first
value heads with their key heads: those columns of ``W_qkvz`` and ``W_ba``,
channels of the convolution and rows of ``W_out``; its output is that share's
term of the sum (the gated norm is over one head's ``dv``, so it is whole).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..common import get_policy
from .initialization import compute_fans, default_weight_init
from .mamba import (causal_conv, causal_windows, conv_tail, matmul_f32,
                    real_positions)
from .module import Module, StateLeaf, prefill_rows, write_prompt_rows

__all__ = ["GatedDeltaNet"]

F32 = jnp.float32


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A [..., Q, Q]``,
    float32: ``A`` is nilpotent, so the inverse is the finite sum of the
    powers of ``-A``, gathered by squaring: ``(I - A)(I + A^2)(I + A^4)...``
    (five squarings for 64 rows: matrix products, where the compiler's own
    triangular solve took a tenth of a 1,024-token prefill, PERF.md PR
    41)."""
    Q = A.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    P = -A
    inv = jnp.eye(Q, dtype=F32) + P
    span = 2
    while span < Q:
        P = mm(P, P)
        inv = inv + mm(inv, P)
        span *= 2
    return inv


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


class GatedDeltaNet(Module):
    """[B, T, d_model] -> [B, T, d_model] (module docstring)."""

    PARAM_ROLES = {"in_qkvz": "kernel_in", "in_ba": "kernel_in",
                   "out_proj": "kernel_out", "norm": "norm_scale",
                   "*": "elementwise"}

    def __init__(self, d_model: int, k_heads: int, v_heads: int,
                 k_head_dim: int, v_head_dim: int, conv_kernel: int = 4,
                 chunk: int = 64, v_heads_held: Optional[int] = None,
                 k_heads_held: Optional[int] = None, eps: float = 1e-6):
        super().__init__()
        hv = v_heads if v_heads_held is None else v_heads_held
        hk = k_heads if k_heads_held is None else k_heads_held
        if v_heads % k_heads or hv * k_heads != hk * v_heads:
            raise ValueError(f"{hv} of {v_heads} value heads do not go with "
                             f"{hk} of {k_heads} key heads")
        self.d_model, self.dk, self.dv = d_model, k_head_dim, v_head_dim
        self.hk, self.hv = hk, hv
        self.conv_kernel, self.chunk, self.eps = conv_kernel, chunk, eps
        self.key_dim, self.value_dim = hk * k_head_dim, hv * v_head_dim
        self.conv_dim = 2 * self.key_dim + self.value_dim

    def _init(self, rng):
        """``A`` uniform in (0, 16) and ``dt_bias`` ones, the source's
        initialisation."""
        ks = jax.random.split(rng, 5)
        dt = get_policy().param_dtype
        winit = self.weight_initializer or default_weight_init

        def w(k, shape):
            fi, fo = compute_fans(shape)
            return winit(k, shape, fi, fo, dt)

        return {"in_qkvz": w(ks[0], (self.d_model,
                                     self.conv_dim + self.value_dim)),
                "in_ba": w(ks[1], (self.d_model, 2 * self.hv)),
                "conv_weight": jax.random.uniform(
                    ks[2], (self.conv_kernel, self.conv_dim), dt,
                    -self.conv_kernel ** -0.5, self.conv_kernel ** -0.5),
                "dt_bias": jnp.ones((self.hv,), dt),
                "A_log": jnp.log(jax.random.uniform(
                    ks[3], (self.hv,), F32, 1e-3, 16.0)).astype(dt),
                "norm": jnp.ones((self.dv,), dt),
                "out_proj": w(ks[4], (self.value_dim, self.d_model))}

    # -- the pieces ------------------------------------------------------

    _mm = staticmethod(matmul_f32)

    def _project(self, params, u):
        """u [..., d_model] -> qkv [..., channels] (before the convolution,
        compute dtype), z [..., Hv dv] (compute dtype), beta [..., Hv] and
        the log decay g [..., Hv] (float32)."""
        c = get_policy().compute_dtype
        y = self._mm(u, params["in_qkvz"])
        ba = self._mm(u, params["in_ba"])
        beta = jax.nn.sigmoid(ba[..., :self.hv])
        g = -jnp.exp(params["A_log"].astype(F32)) * jax.nn.softplus(
            ba[..., self.hv:] + params["dt_bias"].astype(F32))
        return (y[..., :self.conv_dim].astype(c),
                y[..., self.conv_dim:].astype(c), beta, g)

    def _split(self, qkv):
        """The convolved channels (float32) -> q, k [..., Hv, dk] (normed,
        q scaled, each key head repeated for its value heads), v [..., Hv,
        dv]."""
        lead = qkv.shape[:-1]
        a, r = self.key_dim, self.hv // self.hk
        q = _l2norm(qkv[..., :a].reshape(lead + (self.hk, self.dk))) \
            * self.dk ** -0.5
        k = _l2norm(qkv[..., a:2 * a].reshape(lead + (self.hk, self.dk)))
        v = qkv[..., 2 * a:].reshape(lead + (self.hv, self.dv))
        return jnp.repeat(q, r, axis=-2), jnp.repeat(k, r, axis=-2), v

    def _out(self, params, o, z):
        """o [..., Hv, dv] float32 and the gate z [..., Hv dv] -> [...,
        d_model]: RMSNorm over a head's dv, its weight, then the gate."""
        c = get_policy().compute_dtype
        lead = z.shape[:-1]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + self.eps) * params["norm"].astype(F32)
        y = o.reshape(lead + (self.value_dim,)) * jax.nn.silu(z.astype(F32))
        return self._mm(y, params["out_proj"]).astype(c)

    def _chunked(self, q, k, v, beta, g):
        """The chunked delta rule from a zero state: q, k [B, T, H, dk], v
        [B, T, H, dv], beta, g [B, T, H] (float32; T a multiple of the
        chunk) -> (o [B, T, H, dv] float32, S [B, H, dk, dv] float32 after
        the last position)."""
        c = get_policy().compute_dtype
        B_, T, H, _ = q.shape
        Q = self.chunk
        n = T // Q
        # [B, H, n, Q, ...]
        ch = lambda a: a.reshape((B_, n, Q) + a.shape[2:]) \
            .transpose((0, 3, 1, 2) + tuple(range(4, a.ndim + 1)))
        q, k, v, beta, g = ch(q), ch(k), ch(v), ch(beta), ch(g)
        cum = jnp.cumsum(g, axis=-1)                            # <= 0
        low = jnp.tril(jnp.ones((Q, Q), bool))
        seg = cum[..., :, None] - cum[..., None, :]
        decay = jnp.where(low, jnp.exp(jnp.where(low, seg, 0.0)), 0.0)
        kb = k * beta[..., None]
        kk = jnp.einsum("bhnik,bhnjk->bhnij", kb.astype(c), k.astype(c),
                        preferred_element_type=F32)
        A = jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1), kk * decay, 0.0)
        rhs = jnp.concatenate([kb * jnp.exp(cum)[..., None],
                               v * beta[..., None]], axis=-1)
        solved = jnp.matmul(_unit_lower_inverse(A), rhs,
                            precision=jax.lax.Precision.HIGHEST)
        W, U = solved[..., :self.dk], solved[..., self.dk:]
        qk = jnp.einsum("bhnik,bhnjk->bhnij", q.astype(c), k.astype(c),
                        preferred_element_type=F32) * decay
        q_in = q * jnp.exp(cum)[..., None]
        k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]
        whole = jnp.exp(cum[..., -1])                           # [B, H, n]

        def carry(S, a):
            W_c, U_c, qk_c, q_c, k_c, whole_c = a
            v_new = U_c - jnp.einsum("bhik,bhkv->bhiv", W_c.astype(c),
                                     S.astype(c), preferred_element_type=F32)
            o = jnp.einsum("bhik,bhkv->bhiv", q_c.astype(c), S.astype(c),
                           preferred_element_type=F32) \
                + jnp.einsum("bhij,bhjv->bhiv", qk_c.astype(c),
                             v_new.astype(c), preferred_element_type=F32)
            S = S * whole_c[..., None, None] \
                + jnp.einsum("bhik,bhiv->bhkv", k_c.astype(c),
                             v_new.astype(c), preferred_element_type=F32)
            return S, o

        first = lambda a: jnp.moveaxis(a, 2, 0)
        last, o = jax.lax.scan(
            carry, jnp.zeros((B_, H, self.dk, self.dv), F32),
            tuple(first(a) for a in (W, U, qk, q_in, k_out, whole)))
        # [n, B, H, Q, dv] -> [B, T, H, dv]
        return o.transpose(1, 0, 3, 2, 4).reshape(B_, T, H, self.dv), last

    def _scan(self, params, u, length=None):
        """u [B, T, d_model] from a zero state; row b's positions ``>=
        length[b]`` (traced, a scalar for every row; None: all real) move
        nothing.  Returns (out [B, T, d_model], state [B, Hv, dk, dv]
        float32 after each row's last real position, qkv [B, T, channels]
        before the convolution)."""
        T, K, Q = u.shape[1], self.conv_kernel, self.chunk
        qkv, z, beta, g = self._project(params, u)
        q, k, v = self._split(causal_conv(causal_windows(qkv, K),
                                          params["conv_weight"]))
        if length is not None:
            # a pad has g = 0 and beta = 0: its decay is exp(0) = 1, its
            # row of the system is the identity's and its correction zero,
            # so the scan's last state is the state after position
            # length - 1
            real = real_positions(length, T)
            beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        pad = -T % Q
        if pad:
            # whole chunks; the added positions have g = 0 and beta = 0 too
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (q, k, v))
            beta, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                       for a in (beta, g))
        with jax.named_scope("gdn_chunk"):
            o, last = self._chunked(q, k, v, beta, g)
        return self._out(params, o[:, :T], z), last, qkv

    def _apply(self, params, x):
        return self._scan(params, x)[0]

    # -- incremental decoding ------------------------------------------

    def decode_state(self, rows: int, length: int):
        """Two leaves of fixed size a row (``length_axis`` None): the matrix
        state ``ssm [rows, Hv, dk, dv]``, float32 whatever the cache's dtype
        (it is a running sum), and the convolution's last ``K - 1`` inputs
        ``conv [rows, K - 1, channels]``."""
        return {"ssm": StateLeaf((rows, self.hv, self.dk, self.dv), None,
                                 "ssm_state", F32),
                "conv": StateLeaf((rows, self.conv_kernel - 1,
                                   self.conv_dim), None, "latent_cache")}

    def decode_prefill(self, params, x, cache, slot, length):
        """x [n, P, d_model], a group of prompts, of row i ``length[i]``
        positions real: the chunked form from a zero state, whatever the
        slots held; a row's pads move nothing (``_scan``); its convolution's
        window is inputs ``length - K + 1 .. length - 1`` (zeros before the
        start); both leaves of row ``slot[i]`` are written whole (a fill-up
        row's not at all: ``write_prompt_rows``)."""
        slot, length = prefill_rows(x, slot, length)
        y, ssm, qkv = self._scan(params, x, length)
        tail = conv_tail(qkv, length, self.conv_kernel)
        return y, {"ssm": write_prompt_rows(cache["ssm"], slot, ssm),
                   "conv": write_prompt_rows(cache["conv"], slot, tail)}

    def decode_step(self, params, x, cache, pos):
        """x [S, 1, d_model]: the recurrence in float32, one position a row,
        both leaves updated in place under the step's donation.  What the
        old state holds of ``k`` and of ``q`` is read in one pass (``o = S_t^T
        q = exp(g) S^T q + delta (k . q)``, so the output needs no pass over
        the new state); the update is the second.  ``pos`` is not read: the
        state carries the order, and an idle row may write anything (its
        slot's next prefill overwrites the row whole)."""
        qkv, z, beta, g = self._project(params, x[:, 0])
        window = jnp.concatenate(
            [cache["conv"], qkv[:, None].astype(cache["conv"].dtype)], axis=1)
        q, k, v = self._split(causal_conv(window, params["conv_weight"]))
        S_ = cache["ssm"].astype(F32)
        decay = jnp.exp(g)[..., None]                           # [S, Hv, 1]
        Sk = jnp.sum(S_ * k[..., None], axis=-2)                # [S, Hv, dv]
        Sq = jnp.sum(S_ * q[..., None], axis=-2)
        delta = beta[..., None] * (v - decay * Sk)
        S_ = S_ * decay[..., None] + k[..., None] * delta[..., None, :]
        o = decay * Sq + delta * jnp.sum(k * q, axis=-1, keepdims=True)
        return self._out(params, o, z)[:, None], {
            "ssm": S_.astype(cache["ssm"].dtype), "conv": window[:, 1:]}
