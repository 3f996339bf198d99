"""Mamba-2 mixer (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060), the
state-space layer of the hybrid models (``models/nemotron.py``).

For an input ``u [T, d_model]``, with ``H`` heads of width ``P``, ``G``
groups of state size ``N`` (head ``h`` reads group ``h // (H / G)``) and a
causal depthwise convolution of ``K`` taps:

    [z, xBC, dt] = u W_in          widths H P, H P + 2 G N, H
    xBC = silu(conv_K(xBC) + b)    over time, a channel at a time
    [x, B, C] = split(xBC)         x [H, P];  B, C [G, N]
    dt = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t[h] = exp(dt_t[h] A[h]) S_(t-1)[h] + dt_t[h] x_t[h] (outer) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
    y = y * silu(z);  y = y * rsqrt(mean over each group's H P / G of y^2
                                    + eps) * w;   out = y W_out

``S`` is a ``[P, N]`` matrix a head, and with the convolution's last ``K -
1`` inputs it is all the layer keeps of the past: a state of fixed size,
whatever the length.

A whole sequence (``_apply``, ``decode_prefill``) runs the chunked form:
inside a chunk of ``chunk`` positions the masked product ``(C B^T) * L``
with the decay matrix ``L[i, j] = exp(sum_(j < k <= i) dt_k A)``, each
chunk's own state, and a scan over the chunks that carries ``[H, P, N]``.
Decays and states are float32, the products take compute-dtype operands
with float32 accumulation.  ``decode_step`` is the recurrence itself, one
position a row.

``heads_held``: a tensor-parallel share holds the first ``heads_held`` heads
with their ``G heads_held / H`` groups: those columns of ``W_in``, channels of
the convolution and rows of ``W_out``; its output is that share's term of the
sum (a group of the gated norm lies within a state group, so it is whole).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import get_policy
from .initialization import compute_fans, default_weight_init
from .module import Module, StateLeaf, prefill_rows, write_prompt_rows

__all__ = ["Mamba2Mixer", "causal_conv", "causal_windows", "conv_tail",
           "matmul_f32", "real_positions"]

F32 = jnp.float32


def matmul_f32(x, w):
    """``x @ w`` over the last axis: compute-dtype operands, float32
    accumulation and result (both recurrent layers' projections)."""
    c = get_policy().compute_dtype
    return jax.lax.dot_general(
        x.astype(c), w.astype(c), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=F32)


def causal_conv(window, weight, bias=None):
    """The causal depthwise convolution of the recurrent layers (this one
    and ``nn/deltanet.GatedDeltaNet``) at one position: ``window [..., K,
    channels]``, the last K inputs oldest first, times ``weight [K,
    channels]`` summed over the taps, plus ``bias`` where the layer has
    one, through SiLU (float32)."""
    y = jnp.sum(window.astype(F32) * weight.astype(F32), axis=-2)
    if bias is not None:
        y = y + bias.astype(F32)
    return jax.nn.silu(y)


def causal_windows(x, taps: int):
    """x [B, T, channels] -> [B, T, K, channels]: position t's window is
    inputs t-K+1..t, zeros before the start."""
    T = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jnp.stack([padded[:, k:k + T] for k in range(taps)], axis=2)


def real_positions(length, T: int):
    """``[B or 1, T, 1]`` boolean: row b's positions before ``length[b]``
    (traced; a scalar stands for every row)."""
    return (jnp.arange(T) < jnp.reshape(length, (-1, 1)))[..., None]


def conv_tail(x, length, taps: int):
    """What a convolution keeps of prompts ``x [B, T, channels]`` of which
    row b's first ``length[b]`` (traced; a scalar stands for every row)
    positions are real: inputs ``length - K + 1 .. length - 1``, zeros
    before the start, ``[B, K - 1, channels]``."""
    at = jnp.reshape(length, (-1, 1)) + jnp.arange(taps - 1)     # [B|1, K-1]
    at = jnp.broadcast_to(at, (x.shape[0], taps - 1))
    return jnp.take_along_axis(
        jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))), at[..., None], axis=1)


class Mamba2Mixer(Module):
    """[B, T, d_model] -> [B, T, d_model] (module docstring)."""

    PARAM_ROLES = {"in_proj": "kernel_in", "out_proj": "kernel_out",
                   "conv_bias": "bias", "norm": "norm_scale",
                   "*": "elementwise"}

    def __init__(self, d_model: int, heads: int, head_dim: int, groups: int,
                 state: int, conv_kernel: int = 4, chunk: int = 128,
                 heads_held: Optional[int] = None, eps: float = 1e-5,
                 dt_range=(0.001, 0.1), dt_floor: float = 1e-4):
        super().__init__()
        held = heads if heads_held is None else heads_held
        if heads % groups or (held * groups) % heads:
            raise ValueError(f"{held} of {heads} heads do not hold whole "
                             f"groups of {groups}")
        self.d_model, self.head_dim, self.state = d_model, head_dim, state
        self.heads, self.groups = held, held * groups // heads
        self.conv_kernel, self.chunk, self.eps = conv_kernel, chunk, eps
        self.dt_range, self.dt_floor = dt_range, dt_floor
        self.d_inner = self.heads * head_dim
        self.conv_dim = self.d_inner + 2 * self.groups * state

    def _init(self, rng):
        """The family's initialisation: ``A`` uniform in [1, 16], ``dt``
        log-uniform in ``dt_range`` floored at ``dt_floor`` and put through
        the inverse softplus into ``dt_bias``, ``D`` ones."""
        ks = jax.random.split(rng, 5)
        dt = get_policy().param_dtype
        winit = self.weight_initializer or default_weight_init

        def w(k, shape):
            fi, fo = compute_fans(shape)
            return winit(k, shape, fi, fo, dt)

        lo, hi = self.dt_range
        step = jnp.exp(jax.random.uniform(ks[2], (self.heads,), F32)
                       * (math.log(hi) - math.log(lo)) + math.log(lo))
        step = jnp.maximum(step, self.dt_floor)
        return {"in_proj": w(ks[0], (self.d_model, self.d_inner
                                     + self.conv_dim + self.heads)),
                "conv_weight": jax.random.uniform(
                    ks[1], (self.conv_kernel, self.conv_dim), dt,
                    -self.conv_kernel ** -0.5, self.conv_kernel ** -0.5),
                "conv_bias": jnp.zeros((self.conv_dim,), dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "A_log": jnp.log(jax.random.uniform(
                    ks[3], (self.heads,), F32, 1.0, 16.0)).astype(dt),
                "D": jnp.ones((self.heads,), dt),
                "norm": jnp.ones((self.d_inner,), dt),
                "out_proj": w(ks[4], (self.d_inner, self.d_model))}

    # -- the pieces ------------------------------------------------------

    _mm = staticmethod(matmul_f32)

    def _project(self, params, u):
        """u [..., d_model] -> z [..., H P], xBC [..., channels] (before the
        convolution, compute dtype), dt [..., H] (float32, before its
        bias)."""
        c = get_policy().compute_dtype
        y = self._mm(u, params["in_proj"])
        a, b = self.d_inner, self.d_inner + self.conv_dim
        return y[..., :a].astype(c), y[..., a:b].astype(c), y[..., b:]

    def _split(self, xbc):
        """The convolved channels -> x [..., H, P], B and C [..., G, N]."""
        a, n = self.d_inner, self.groups * self.state
        lead = xbc.shape[:-1]
        return (xbc[..., :a].reshape(lead + (self.heads, self.head_dim)),
                xbc[..., a:a + n].reshape(lead + (self.groups, self.state)),
                xbc[..., a + n:].reshape(lead + (self.groups, self.state)))

    def _steps(self, params, dt):
        """dt [..., H] -> (softplus(dt + dt_bias), A [H]), float32."""
        return (jax.nn.softplus(dt.astype(F32)
                                + params["dt_bias"].astype(F32)),
                -jnp.exp(params["A_log"].astype(F32)))

    def _out(self, params, y, z):
        """y [..., H, P] float32 and the gate z [..., H P] -> [..., d_model]:
        the gate first, then RMSNorm over each state group's channels."""
        c = get_policy().compute_dtype
        lead = z.shape[:-1]
        y = y.reshape(lead + (self.d_inner,)) * jax.nn.silu(z.astype(F32))
        g = y.reshape(lead + (self.groups, self.d_inner // self.groups))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + self.eps)
        y = g.reshape(lead + (self.d_inner,)) * params["norm"].astype(F32)
        return self._mm(y, params["out_proj"]).astype(c)

    def _conv(self, params, window):
        """window [..., K, channels], the last K inputs oldest first -> the
        convolution's output at the newest, through SiLU (float32)."""
        return causal_conv(window, params["conv_weight"],
                           params["conv_bias"])

    def _scan(self, params, u, length=None):
        """u [B, T, d_model] from a zero state; row b's positions ``>=
        length[b]`` (traced, a scalar for every row; None: all real) move
        nothing.  Returns (out [B, T, d_model], ssm state [B, H, P, N]
        float32 after each row's last real position, xBC [B, T, channels]
        before the convolution)."""
        c = get_policy().compute_dtype
        B_, T, _ = u.shape
        H, P, G, N, K, Q = (self.heads, self.head_dim, self.groups,
                            self.state, self.conv_kernel, self.chunk)
        z, xbc, dt = self._project(params, u)
        x, Bm, Cm = self._split(self._conv(params, causal_windows(xbc, K)))
        dt, A = self._steps(params, dt)
        if length is not None:
            # a pad has dt = 0: its decay is exp(0) = 1 and its input term
            # zero, so the scan's last state is the state after position
            # length - 1
            dt = jnp.where(real_positions(length, T), dt, 0.0)
        pad = -T % Q
        if pad:
            # whole chunks; the added positions have dt = 0 as well
            x, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for a in (x, Bm, Cm))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        n = (T + pad) // Q
        chunked = lambda a: a.reshape((B_, n, Q) + a.shape[2:])
        x, Bm, Cm, dt = chunked(x), chunked(Bm), chunked(Cm), chunked(dt)
        R = H // G
        # per group: heads [B, n, Q, G, R, ...]
        xg = (x * dt[..., None]).astype(c).reshape(B_, n, Q, G, R, P)
        cum = jnp.cumsum(dt * A, axis=2).reshape(B_, n, Q, G, R)  # <= 0
        Bc, Cc = Bm.astype(c), Cm.astype(c)
        # inside a chunk: y_i += sum_(j <= i) (C_i . B_j) exp(cum_i - cum_j)
        # dt_j x_j
        cb = jnp.einsum("bnigs,bnjgs->bngij", Cc, Bc,
                        preferred_element_type=F32)
        seg = cum[:, :, :, None] - cum[:, :, None, :]     # [B,n,i,j,G,R]
        tri = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
        L = jnp.where(tri[None, None, :, :, None, None],
                      jnp.exp(jnp.where(tri[None, None, :, :, None, None],
                                        seg, 0.0)), 0.0)
        M = (cb.transpose(0, 1, 3, 4, 2)[..., None] * L).astype(c)
        y = jnp.einsum("bnijgr,bnjgrp->bnigrp", M, xg,
                       preferred_element_type=F32)
        # each chunk's own state, decayed to the chunk's end
        to_end = jnp.exp(cum[:, :, -1:] - cum)             # [B,n,Q,G,R]
        own = jnp.einsum("bnjgrp,bnjgs->bngrps",
                         (xg.astype(F32) * to_end[..., None]).astype(c), Bc,
                         preferred_element_type=F32)
        whole = jnp.exp(cum[:, :, -1])                     # [B,n,G,R]

        def carry(S, a):
            own_c, whole_c = a
            return S * whole_c[..., None, None] + own_c, S

        last, before = jax.lax.scan(
            carry, jnp.zeros((B_, G, R, P, N), F32),
            (own.transpose(1, 0, 2, 3, 4, 5), whole.transpose(1, 0, 2, 3)))
        before = before.transpose(1, 0, 2, 3, 4, 5)        # [B,n,G,R,P,N]
        # what the chunks before add: C_i . S_before, decayed to position i
        y = y + jnp.einsum("bnigs,bngrps->bnigrp", Cc, before.astype(c),
                           preferred_element_type=F32) \
            * jnp.exp(cum)[..., None]
        y = y.reshape(B_, n * Q, H, P)[:, :T] \
            + params["D"].astype(F32)[:, None] \
            * x.reshape(B_, n * Q, H, P)[:, :T].astype(F32)
        return self._out(params, y, z), last.reshape(B_, H, P, N), xbc

    def _apply(self, params, x):
        return self._scan(params, x)[0]

    # -- incremental decoding ------------------------------------------

    def decode_state(self, rows: int, length: int):
        """Two leaves of fixed size a row (``length_axis`` None): the
        recurrent state ``ssm [rows, H, P, N]``, float32 whatever the
        cache's dtype (it is a running sum), and the convolution's last ``K
        - 1`` inputs ``conv [rows, K - 1, channels]``."""
        return {"ssm": StateLeaf((rows, self.heads, self.head_dim,
                                  self.state), None, "ssm_state", F32),
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
        y, ssm, xbc = self._scan(params, x, length)
        tail = conv_tail(xbc, length, self.conv_kernel)
        return y, {"ssm": write_prompt_rows(cache["ssm"], slot, ssm),
                   "conv": write_prompt_rows(cache["conv"], slot, tail)}

    def decode_step(self, params, x, cache, pos):
        """x [S, 1, d_model]: the recurrence, one position a row, both
        leaves updated in place under the step's donation.  ``pos`` is not
        read: the state carries the order, and an idle row may write
        anything (its slot's next prefill overwrites the row whole)."""
        R = self.heads // self.groups
        z, xbc, dt = self._project(params, x[:, 0])
        window = jnp.concatenate(
            [cache["conv"], xbc[:, None].astype(cache["conv"].dtype)], axis=1)
        xh, Bm, Cm = self._split(self._conv(params, window))
        dt, A = self._steps(params, dt)                      # [S, H]
        # a head's B and C are its group's; the state keeps the leaf's own
        # shape through the update: one pass in, one pass out
        Bh, Ch = jnp.repeat(Bm, R, axis=1), jnp.repeat(Cm, R, axis=1)
        S_ = cache["ssm"] * jnp.exp(dt * A)[..., None, None] \
            + (xh * dt[..., None])[..., None] * Bh[:, :, None, :]
        y = jnp.sum(S_ * Ch[:, :, None, :], axis=-1) \
            + params["D"].astype(F32)[:, None] * xh
        return self._out(params, y, z)[:, None], {
            "ssm": S_.astype(cache["ssm"].dtype), "conv": window[:, 1:]}
