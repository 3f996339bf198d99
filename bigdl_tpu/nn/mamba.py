"""The selective state-space mixers of the hybrid models: Mamba-2
(``Mamba2Mixer``, ``models/nemotron.py``) and Mamba-1 (``MambaMixer``,
``models/jamba.py``), with what the recurrent layers share (this file's two
and ``nn/deltanet.GatedDeltaNet``): the causal convolution, its windows and
its tail, the real positions of a padded group, the projections' product.

**Mamba-2** (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060).
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

**Mamba-1** (Gu & Dao, "Mamba", arXiv:2312.00752, with the Jamba family's
norms on ``dt``, ``B`` and ``C``): ``MambaMixer``'s docstring.  Its decay
``exp(Delta_t[d] A[d, n])`` differs by channel *and* by state index, so no
choice of the chunked form's numbers gives it: a whole sequence is a true
scan over time (``ops/ssm.selective_scan``: a Pallas kernel on a TPU,
``lax.scan`` elsewhere).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import get_policy
from .initialization import compute_fans, default_weight_init
from .module import Module, StateLeaf, prefill_rows, write_prompt_rows
from .normalization import rms_norm
from ..ops.ssm import recur as ssm_recur, selective_scan

__all__ = ["Mamba2Mixer", "MambaMixer", "causal_conv", "causal_windows",
           "conv_tail", "matmul_f32", "real_positions"]

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


class MambaMixer(Module):
    """Mamba-1's selective state-space mixer, [B, T, d_model] -> [B, T,
    d_model], as the Jamba family runs it.  For an input ``u [T, d_model]``,
    ``d_inner`` channels, a state of ``N`` a channel, a step size through a
    projection of rank ``R`` and a causal depthwise convolution of ``K``
    taps:

        [x, z] = u W_in                    widths d_inner, d_inner
        x = silu(conv_K(x) + b)            over time, a channel at a time
        [dt, B, C] = x W_x                 widths R, N, N
        dt, B, C = RMSNorm_dt(dt), RMSNorm_B(B), RMSNorm_C(C)
        Delta = softplus(dt W_dt + b_dt)   [d_inner]
        A = -exp(A_log)                    [N, d_inner]
        h_t[n, d] = exp(Delta_t[d] A[n, d]) h_(t-1)[n, d]
                    + Delta_t[d] B_t[n] x_t[d]
        y_t[d] = sum_n h_t[n, d] C_t[n] + D[d] x_t[d]
        y = y * silu(z);  out = y W_out

    No bias but the convolution's and ``b_dt``.  ``h``, with the
    convolution's last ``K - 1`` inputs, is all the layer keeps of the past.

    **The axis order is this layer's own**: ``A_log`` and the state are kept
    ``[N, d_inner]`` (the published checkpoints keep ``A_log [d_inner,
    N]``), so that the channels lie in the 128 lanes and the 16 state
    indices in the sublanes: ``N`` = 16 in the minor axis would fill an
    eighth of a lane row, and the sum over ``n`` is then a sum of rows.

    A whole sequence (``_apply``, ``decode_prefill``) is a scan over time
    that carries ``h [B, N, d_inner]`` in float32
    (``ops/ssm.selective_scan``: a Pallas kernel on a TPU, ``lax.scan``
    elsewhere, one equation): what is laid out of a prompt is ``[B, T,
    d_inner]`` and ``[B, T, N]``, never ``[B, T, N, d_inner]``.
    Decay, state and sum are float32; the four products take compute-dtype
    operands with float32 accumulation.  ``decode_step`` is the recurrence
    itself, one position a row."""

    PARAM_ROLES = {"in_proj": "kernel_in", "out_proj": "kernel_out",
                   "conv_bias": "bias", "dt_norm": "norm_scale",
                   "B_norm": "norm_scale", "C_norm": "norm_scale",
                   "*": "elementwise"}

    def __init__(self, d_model: int, d_inner: int, state: int, dt_rank: int,
                 conv_kernel: int = 4, eps: float = 1e-6,
                 dt_range=(0.001, 0.1)):
        super().__init__()
        self.d_model, self.d_inner, self.state = d_model, d_inner, state
        self.dt_rank, self.conv_kernel = dt_rank, conv_kernel
        self.eps, self.dt_range = eps, dt_range

    def _init(self, rng):
        """Mamba-1's initialisation: ``A[n, d] = n + 1`` (``A_log`` its
        logarithm), ``Delta`` log-uniform in ``dt_range`` put through the
        inverse softplus into ``dt_bias``, ``D`` and the norms ones."""
        ks = jax.random.split(rng, 6)
        dt = get_policy().param_dtype
        winit = self.weight_initializer or default_weight_init

        def w(k, shape):
            fi, fo = compute_fans(shape)
            return winit(k, shape, fi, fo, dt)

        lo, hi = self.dt_range
        step = jnp.exp(jax.random.uniform(ks[0], (self.d_inner,), F32)
                       * (math.log(hi) - math.log(lo)) + math.log(lo))
        N, C, K = self.state, self.d_inner, self.conv_kernel
        return {"A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=F32)[:, None], (N, C))
                    ).astype(dt),
                "B_norm": jnp.ones((N,), dt), "C_norm": jnp.ones((N,), dt),
                "D": jnp.ones((C,), dt),
                "conv_bias": jnp.zeros((C,), dt),
                "conv_weight": jax.random.uniform(
                    ks[1], (K, C), dt, -K ** -0.5, K ** -0.5),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "dt_norm": jnp.ones((self.dt_rank,), dt),
                "dt_proj": w(ks[2], (self.dt_rank, C)),
                "in_proj": w(ks[3], (self.d_model, 2 * C)),
                "out_proj": w(ks[4], (C, self.d_model)),
                "x_proj": w(ks[5], (C, self.dt_rank + 2 * N))}

    # -- the pieces ------------------------------------------------------

    def _project(self, params, u):
        """u [..., d_model] -> x (before the convolution), z: [..., d_inner]
        each, compute dtype."""
        c = get_policy().compute_dtype
        y = matmul_f32(u, params["in_proj"]).astype(c)
        return y[..., :self.d_inner], y[..., self.d_inner:]

    def _conv(self, params, window):
        """window [..., K, d_inner], the last K inputs oldest first -> the
        convolution's output at the newest, through SiLU (float32)."""
        return causal_conv(window, params["conv_weight"],
                           params["conv_bias"])

    def _norm(self, params, name, v):
        return rms_norm(v, params[name], self.eps)

    def _selective(self, params, x):
        """The convolved channels x [..., d_inner] (float32) -> Delta [...,
        d_inner], B and C [..., N]: float32."""
        R, N = self.dt_rank, self.state
        dbc = matmul_f32(x, params["x_proj"])
        dt = self._norm(params, "dt_norm", dbc[..., :R])
        B = self._norm(params, "B_norm", dbc[..., R:R + N])
        C = self._norm(params, "C_norm", dbc[..., R + N:])
        delta = jax.nn.softplus(matmul_f32(dt, params["dt_proj"])
                                + params["dt_bias"].astype(F32))
        return delta, B, C

    def _decay(self, params):
        """A [N, d_inner] and D [d_inner], float32."""
        return -jnp.exp(params["A_log"].astype(F32)), params["D"].astype(F32)

    def _out(self, params, y, z):
        c = get_policy().compute_dtype
        return matmul_f32(y * jax.nn.silu(z.astype(F32)),
                          params["out_proj"]).astype(c)

    def _scan(self, params, u, length=None):
        """u [B, T, d_model] from a zero state; row b's positions ``>=
        length[b]`` (traced, a scalar for every row; None: all real) move
        nothing.  Returns (out [B, T, d_model], state [B, N, d_inner]
        float32 after each row's last real position, x [B, T, d_inner]
        before the convolution)."""
        T = u.shape[1]
        x_in, z = self._project(params, u)
        x = self._conv(params, causal_windows(x_in, self.conv_kernel))
        delta, Bm, Cm = self._selective(params, x)
        if length is not None:
            # a pad has Delta = 0: its decay is exp(0) = 1 and its input
            # term zero, so the scan's last state is the state after
            # position length - 1
            delta = jnp.where(real_positions(length, T), delta, 0.0)
        y, last = selective_scan(delta, x, Bm, Cm, *self._decay(params))
        return self._out(params, y, z), last, x_in

    def _apply(self, params, x):
        return self._scan(params, x)[0]

    # -- incremental decoding ------------------------------------------

    def decode_state(self, rows: int, length: int):
        """Two leaves of fixed size a row (``length_axis`` None): the
        recurrent state ``ssm [rows, N, d_inner]``, float32 whatever the
        cache's dtype (it is a running sum), and the convolution's last ``K
        - 1`` inputs ``conv [rows, K - 1, d_inner]``."""
        return {"ssm": StateLeaf((rows, self.state, self.d_inner), None,
                                 "ssm_state", F32),
                "conv": StateLeaf((rows, self.conv_kernel - 1,
                                   self.d_inner), None, "latent_cache")}

    def decode_prefill(self, params, x, cache, slot, length):
        """x [n, P, d_model], a group of prompts, of row i ``length[i]``
        positions real: the scan from a zero state, whatever the slots
        held; a row's pads move nothing (``_scan``); its convolution's
        window is inputs ``length - K + 1 .. length - 1`` (zeros before the
        start); both leaves of row ``slot[i]`` are written whole (a fill-up
        row's not at all: ``write_prompt_rows``)."""
        slot, length = prefill_rows(x, slot, length)
        y, ssm, x_in = self._scan(params, x, length)
        tail = conv_tail(x_in, length, self.conv_kernel)
        return y, {"ssm": write_prompt_rows(cache["ssm"], slot, ssm),
                   "conv": write_prompt_rows(cache["conv"], slot, tail)}

    def decode_step(self, params, x, cache, pos):
        """x [S, 1, d_model]: the recurrence, one position a row, both
        leaves updated in place under the step's donation.  ``pos`` is not
        read: the state carries the order, and an idle row may write
        anything (its slot's next prefill overwrites the row whole)."""
        x_in, z = self._project(params, x[:, 0])
        window = jnp.concatenate(
            [cache["conv"], x_in[:, None].astype(cache["conv"].dtype)],
            axis=1)
        xc = self._conv(params, window)
        delta, Bm, Cm = self._selective(params, xc)
        h, y = ssm_recur(cache["ssm"], delta, xc, Bm, Cm,
                         *self._decay(params))
        return self._out(params, y, z)[:, None], {
            "ssm": h.astype(cache["ssm"].dtype), "conv": window[:, 1:]}
