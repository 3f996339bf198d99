"""Mamba-1's selective scan over a whole prompt (``nn/mamba.MambaMixer``):

    h_t[n, d] = exp(Delta_t[d] A[n, d]) h_(t-1)[n, d]
                + Delta_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n h_t[n, d] C_t[n] + D[d] x_t[d]

from a zero state, for ``delta`` and ``x [rows, T, channels]``, ``B`` and ``C
[rows, T, N]``, ``A [N, channels]`` and ``D [channels]``, all float32.
Returns ``y [rows, T, channels]`` and the state after the last position
``[rows, N, channels]``.  A position whose ``delta`` is zero moves nothing
(decay ``exp(0)``, input term zero): that is how a prompt's pads are kept
out.

The decay differs by channel *and* by state index, so it does not factor out
of the sum over ``n`` and no matrix product takes the work: it is ``N x
channels`` exponentials, two products and a sum a position, then a reduction
over ``N``, strictly in order.

Two forms of the one equation, chosen by what the code can observe as
``ops/grouped`` chooses its product:

* on a TPU, for a channel count that is a multiple of the 128 lanes, the
  Pallas kernel ``selective_scan``: a grid over (row, block of channels,
  chunk of positions), the chunks innermost and in order.  A block's ``[N,
  block]`` state is the kernel's second output, whose block does not move
  with the chunk, so it stays in fast memory from chunk to chunk; each of
  ``delta``, ``x``, ``B``, ``C`` is read and ``y`` written once.  ``B`` and
  ``C`` go in ``[rows, T, N, 1]`` so that a position's ``[N, 1]`` column, the
  state index in the sublanes, is one leading index away.  (`jamba2.decode`,
  PR 45: 26 scans of a prefill call take 1.1 ms where the other form's loops
  of small fusions took 3.7.)
* elsewhere ``lax.scan`` over time that carries ``[rows, N, channels]``,
  ``_UNROLL`` positions to an iteration; it is also what the kernel is
  differentiated through and compared with in the tests.

Nothing ``[rows, T, N, channels]`` is laid out in either form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["recur", "selective_scan"]

F32 = jnp.float32

#: positions an iteration takes (the kernel's inner loop and ``lax.scan``'s)
_UNROLL = 8
#: positions a grid step of the kernel takes
_CHUNK = 64
#: channels a grid step takes, the widest that divides them: a position of
#: a block costs about 110 ns whatever the block's width (256 / 512 / 1,024
#: channels read 2.18 / 1.14 / 0.62 ms at ``[4, 256]`` on a v5e, PR 44)
_BLOCKS = (1024, 512, 256, 128)


def selective_scan(delta, x, B, C, A, D):
    """(y, last state): module docstring."""
    if jax.default_backend() == "tpu" and x.shape[-1] % _BLOCKS[-1] == 0:
        return _pallas(delta, x, B, C, A, D)
    return _scan(delta, x, B, C, A, D)


def recur(h, delta, x, B, C, A, D):
    """One position: h [rows, N, channels], delta and x [rows, channels], B
    and C [rows, N] -> (h', y [rows, channels])."""
    h = jnp.exp(delta[:, None, :] * A) * h \
        + (delta * x)[:, None, :] * B[:, :, None]
    return h, jnp.sum(h * C[:, :, None], axis=1) + D * x


def _scan(delta, x, B, C, A, D):
    rows, T, channels = x.shape
    Q = min(_UNROLL, T)
    pad = -T % Q
    # time first, whole chunks; the added positions have delta = 0
    seq = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))).swapaxes(0, 1)
           .reshape(((T + pad) // Q, Q) + a.shape[:1] + a.shape[2:])
           for a in (delta, x, B, C)]

    def step(h, at):
        ys = []
        for i in range(Q):
            h, y = recur(h, *(a[i] for a in at), A, D)
            ys.append(y)
        return h, jnp.stack(ys)

    last, y = lax.scan(step, jnp.zeros((rows,) + A.shape, F32), seq)
    return y.reshape((T + pad, rows, channels))[:T].swapaxes(0, 1), last


def _kernel(delta_ref, x_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    A, D = a_ref[...], d_ref[...]              # [N, block], [1, block]

    def group(g, h):
        # eight positions an iteration (a float32 tile's sublanes): their
        # exponentials do not wait for the state, only the product and the
        # sum behind them do
        base = pl.multiple_of(g * _UNROLL, _UNROLL)
        ds = delta_ref[0, pl.ds(base, _UNROLL), :]           # [8, block]
        xs = x_ref[0, pl.ds(base, _UNROLL), :]
        for i in range(_UNROLL):
            d, xv = ds[i:i + 1], xs[i:i + 1]                 # [1, block]
            h = jnp.exp(d * A) * h + (d * xv) * b_ref[0, base + i]
            y_ref[0, pl.ds(base + i, 1), :] = (
                jnp.sum(h * c_ref[0, base + i], axis=0, keepdims=True)
                + D * xv)
        return h

    h_ref[0] = lax.fori_loop(0, delta_ref.shape[1] // _UNROLL, group,
                             h_ref[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _pallas(delta, x, B, C, A, D, interpret: bool = False):
    """The Pallas form (``interpret``: the CPU tests)."""
    rows, T, channels = x.shape
    N = A.shape[0]
    block = next(b for b in _BLOCKS if channels % b == 0)
    chunk = min(_CHUNK, -(-T // _UNROLL) * _UNROLL)
    pad = -T % chunk
    # whole chunks; the added positions have delta = 0
    delta, x, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                      for a in (delta, x, B, C))
    Tp = T + pad
    seq = pl.BlockSpec((1, chunk, block), lambda b, j, t: (b, t, j))
    col = pl.BlockSpec((1, chunk, N, 1), lambda b, j, t: (b, t, 0, 0))
    y, last = pl.pallas_call(
        _kernel,
        grid=(rows, channels // block, Tp // chunk),
        in_specs=[seq, seq, col, col,
                  pl.BlockSpec((N, block), lambda b, j, t: (0, j)),
                  pl.BlockSpec((1, block), lambda b, j, t: (0, j))],
        out_specs=[seq,
                   pl.BlockSpec((1, N, block), lambda b, j, t: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((rows, Tp, channels), F32),
                   jax.ShapeDtypeStruct((rows, N, channels), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # the equation's operations, an exponential counted as one: a state
        # element takes Delta A, exp, dA h, (Delta x) B, their sum, h C and
        # its share of the sum over n (7); a channel Delta x, D x and one
        # more sum (3); the bytes are one pass
        cost_estimate=pl.CostEstimate(
            flops=rows * Tp * channels * (7 * N + 3),
            transcendentals=rows * Tp * N * channels,
            bytes_accessed=4 * (rows * Tp * (3 * channels + 2 * N)
                                + rows * N * channels)),
        interpret=interpret,
        # the kernel's name in the device trace
        name="selective_scan",
    )(delta, x, B[..., None], C[..., None], A, D[None, :])
    return y[:, :T], last


def _pallas_fwd(delta, x, B, C, A, D, interpret):
    return _pallas(delta, x, B, C, A, D, interpret), (delta, x, B, C, A, D)


def _pallas_bwd(interpret, operands, cotangents):
    return jax.vjp(_scan, *operands)[1](cotangents)


_pallas.defvjp(_pallas_fwd, _pallas_bwd)
