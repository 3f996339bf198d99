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

It is ``lax.scan`` over time that carries ``[rows, N, channels]``,
``_UNROLL`` positions to an iteration, on every backend: nothing ``[rows, T,
N, channels]`` is laid out.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["recur", "selective_scan"]

F32 = jnp.float32

#: positions an iteration of the scan takes
_UNROLL = 8


def recur(h, delta, x, B, C, A, D):
    """One position: h [rows, N, channels], delta and x [rows, channels], B
    and C [rows, N] -> (h', y [rows, channels])."""
    h = jnp.exp(delta[:, None, :] * A) * h \
        + (delta * x)[:, None, :] * B[:, :, None]
    return h, jnp.sum(h * C[:, :, None], axis=1) + D * x


def selective_scan(delta, x, B, C, A, D):
    """(y, last state): module docstring."""
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
