"""Grouped matrix product: rows sorted by group, each group's run of rows
times that group's own matrix (a dropless expert layer's product,
parallel/expert.GatedMoE).

``lax.ragged_dot`` is the compiler's own and is what runs wherever it runs
well.  On the TPU it tiles the contraction and the output by the largest of
its tile sizes that divides them; where one of them is no multiple of 256
(an expert width of 1,856, a hidden size of 2,688) it falls to 128 x 128
tiles, a grid step moves 32 KB, and the steps' own cost is the product's time
(`nemo3.decode`, PR 32: 8.8 ms for 638 MB of weights that stream in 0.8).  And
it takes a group's matrix only as ``[k, n]``: a table whose ``n`` is no
multiple of the 128 lanes is kept by the device with ``k`` in the lanes, and
is copied whole before every product.  And where the groups are many and
small (`qwen3n.decode`, PR 41: 128 matrices of 2 MB, ``[2048, 512]`` and
``[512, 2048]``) it streams them at 91 % of the bandwidth for a decode step's
1,920 rows (0.33-0.36 ms a product in the step) but takes 0.83-0.96 ms for a
prompt's 2,560-10,240 rows, where the kernel below takes 0.46-0.64.  For
those shapes the product is the
Pallas grouped matmul that ships with jax (``megablox.gmm``) with tiles chosen
here from the shape: the whole contraction at once, up to 512 output columns,
128 rows (256 for a prompt's thousands), so a grid step moves megabytes; and
it reads a table stored ``[n, k]`` as it lies (``transposed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_matmul"]


#: a group's matrix at or under this many bytes is "small", and more rows
#: than this are a prompt's, not a decode step's (read on the chip, PR 41:
#: 1,920 rows of 192 slots x 10 choices stay on the ragged dot, a 256-token
#: prompt's 2,560 do not; `dsv2.decode`'s 15.7 MB matrices are not small)
_SMALL_GROUP_BYTES = 4 << 20
_FEW_ROWS = 2048


def _tiles(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn) for the Pallas kernel: the contraction whole (a table's
    tile is then ``k x tn``, 1-3 MB at these widths, and the accumulator is
    written once); the widest multiple of 128 up to 512 that divides ``n``,
    or 512 with a last partial tile; few rows for a decode step's hundreds,
    more for a prompt's thousands.  About 10 MB of the 16 MB a kernel may
    use, double buffers counted."""
    tn = next((t for t in (512, 384, 256) if n % t == 0), 512)
    return (128 if m <= 1024 else 256), k, tn


def grouped_matmul(x, w, sizes, *, transposed: bool = False):
    """``x [m, k]`` (rows sorted by group) times ``w [g, k, n]``, or ``[g,
    n, k]`` with ``transposed``; ``sizes [g]`` int32 rows a group.  Returns
    float32 ``[m, n]``; rows past ``sum(sizes)`` belong to no group and hold
    nothing that may be read.  The shape and the backend decide which
    product runs (module docstring): ``lax.ragged_dot`` where it tiles well
    or no TPU is there, the Pallas kernel on a TPU otherwise."""
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tiles_well = not transposed and k % 256 == 0 and n % 256 == 0
    # many small matrices under a prompt's thousands of rows (module text)
    if k * n * w.dtype.itemsize <= _SMALL_GROUP_BYTES and m > _FEW_ROWS:
        tiles_well = False
    if tiles_well or min(k, n) < 128 or jax.default_backend() != "tpu":
        return lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transposed else w,
                              sizes, preferred_element_type=jnp.float32)
    return _pallas(x, w, sizes, transposed)


def _pallas(x, w, sizes, transposed: bool, interpret: bool = False):
    """The Pallas grouped matmul with this module's tiles (``interpret``:
    the CPU tests)."""
    # the package's differentiable wrapper takes positional arguments only
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tm, tk, tn = _tiles(m, k, n)
    rows = -m % tm
    if rows:                # whole row tiles; the added rows are no group's
        x = jnp.pad(x, ((0, rows), (0, 0)))
    out = gmm(x, w, sizes, jnp.float32, (tm, tk, tn), None, None,
              transposed, interpret)
    return out[:m] if rows else out
