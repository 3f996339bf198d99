"""Flash attention: Pallas TPU kernels with a portable jnp fallback.

The reference (2017 BigDL) predates attention; this op underpins the net-new
long-context capabilities required of the rebuild (SURVEY.md §7 item 7 — SP /
ring attention) and the MultiHeadAttention layer.  Design follows the standard
online-softmax blockwise scheme: for each query block, stream key/value blocks
through VMEM, keeping running (max, sum, accumulator) statistics so the full
[Tq, Tk] score matrix never materializes in HBM.

On TPU the kernel tiles onto the MXU with (block_q x d) @ (d x block_k)
matmuls whose operands keep the dtype they arrive in: bfloat16 q, k, v go to
the MXU as bfloat16 (one pass; a product of two bfloat16 numbers is exact in
float32), float32 q, k, v as float32 at `Precision.HIGHEST`.  Both products
accumulate in float32, the softmax statistics are float32, and the
probabilities are rounded to v's dtype for `p @ v` — the arithmetic of
`mha_reference`.  The blocks are chosen from the shape (`_choose_blocks`).

The backward is Pallas too (`_flash_bwd_pallas`, kernels `flash_bwd_dkv` and
`flash_bwd_dq`): under differentiation the forward also leaves each row's
log-sum-exp, lane-dense float32 `[B * H, Tq]`, and the two kernels rebuild a
block's probabilities from it, one summing dK and dV of a key block over the
query blocks that see it, one dQ of a query block over the key blocks it
sees.  The same rule of arithmetic holds: operands in the dtype they arrive
in, P and dS rounded to it for the MXU as the forward rounds P, float32
accumulators; a block wholly above a causal diagonal is neither computed
nor fetched.

On CPU (tests / virtual meshes) we use the exact jnp reference instead —
same math, XLA-fused.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "mha_reference"]

_NEG_INF = float("-inf")


def mha_reference(q, k, v, *, causal: bool = False,
                  sm_scale: Optional[float] = None,
                  q_offset: int = 0, k_offset: int = 0):
    """Exact attention in plain jnp. q,k,v: [B, H, T, D].

    q_offset / k_offset give the global sequence positions of q[..,0,:] and
    k[..,0,:] — used by ring attention where each device holds a rotating
    key/value block.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    if causal:
        qi = q_offset + jnp.arange(q.shape[2])[:, None]
        kj = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(kj > qi, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    # rows with every position masked produce NaN from softmax(-inf row);
    # zero them (they are meaningless and must not poison gradients)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

def _precision(dtype):
    """The operands go to the MXU in the dtype they arrive in: bfloat16 in
    one pass (a product of two bfloat16 numbers is exact in float32, so
    q @ k^T is the sum the float32 cast would give), float32 at HIGHEST.
    Nothing but the dtype selects the arithmetic, forward or backward."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, contract, precision):
    """a @ b over the given axes of a and b, accumulated in float32."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def _hide(s, *, q0, k0, q_axis: int, causal: bool, kv_len: int,
          block_k: int):
    """The score tile `s` with -inf where its query may not see its key: a
    key past the query (causal) or in the padded tail of the last key
    block.  Queries run along `q_axis` of the tile from position `q0`, keys
    along the other axis from `k0`."""
    ragged = bool(kv_len % block_k)
    if not (causal or ragged):
        return s
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if causal:
        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        s = jnp.where(kj > qi, _NEG_INF, s)
    if ragged:
        s = jnp.where(kj >= kv_len, _NEG_INF, s)
    return s


def _seen(i, j, *, causal: bool, block_q: int, block_k: int):
    """Whether query block `i` sees any key of key block `j`: a block wholly
    above a causal diagonal contributes nothing and is not computed."""
    return (j * block_k <= (i + 1) * block_q - 1) if causal else True


def _flash_kernel(q_ref, k_ref, v_ref, *refs, sm_scale: float, causal: bool,
                  block_q: int, block_k: int, kv_len: int):
    """One (query block, key block) step of the forward.  `refs` are the
    outputs and then the scratch: `o_ref`, under differentiation `lse_ref`
    (the rows' log-sum-exp, a lane-dense `[1, block_q]` row), and the
    running max, sum and accumulator."""
    import jax.experimental.pallas as pl

    o_ref, *lse_refs, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(1)          # query-block index
    j = pl.program_id(2)          # key-block index (innermost grid dim)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_seen(i, j, causal=causal, block_q=block_q, block_k=block_k))
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]       # [bq, d], [bk, d] x 2
        precision = _precision(q.dtype)
        # the scale multiplies the float32 scores: folded into q it would
        # round q
        s = _dot(q, k, (1, 1), precision) * sm_scale           # [bq, bk]
        s = _hide(s, q0=i * block_q, k0=j * block_k, q_axis=0,
                  causal=causal, kv_len=kv_len, block_k=block_k)

        m_prev = m_scr[:]                            # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp(-inf - -inf) would be NaN; fully-masked blocks give m_new=-inf
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - m_new))
        # l sums the float32 p; only the MXU's operand is rounded to v's
        # dtype, as mha_reference rounds its probabilities
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot(
            p.astype(v.dtype), v, (1, 0), precision)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:]
        seen = l != 0.0                              # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / jnp.where(seen, l, 1.0)).astype(o_ref.dtype)
        for lse_ref in lse_refs:
            # a row that saw nothing keeps a finite statistic, so that the
            # backward's exp(-inf - lse) is 0 and not NaN
            lse = jnp.where(seen, m_scr[:] + jnp.log(l), 0.0)    # [bq, 1]
            lse_ref[0] = lse.reshape(1, block_q)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *,
                          sm_scale: float, causal: bool, block_q: int,
                          block_k: int, kv_len: int):
    """dK and dV of one key block, summed over the query blocks that can
    see it (the innermost grid dimension).  The tile is the transposed one,
    `[block_k, block_q]`: the rows' statistics are then lane-dense
    `[1, block_q]` rows as they are stored, and `P^T dO`, `dS^T Q` are
    plain products."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)          # key-block index
    i = pl.program_id(2)          # query-block index (innermost grid dim)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_seen(i, j, causal=causal, block_q=block_q, block_k=block_k))
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        precision = _precision(q.dtype)
        s = _dot(k, q, (1, 1), precision) * sm_scale           # [bk, bq]
        s = _hide(s, q0=i * block_q, k0=j * block_k, q_axis=1,
                  causal=causal, kv_len=kv_len, block_k=block_k)
        p = jnp.exp(s - lse_ref[0])                  # hidden: exp(-inf) = 0
        dv_scr[:] += _dot(p.astype(do.dtype), do, (1, 0), precision)
        dp = _dot(v, do, (1, 1), precision)                    # [bk, bq]
        ds = p * (dp - delta_ref[0])
        dk_scr[:] += _dot(ds.astype(q.dtype), q, (1, 0), precision)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, lse_scr, delta_scr, *,
                         sm_scale: float, causal: bool, block_q: int,
                         block_k: int, kv_len: int):
    """dQ of one query block, summed over the key blocks it can see (the
    innermost grid dimension).  The tile is `[block_q, block_k]` as in the
    forward, so the rows' statistics are turned into columns once a query
    block."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)          # query-block index
    j = pl.program_id(2)          # key-block index (innermost grid dim)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lse_scr[:] = lse_ref[0].reshape(block_q, 1)
        delta_scr[:] = delta_ref[0].reshape(block_q, 1)

    @pl.when(_seen(i, j, causal=causal, block_q=block_q, block_k=block_k))
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        precision = _precision(q.dtype)
        s = _dot(q, k, (1, 1), precision) * sm_scale           # [bq, bk]
        s = _hide(s, q0=i * block_q, k0=j * block_k, q_axis=0,
                  causal=causal, kv_len=kv_len, block_k=block_k)
        p = jnp.exp(s - lse_scr[:])                  # hidden: exp(-inf) = 0
        dp = _dot(do, v, (1, 1), precision)                    # [bq, bk]
        ds = p * (dp - delta_scr[:])
        dq_scr[:] += _dot(ds.astype(k.dtype), k, (1, 0), precision)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


# Mosaic's scoped VMEM is 16 MiB by default (v5e; later chips have more) and
# the kernels ask for no more: a raised limit made the same blocks slower
# (PERF.md section 6, PR 28).  `_vmem_bytes` is kept under seven eighths.
_VMEM_BUDGET = 14 * 2 ** 20
_LANES = 128
_BLOCK_CAP = 1024          # both blocks: where the forward's sweep's optimum lies


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _vmem_bytes(block_q: int, block_k: int, D: int, dtype,
                backward: bool = False) -> int:
    """VMEM one grid step is reckoned to hold.  Forward (`_flash_kernel`):
    the q, o, k, v tiles (double-buffered by the pipeline, the last dim
    padded to the 128 lanes), the three float32 scratch buffers, and the
    score tile twice in float32 (s, p) and once in the operands' dtype (p
    as the MXU takes it).  An upper bound: the smallest limit Mosaic
    compiled eleven probes under (blocks 256-2,048, D 64-256, both dtypes)
    was 0.17-0.90 of it.  Backward (the larger of the two kernels,
    `_flash_bwd_dkv_kernel`): the q, dO, k, v, dK, dV tiles, the two float32
    accumulators, the rows' statistics, and the score tile twice in float32
    (s that becomes p, dP that becomes dS) and once in the operands' dtype
    (p as the MXU takes it is spent on dV before dS is rounded): at
    1,024 x 1,024, D 64, bfloat16 it reckons 14.1 MiB, and Mosaic compiled
    that under its 16 (PERF.md section 6, PR 46)."""
    size = jnp.dtype(dtype).itemsize
    d = _round_up(D, _LANES)
    tile = block_q * _round_up(block_k, _LANES)
    if backward:
        tiles = 2 * 2 * (block_q + 2 * block_k) * d * size
        scratch = 2 * block_k * d * 4 + 2 * 2 * 8 * block_q * 4
        return tiles + scratch + tile * (2 * 4 + size)
    tiles = 2 * 2 * (block_q + block_k) * d * size
    scratch = block_q * (2 * _LANES + d) * 4
    return tiles + scratch + tile * (2 * 4 + size)


def _choose_blocks(Tq: int, Tk: int, D: int, dtype, *, grad: bool = False,
                   backward: bool = False) -> tuple[int, int]:
    """(block_q, block_k) for a `[.., Tq, D] x [.., Tk, D]` call.

    Large blocks keep the MXU fed (few, full grid steps), small ones skip
    more of a causal triangle: on a v5e the larger block won at every
    length and head size swept in bfloat16, up to the 1,024 x 1,024 the
    default scoped VMEM takes, and a wide key block beat a tall query block
    of the same area (PERF.md section 6, PR 28).  A length splits into the
    fewest blocks under the cap, of even size, rounded up to the tiling
    (query rows to the operands' sublane packing, keys to the 128 lanes of
    the score tile), so padding is under one tile a block and a short
    sequence is one block.  Whatever the shape, the blocks are halved,
    the query block first, until `_vmem_bytes` fits the budget, so an
    unseen head size or dtype still compiles.

    `grad`: the forward of a differentiated call, which leaves the rows'
    log-sum-exp; `backward`: the backward kernels, which hold more a step
    (`_vmem_bytes`).  In both the rows' statistics are lane-dense, so a
    query block that is a part of the length is whole 128-lane tiles."""
    rows = 32 // jnp.dtype(dtype).itemsize     # 8 float32, 16 bfloat16
    q_tile = _LANES if grad or backward else rows      # of a part's block

    def fit(T, tile):
        n_blocks = -(-T // _BLOCK_CAP)
        return _round_up(-(-T // n_blocks), tile)

    block_q = fit(Tq, q_tile if Tq > _BLOCK_CAP else rows)
    block_k = fit(Tk, _LANES)
    while _vmem_bytes(block_q, block_k, D, dtype, backward) > _VMEM_BUDGET:
        if block_q > q_tile and (block_q >= block_k or block_k == _LANES):
            block_q = _round_up(block_q // 2, q_tile)
        elif block_k > _LANES:
            block_k = _round_up(block_k // 2, _LANES)
        else:
            break       # one tile of each: nothing smaller exists
    return block_q, block_k


def _blocks_and_padding(q, k, block_q, block_k, **rule):
    """The blocks of a call and its operands' lengths padded to them.  An
    explicit block wins (cut to the sequence); one left to the rule is a
    whole tile and may be longer than the sequence."""
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    chosen = _choose_blocks(Tq, Tk, D, q.dtype, **rule)
    block_q = chosen[0] if block_q is None else min(block_q, Tq)
    block_k = chosen[1] if block_k is None else min(block_k, Tk)
    return block_q, block_k, _round_up(Tq, block_q), _round_up(Tk, block_k)


def _heads_padded(x, T: int):
    """[B, H, t, D] -> [B * H, T, D], the sequence padded with zeros."""
    B, H, t, D = x.shape
    if T > t:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, T - t), (0, 0)))
    return x.reshape(B * H, T, D)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret", "with_lse"))
def _flash_pallas(q, k, v, *, causal: bool, sm_scale: float,
                  block_q: Optional[int], block_k: Optional[int],
                  interpret: bool, with_lse: bool = False):
    """The forward kernel's call.  `with_lse`: also the rows' log-sum-exp,
    float32 `[B * H, Tq]`, for the backward."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    block_q, block_k, Tqp, Tkp = _blocks_and_padding(
        q, k, block_q, block_k, grad=with_lse)
    # padded keys are masked inside the kernel, padded query rows are
    # sliced off the output
    qr, kr, vr = _heads_padded(q, Tqp), _heads_padded(k, Tkp), \
        _heads_padded(v, Tkp)

    # inside a shard_map body (parallel/ring_attention.py's all-to-all
    # route) the outputs vary over whatever mesh axes the queries do;
    # outside one the set is empty
    vma = jax.typeof(qr).vma
    out_specs = [pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tqp, D), q.dtype, vma=vma)]
    if with_lse:
        # lane-dense: a [.., Tq, 1] column is padded to 128 lanes by the
        # device, 67 MB a layer of `gpt2m.train` for these 0.5
        out_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, 1, Tqp), jnp.float32, vma=vma))
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=Tk)
    out, *lse = pl.pallas_call(
        kernel,
        grid=(B * H, Tqp // block_q, Tkp // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        # the kernel's name for the device trace (where it reaches it:
        # benchmark/layer_metrics/flash_fwd_ms.train.py)
        name="flash_fwd",
    )(qr, kr, vr)
    out = out.reshape(B, H, Tqp, D)[:, :, :Tq, :]
    if with_lse:
        return out, lse[0][:, 0, :Tq]
    return out


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _flash_bwd_pallas(q, k, v, lse, delta, g, *, causal: bool,
                      sm_scale: float, block_q: Optional[int],
                      block_k: Optional[int], interpret: bool):
    """The standard flash-attention backward as two Pallas kernels.

    With `P = exp(S - lse)` rebuilt a block at a time from the forward's
    row statistics `lse` and with `delta = rowsum(dO * O)`, both float32
    `[B * H, Tq]` (`g` is dO):
        dV = P^T dO;  dP = dO V^T;  dS = P * (dP - delta)
        dK = scale * dS^T Q;  dQ = scale * dS K
    `flash_bwd_dkv` sums dK, dV of a key block over the query blocks,
    `flash_bwd_dq` sums dQ of a query block over the key blocks, each in
    float32 scratch written once a block.  A block wholly above a causal
    diagonal is neither computed nor fetched (its index is clamped to the
    nearest block that is).  P and dS go to the MXU in the operands' dtype,
    as the forward's P does."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    block_q, block_k, Tqp, Tkp = _blocks_and_padding(
        q, k, block_q, block_k, backward=True)
    nq, nk = Tqp // block_q, Tkp // block_k

    # padded query rows carry a zero dO (and so a zero delta, dP and dS),
    # padded keys are masked by kv_len
    rows = [jnp.pad(x.reshape(B * H, 1, Tq), ((0, 0), (0, 0), (0, Tqp - Tq)))
            for x in (lse, delta)]
    operands = (_heads_padded(q, Tqp), _heads_padded(k, Tkp),
                _heads_padded(v, Tkp), _heads_padded(g, Tqp), *rows)
    vma = jax.typeof(operands[0]).vma
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=Tk)

    def specs(q_of, k_of):
        """in_specs of (q, k, v, dO, lse, delta) given a grid step's query
        and key block."""
        at_q = pl.BlockSpec((1, block_q, D), lambda *at: (at[0], q_of(*at), 0))
        at_k = pl.BlockSpec((1, block_k, D), lambda *at: (at[0], k_of(*at), 0))
        row = pl.BlockSpec((1, 1, block_q), lambda *at: (at[0], 0, q_of(*at)))
        return [at_q, at_k, at_k, at_q, row, row]

    def first_q(b, j, i):       # grid (head, key block, query block)
        if not causal:
            return i
        return jnp.minimum(jnp.maximum(i, j * block_k // block_q), nq - 1)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **static),
        grid=(B * H, nk, nq),
        in_specs=specs(first_q, lambda b, j, i: j),
        out_specs=[pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
                   for _ in range(2)],
        out_shape=[jax.ShapeDtypeStruct((B * H, Tkp, D), x.dtype, vma=vma)
                   for x in (k, v)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)
                        for _ in range(2)],
        interpret=interpret,
        # the kernels' names for the device trace
        # (benchmark/layer_metrics/attn_bwd_ms.train.py)
        name="flash_bwd_dkv",
    )(*operands)

    def last_k(b, i, j):        # grid (head, query block, key block)
        if not causal:
            return j
        return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        grid=(B * H, nq, nk),
        in_specs=specs(lambda b, i, j: i, last_k),
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tqp, D), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)
    return (dq.reshape(B, H, Tqp, D)[:, :, :Tq],
            dk.reshape(B, H, Tkp, D)[:, :, :Tk],
            dv.reshape(B, H, Tkp, D)[:, :, :Tk])


def _heads_last(x):
    """[B, H, T, D] <-> [B, T, H, D]: the heads side by side in the lanes, as
    a caller's projections hold them (its own transposes cancel this one).
    What lives from the forward to the backward is kept so: as the kernels
    take it, `[.., T, D]`, the device pads a 64-wide head to 128 lanes, and
    with nothing between the two it keeps that copy, 1.8 GB more over
    `gpt2m.train`'s 24 layers (PERF.md section 6, PR 46)."""
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Differentiable wrapper over the Pallas forward: pallas_call has no
    autodiff rule, so training through the kernel needs an explicit VJP.
    Under differentiation the forward also leaves the rows' log-sum-exp;
    the backward is the two Pallas kernels of `_flash_bwd_pallas`, which
    rebuild each block's probabilities from it, so BOTH directions stay
    linear-memory in sequence length: q, k, v, the output and a float32
    `[B * H, Tq]` live from one to the other."""
    return _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, with_lse=True)
    return out, (*map(_heads_last, (q, k, v, out)), lse)


def _flash_diff_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    # the barrier ties the kept forms to the cotangent, so the kernels'
    # padded copies of them are made here, in the backward: without it the
    # compiler reuses the forward's copies, and it is those that live on
    (q, k, v, out, lse), g = jax.lax.optimization_barrier((res, g))
    # delta = rowsum(dO * O), from the kept form of O: one pass, no copy
    delta = jnp.sum(_heads_last(g).astype(jnp.float32)
                    * out.astype(jnp.float32), axis=-1)      # [B, Tq, H]
    B, Tq, H = delta.shape
    return _flash_bwd_pallas(
        *map(_heads_last, (q, k, v)), lse,
        delta.transpose(0, 2, 1).reshape(B * H, Tq), g, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False):
    """Blockwise (flash) attention.  q,k,v: [B, H, T, D] -> [B, H, Tq, D].

    block_q / block_k: None = chosen from (Tq, Tk, D, dtype) by
    `_choose_blocks`; an explicit value wins.  The operands' dtype decides
    the kernel's arithmetic (module docstring): nothing else selects it.

    use_pallas: None = the backend decides: the Pallas kernel on a TPU
    (the only path `gpt2m.train` can hold there: the jnp path keeps a
    float32 [B, H, T, T] score tensor a layer for the backward) and
    `mha_reference` elsewhere (the only one a CPU can take).  An explicit
    value wins: tests and `ring_attention` pass one.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        # which side ran is proven from the compiled program, never from
        # this line: chip_smoke.py looks for `tpu_custom_call` in the LM
        # step's text
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return _flash_diff(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret)
