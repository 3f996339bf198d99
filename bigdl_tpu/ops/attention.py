"""Flash attention: a Pallas TPU kernel with a portable jnp fallback.

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
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  kv_len: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(1)          # query-block index
    j = pl.program_id(2)          # key-block index (innermost grid dim)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: key block strictly past the query block contributes nothing
    run = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        # the operands go to the MXU in the dtype they arrive in: bfloat16
        # in one pass (the products are exact in float32, so q @ k^T is the
        # sum the float32 cast would give), float32 at HIGHEST as before
        q, k, v = q_ref[0], k_ref[0], v_ref[0]       # [bq, d], [bk, d] x 2
        precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        # the scale multiplies the float32 scores: folded into q it would
        # round q
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * sm_scale                    # [bq, bk]
        kj = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(kj > qi, _NEG_INF, s)
        if kv_len % block_k:          # mask keys in the padded tail block
            s = jnp.where(kj >= kv_len, _NEG_INF, s)

        m_prev = m_scr[:]                            # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp(-inf - -inf) would be NaN; fully-masked blocks give m_new=-inf
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - m_new))
        # l sums the float32 p; only the MXU's operand is rounded to v's
        # dtype, as mha_reference rounds its probabilities
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


# Mosaic's scoped VMEM is 16 MiB by default (v5e; later chips have more) and
# the kernel asks for no more: a raised limit made the same blocks slower
# (PERF.md section 6, PR 28).  `_vmem_bytes` is kept under seven eighths.
_VMEM_BUDGET = 14 * 2 ** 20
_LANES = 128
_BLOCK_CAP = 1024          # both blocks: where the sweep's optimum lies
_BWD_BLOCK_Q = 128


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _vmem_bytes(block_q: int, block_k: int, D: int, dtype) -> int:
    """VMEM one grid step of `_flash_kernel` is reckoned to hold: the q, o,
    k, v tiles (double-buffered by the pipeline, the last dim padded to the
    128 lanes), the three float32 scratch buffers, and the score tile twice
    in float32 (s, p) and once in the operands' dtype (p as the MXU takes
    it).  An upper bound: the smallest limit Mosaic compiled eleven probes
    under (blocks 256-2,048, D 64-256, both dtypes) was 0.17-0.90 of it."""
    size = jnp.dtype(dtype).itemsize
    d = _round_up(D, _LANES)
    tiles = 2 * 2 * (block_q + block_k) * d * size
    scratch = block_q * (2 * _LANES + d) * 4
    scores = block_q * _round_up(block_k, _LANES) * (2 * 4 + size)
    return tiles + scratch + scores


def _choose_blocks(Tq: int, Tk: int, D: int, dtype) -> tuple[int, int]:
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
    unseen head size or dtype still compiles."""
    rows = 32 // jnp.dtype(dtype).itemsize     # 8 float32, 16 bfloat16

    def fit(T, cap, align):
        n_blocks = -(-T // cap)
        return _round_up(-(-T // n_blocks), align)

    block_q = fit(Tq, _BLOCK_CAP, rows)
    block_k = fit(Tk, _BLOCK_CAP, _LANES)
    while _vmem_bytes(block_q, block_k, D, dtype) > _VMEM_BUDGET:
        if block_q > rows and (block_q >= block_k or block_k == _LANES):
            block_q = _round_up(block_q // 2, rows)
        elif block_k > _LANES:
            block_k = _round_up(block_k // 2, _LANES)
        else:
            break       # one tile of each: nothing smaller exists
    return block_q, block_k


def _flash_pallas(q, k, v, *, causal: bool, sm_scale: float,
                  block_q: Optional[int], block_k: Optional[int],
                  interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    # an explicit block wins (cut to the sequence); one left to the rule is
    # a whole tile and may be longer than the sequence, which is padded
    chosen = _choose_blocks(Tq, Tk, D, q.dtype)
    block_q = chosen[0] if block_q is None else min(block_q, Tq)
    block_k = chosen[1] if block_k is None else min(block_k, Tk)

    # pad sequence lengths up to block multiples; padded keys are masked
    # inside the kernel, padded query rows are sliced off the output
    pq = (-Tq) % block_q
    pk = (-Tk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Tqp, Tkp = Tq + pq, Tk + pk

    qr = q.reshape(B * H, Tqp, D)
    kr = k.reshape(B * H, Tkp, D)
    vr = v.reshape(B * H, Tkp, D)

    grid = (B * H, Tqp // block_q, Tkp // block_k)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=Tk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        # inside a shard_map body (parallel/ring_attention.py's
        # all-to-all route) the output varies over whatever mesh axes the
        # queries do; outside one the set is empty
        out_shape=jax.ShapeDtypeStruct((B * H, Tqp, D), q.dtype,
                                       vma=jax.typeof(qr).vma),
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
    return out.reshape(B, H, Tqp, D)[:, :, :Tq, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Differentiable wrapper over the Pallas forward: pallas_call has no
    autodiff rule, so training through the kernel needs an explicit VJP.
    The backward is a blockwise recompute (`_flash_bwd_chunked`): a scan
    over query blocks rebuilds each block's probabilities and accumulates
    dQ/dK/dV, so BOTH directions stay linear-memory in sequence length."""
    return _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out = _flash_diff(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd_chunked(q, k, v, g, *, causal: bool, sm_scale: float,
                       block_q: int):
    """Standard flash-attention backward, scanned over query blocks.

    For each block (rows r0..r0+c) the dense-math identities
        P  = softmax(S),  S = scale * Qc K^T  (+ causal mask)
        dV += P^T dO;  dP = dO V^T;  dS = P * (dP - rowsum(dP .* P))
        dQc = scale * dS K;  dK += scale * dS^T Qc
    are evaluated with only a [c, Tk] score block live, carrying (dK, dV)
    through the scan — memory O(block_q * Tk), not O(Tq * Tk)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    c = min(block_q, Tq)
    pq = (-Tq) % c
    if pq:  # pad query rows; their dO is zero so they contribute nothing
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, 0), (0, pq), (0, 0)))
    n_blocks = (Tq + pq) // c
    qb = q.reshape(B, H, n_blocks, c, D)
    gb = g.reshape(B, H, n_blocks, c, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    col = jnp.arange(Tk)

    hi = jax.lax.Precision.HIGHEST  # float32 whatever the inputs' dtype:
    # for float32 inputs this is the forward's arithmetic; for bfloat16
    # ones the forward rounds p to bfloat16 for p @ v and this does not, so
    # it is the gradient of the same function to within that rounding

    def body(carry, idx_qc_gc):
        dk, dv = carry
        blk, qc, gc = idx_qc_gc
        qcf = qc.astype(jnp.float32)
        gcf = gc.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qcf, kf, precision=hi) * sm_scale
        if causal:
            row = blk * c + jnp.arange(c)
            s = jnp.where(row[:, None] >= col[None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)  # fully-masked rows
        p = jnp.exp(s - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(denom == 0.0, 1.0, denom)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, gcf, precision=hi)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gcf, vf, precision=hi)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dqc = jnp.einsum("bhqk,bhkd->bhqd", ds, kf, precision=hi) * sm_scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qcf,
                             precision=hi) * sm_scale
        return (dk, dv), dqc

    zeros = jnp.zeros((B, H, Tk, D), jnp.float32)
    vma = tuple(jax.typeof(kf).vma)
    if vma:  # inside a shard_map body the carry varies as k and v do
        zeros = jax.lax.pcast(zeros, vma, to="varying")
    (dk, dv), dq_blocks = jax.lax.scan(
        body, (zeros, zeros),
        (jnp.arange(n_blocks),
         jnp.moveaxis(qb, 2, 0), jnp.moveaxis(gb, 2, 0)))
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(B, H, Tq + pq, D)[:, :, :Tq]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_diff_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    # the scan's chunk is the caller's block_q, or the 128 rows it always
    # was: it holds float32 [B, H, chunk, Tk] score blocks, which the
    # forward's chosen block (up to 1,024 rows) would make eight times
    # the size
    return _flash_bwd_chunked(q, k, v, g, causal=causal, sm_scale=sm_scale,
                              block_q=block_q or _BWD_BLOCK_Q)


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
