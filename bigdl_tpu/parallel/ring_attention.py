"""Ring attention: sequence/context parallelism over the mesh 'seq' axis.

Net-new capability vs the reference (SURVEY.md §5.7: BigDL handles sequence
length with a per-timestep host loop, `nn/Recurrent.scala:80-152`; no SP/CP
exists).  Here long sequences shard across devices and attention runs as a
ring: each device holds one query shard permanently and rotates key/value
shards around the ring with `jax.lax.ppermute` over ICI, accumulating
online-softmax partial results (running max / sum / accumulator), so the full
sequence never materializes on any one chip.

The per-step block attention is exact (same math as ops.attention); combining
across ring steps uses the standard log-sum-exp merge, so ring attention is
bit-comparable to full attention up to float reordering.

Also provided: `ulysses_attention` — the all-to-all alternative (DeepSpeed
Ulysses style): transpose sequence shards into head shards with
`lax.all_to_all`, run full-sequence attention on 1/N of the heads locally,
transpose back.  Cheaper in collectives (2 all-to-alls) when heads >= devices.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.compat import shard_map

__all__ = ["ring_attention", "ulysses_attention"]

_NEG_INF = float("-inf")


def _pvary(x, axes):
    """Mark x as device-varying over `axes` (shard_map VMA bookkeeping),
    skipping axes it already varies over."""
    try:
        already = jax.typeof(x).vma
    except (AttributeError, TypeError):
        already = frozenset()
    axes = tuple(a for a in axes if a not in already)
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def _pvary_like(x, ref):
    """Mark x varying over whatever axes `ref` varies over."""
    try:
        return _pvary(x, tuple(jax.typeof(ref).vma))
    except (AttributeError, TypeError):
        return x


_CHUNK = 512  # key-chunk size for the blockwise inner step


def _block_attn(q, k, v, sm_scale, causal, q_off, k_off):
    """One ring step: partial attention of local q vs one k/v block.

    q,k,v: [B, H, t, D].  Returns (o_unnorm [f32], m, l) with
    m,l: [B, H, t, 1] running-softmax statistics for this block alone.
    Memory stays O(t * chunk): keys stream through in _CHUNK-sized pieces
    (flash-style online softmax), never materializing the [t, t] score matrix.
    """
    B, H, t, D = q.shape
    tk = k.shape[2]
    chunk = min(_CHUNK, tk)
    pad = (-tk) % chunk
    if pad:  # padded keys are masked below via the kj >= tk test
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (tk + pad) // chunk
    kc = k.reshape(B, H, nc, chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, H, nc, chunk, D).transpose(2, 0, 1, 3, 4)
    qi = q_off + jnp.arange(t)[:, None]

    def step(carry, ckv):
        o, m, l = carry
        kb, vb, c = ckv
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
        kj = k_off + c * chunk + jnp.arange(chunk)[None, :]
        mask = (kj >= k_off + tk)
        if causal:
            mask = mask | (kj > qi)
        s = jnp.where(mask, _NEG_INF, s)
        m_b = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_b)
        safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - safe_m))
        alpha = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - safe_m))
        o = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                   vb.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return (o, m_new, l), None

    o0 = _pvary_like(jnp.zeros((B, H, t, D), jnp.float32), q)
    m0 = _pvary_like(jnp.full((B, H, t, 1), _NEG_INF, jnp.float32), q)
    l0 = _pvary_like(jnp.zeros((B, H, t, 1), jnp.float32), q)
    if nc == 1:
        (o, m, l), _ = step((o0, m0, l0), (kc[0], vc[0], jnp.int32(0)))
    else:
        (o, m, l), _ = jax.lax.scan(
            step, (o0, m0, l0), (kc, vc, jnp.arange(nc)))
    return o, m, l


def _ring_attn_local(q, k, v, *, axis_name: str, causal: bool,
                     sm_scale: float, vary_axes=()):
    """Runs inside shard_map: q,k,v are the LOCAL sequence shards [B,H,t,D]."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    t = q.shape[2]
    q_off = my * t

    # ring permutation: shard s lives on device (s + step) mod n — i.e. each
    # step we hand our current k/v block to the next device
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        k_blk, v_blk, o, m, l = carry
        k_off = ((my - s) % n) * t
        o_b, m_b, l_b = _block_attn(q, k_blk, v_blk, sm_scale, causal,
                                    q_off, k_off)
        # merge (o,m,l) <- (o_b,m_b,l_b): log-sum-exp combine
        m_new = jnp.maximum(m, m_b)
        safe = lambda a, mn: jnp.where(a == _NEG_INF, 0.0, jnp.exp(a - mn))
        a1 = jnp.where(m_new == _NEG_INF, 0.0, safe(m, m_new))
        a2 = jnp.where(m_new == _NEG_INF, 0.0, safe(m_b, m_new))
        o = o * a1 + o_b * a2
        l = l * a1 + l_b * a2
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o, m_new, l), None

    B, H, _, D = q.shape
    # mark the fresh accumulators as device-varying over every axis the
    # inputs vary over, so the scan carry types stay consistent across
    # iterations (shard_map VMA rule)
    axes = (axis_name,) + tuple(a for a in vary_axes if a != axis_name)
    o0 = _pvary(jnp.zeros((B, H, t, D), jnp.float32), axes)
    m0 = _pvary(jnp.full((B, H, t, 1), _NEG_INF, jnp.float32), axes)
    l0 = _pvary(jnp.zeros((B, H, t, 1), jnp.float32), axes)
    (k, v, o, m, l), _ = jax.lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l).astype(q.dtype)


def ring_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                   seq_axis: str = "seq", causal: bool = False,
                   sm_scale: Optional[float] = None,
                   batch_axis: Optional[str] = "data"):
    """Sequence-parallel exact attention.  q,k,v: [B, H, T, D] with T sharded
    over `seq_axis` (and optionally B over `batch_axis`).

    Outside a mesh context pass `mesh=`; returns [B, H, T, D] with the same
    sharding.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None:
        mesh = _current_mesh()
    # batch_axis: one name or a tuple (MeshLayout batches span data x
    # fsdp); absent axes drop out
    if batch_axis and not isinstance(batch_axis, (list, tuple)):
        batch_axis = (batch_axis,)
    batch = tuple(a for a in (batch_axis or ())
                  if a and a in mesh.axis_names) or None
    spec = P(batch, None, seq_axis, None)
    fn = shard_map(
        partial(_ring_attn_local, axis_name=seq_axis, causal=causal,
                sm_scale=sm_scale,
                vary_axes=batch or ()),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, sm_scale: float):
    """Inside shard_map: [B, H, t, D] seq-sharded -> all_to_all -> [B, H/n, T, D]
    head-sharded -> exact attention -> all_to_all back."""
    # split heads over the axis, gather sequence:  axis 1 scatters, axis 2 joins
    def fwd(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def rev(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = fwd(q), fwd(k), fwd(v)
    # flash attention keeps memory linear in the gathered sequence length
    # in BOTH directions (blockwise pallas forward, which leaves its row
    # statistics, + the blockwise pallas backward that rebuilds the
    # probabilities from them, ops/attention._flash_bwd_pallas)
    from ..ops.attention import flash_attention
    oh = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return rev(oh)


def ulysses_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                      seq_axis: str = "seq", causal: bool = False,
                      sm_scale: Optional[float] = None,
                      batch_axis: Optional[str] = "data"):
    """All-to-all sequence parallelism (heads must divide the seq-axis size)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None:
        mesh = _current_mesh()
    n = mesh.shape[seq_axis]
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by |{seq_axis}|={n}")
    batch = batch_axis if (batch_axis and batch_axis in mesh.axis_names) \
        else None
    spec = P(batch, None, seq_axis, None)
    fn = shard_map(
        partial(_ulysses_local, axis_name=seq_axis, causal=causal,
                sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _current_mesh() -> Mesh:
    """Mesh from the active `with mesh:` context if any, else Engine's."""
    from ..utils.engine import Engine
    try:  # private fallback, guarded: degrade to Engine.mesh() on jax changes
        env = jax._src.mesh.thread_resources.env
        if env.physical_mesh and not env.physical_mesh.empty:
            return env.physical_mesh
    except AttributeError:
        pass
    return Engine.mesh()
