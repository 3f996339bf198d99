"""Bucketed bf16 gradient wire.

The reference ships gradients between nodes as `FP16CompressedTensor`
**blocks** (parameters/AllReduceParameter.scala: the flat gradient is cut
into per-node slices and each slice compresses/reduces independently),
which is what lets its aggregation pipeline overlap with compute.  The
TPU-native analog: the train step casts gradients to the wire dtype so the
GSPMD all-reduce rides ICI at bf16 (optim/optimizer.py `_build_step`), but
per-LEAF — ~160 converts and ~160 reduce ops on a ResNet-50, each too
small to hide behind the backward tail.

`wire_cast` replaces that with size-capped buckets: grad leaves are cast
to the wire dtype, concatenated into 1-D buffers of at most
``BIGDL_TPU_WIRE_BUCKET_MB`` (wire bytes), and split back after the
round-trip to f32.  The cast is elementwise and concatenate/slice move
values verbatim, so the result is **bit-identical** to the per-leaf path —
only the program XLA schedules changes: a handful of bucket-sized converts
whose reductions a latency-hiding scheduler can issue while the backward
tail is still computing.  ``bucket_mb <= 0`` (the default) keeps the per-leaf
path byte-for-byte.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from ..utils import config as _config

__all__ = ["bucket_assignment", "bucket_count", "wire_cast",
           "wire_bucket_mb"]


def wire_bucket_mb() -> float:
    """The ``BIGDL_TPU_WIRE_BUCKET_MB`` knob: max wire-dtype megabytes per
    gradient bucket; 0 (default) = per-leaf wire cast (the legacy path)."""
    return _config.get_float("WIRE_BUCKET_MB", 0.0)


def bucket_assignment(sizes: List[int], itemsize: int,
                      cap_mb: float) -> List[List[int]]:
    """Greedy size-capped bucketing over leaves in tree order: consecutive
    leaves share a bucket until adding the next would exceed ``cap_mb``
    (wire bytes).  A single leaf larger than the cap gets its own bucket —
    never split, so the per-leaf numerics stay trivially identical."""
    cap_elems = max(1, int(cap_mb * (1 << 20) / max(itemsize, 1)))
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_elems = 0
    for i, n in enumerate(sizes):
        if cur and cur_elems + n > cap_elems:
            buckets.append(cur)
            cur, cur_elems = [], 0
        cur.append(i)
        cur_elems += n
    if cur:
        buckets.append(cur)
    return buckets


def bucket_count(tree, wire, bucket_mb: Optional[float] = None) -> int:
    """How many wire buckets :func:`wire_cast` will use for ``tree``
    (0 = per-leaf path: ``wire`` is None or bucketing is off).  This is
    the structural count the train step's compile card self-reports and
    ``tools/perf_gate.py`` exact-matches — computed from the SAME
    assignment ``wire_cast`` bakes into the program."""
    if wire is None:
        return 0
    if bucket_mb is None:
        bucket_mb = wire_bucket_mb()
    if bucket_mb <= 0:
        return 0
    sizes = [int(leaf.size) for leaf in jax.tree.leaves(tree)]
    if not sizes:
        return 0
    return len(bucket_assignment(sizes, jnp.dtype(wire).itemsize,
                                 bucket_mb))


def wire_cast(grads, wire, bucket_mb: Optional[float] = None,
              constraint=None):
    """Round-trip the gradient tree through the wire dtype.

    bucket_mb <= 0: the per-leaf ``astype(wire).astype(f32)`` map (exactly
    the legacy `_build_step` line).  bucket_mb > 0: the same cast computed
    through size-capped fused buckets (see module docstring) —
    bit-identical values, bucket-granular program.  `constraint` (e.g. a
    ZeRO `with_sharding_constraint`) is applied to each wire-dtype bucket
    so bucket shardings respect the strategy's slices."""
    if wire is None:
        return grads
    if bucket_mb is None:
        bucket_mb = wire_bucket_mb()
    if bucket_mb <= 0:
        return jax.tree.map(
            lambda g: g.astype(wire).astype(jnp.float32), grads)
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(g.size) for g in leaves]
    itemsize = jnp.dtype(wire).itemsize
    out = [None] * len(leaves)
    for bucket in bucket_assignment(sizes, itemsize, bucket_mb):
        parts = [leaves[i].astype(wire).reshape(-1) for i in bucket]
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if constraint is not None:
            buf = constraint(buf)
        buf32 = buf.astype(jnp.float32)
        off = 0
        for i in bucket:
            n = sizes[i]
            out[i] = jax.lax.slice(buf32, (off,), (off + n,)).reshape(
                leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)
