"""Device-memory accounting for the smoke drills and the gate.

FSDP's whole value proposition is a MEMORY number — per-device
parameter+slot bytes dropping to ~1/N — and donation's is a PEAK number
(no second params+slots copy alive during the update).  Neither shows
up in images/sec, so the drills (tools/shard_smoke.py,
tools/pipeline_smoke.py, tools/perf_gate.py) read them explicitly
(satellite of ISSUE 9):

- :func:`live_device_bytes` — the live-buffer sum: every
  ``jax.live_arrays()`` leaf's addressable shards on one device.  No
  peak semantics, but deltas across a step still show donation working
  (a donated step leaves no second copy alive).
- :func:`tree_device_bytes` — one pytree's bytes on one device: the
  per-device parameter (or slot) footprint, == total/N under an FSDP=N
  layout and == total when replicated.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["live_device_bytes", "tree_device_bytes", "tree_total_bytes",
           "embedding_table_bytes", "compiled_memory_analysis"]


def _shard_bytes_on(leaf, device) -> int:
    """Bytes leaf `leaf` occupies on `device` (0 when absent there)."""
    if not hasattr(leaf, "addressable_shards"):
        return 0
    total = 0
    for s in leaf.addressable_shards:
        if s.device == device:
            total += int(s.data.nbytes)
    return total


def live_device_bytes(device=None) -> int:
    """Sum of all live jax.Array bytes resident on one device — the
    CPU-measurable stand-in for ``bytes_in_use``.  Deleted (donated)
    buffers are not live, so a donated train step shows here as NOT
    doubling params+slots."""
    dev = device or jax.devices()[0]
    total = 0
    for arr in jax.live_arrays():
        try:
            total += _shard_bytes_on(arr, dev)
        except Exception:  # noqa: BLE001 — a concurrently deleted array
            continue
    return total


def tree_device_bytes(tree, device=None) -> int:
    """One pytree's bytes on one device (per-device param/slot
    footprint: total/N under FSDP=N, total when replicated)."""
    dev = device or jax.devices()[0]
    return sum(_shard_bytes_on(leaf, dev) for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "addressable_shards"))


def tree_total_bytes(tree) -> int:
    """The tree's LOGICAL size (global bytes, sharding-independent)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and hasattr(leaf, "size"):
            nbytes = int(leaf.size) * leaf.dtype.itemsize
        total += int(nbytes or 0)
    return total


def embedding_table_bytes(model, params, device=None):
    """Per-table accounting for every module whose param_roles() place a
    parameter under ``embedding_row`` (LookupTable and friends): logical
    table bytes, bytes resident on one device, and the resident fraction
    — exactly 1/N under an fsdp×tp=N row-sharded layout, 1.0 when
    replicated.  Embedding tables dominate recommender memory (the
    wide-and-deep workload's whole FSDP story); tools/perf_gate.py pins
    the fraction.  Walks the module tree parallel to the params
    pytree (the Container/Graph list-alignment).  Returns a list of one dict per table, or
    None when the model has no embedding-role parameters."""
    dev = device or jax.devices()[0]
    out = []

    def walk(mod, p):
        kids = getattr(mod, "modules", None)
        if kids is not None and isinstance(p, list) and len(kids) == len(p):
            for m, cp in zip(kids, p):
                walk(m, cp)
            return
        roles = mod.param_roles() if hasattr(mod, "param_roles") else None
        if not roles or not isinstance(p, dict):
            return
        for name, leaf in p.items():
            role = roles.get(name, roles.get("*"))
            if role != "embedding_row":
                continue
            total = tree_total_bytes(leaf)
            per_dev = tree_device_bytes(leaf, dev)
            out.append({"module": type(mod).__name__, "param": name,
                        "rows": int(leaf.shape[0]) if leaf.ndim else 0,
                        "table_bytes": total,
                        "table_bytes_per_device": per_dev,
                        "device_fraction": round(per_dev / total, 6)
                        if total else 0.0})

    walk(model, params)
    return out or None


def compiled_memory_analysis(compiled) -> Optional[dict]:
    """XLA's own memory budget for one compiled executable
    (``Compiled.memory_analysis()``) as a plain dict, or None where the
    backend doesn't expose it.

    ``temp_bytes`` is the compiler's peak scratch estimate — every
    intermediate the program keeps alive at once, which for a train step
    is dominated by saved-for-backward activations.  This is the
    CPU-measurable proxy for the pipeline-schedule memory claim
    (ISSUE 13): a 1F1B step's bounded in-flight stash must budget no
    more temp than the GPipe step's keep-every-microbatch backward
    (``tools/pipeline_smoke.py`` + tests assert the ≤)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — unimplemented on this backend
        return None
    if ma is None:
        return None
    out = {}
    for name, key in (("temp_size_in_bytes", "temp_bytes"),
                      ("argument_size_in_bytes", "argument_bytes"),
                      ("output_size_in_bytes", "output_bytes"),
                      ("alias_size_in_bytes", "alias_bytes"),
                      ("generated_code_size_in_bytes", "code_bytes")):
        val = getattr(ma, name, None)
        if val is not None:
            out[key] = int(val)
    return out or None


