"""Persistent AOT executable cache: cold start is a cache read, not a compile.

Whole-step XLA compiles take tens of seconds to minutes (ResNet-50's train
step, a serve bucket ladder), and a process that restarts pays them again.
The XLA persistent cache (utils/platform.enable_compilation_cache) already
warms the *compiler*; this module goes one level up and caches the
**serialized executable** itself
(`jax.jit(...).lower(...).compile()` via
`jax.experimental.serialize_executable`), so a warm process performs zero
XLA work at all: startup becomes IO.

Three compile choke points route through here:

- the Optimizer's pjit train step (optim/optimizer._build_step) — keyed by
  the **HLO hash** (plus versions/backend/mesh/avals), so any model or
  lowering change is automatically a miss;
- Evaluator/Predictor/serve forward (optim.optimizer._ShardedForward) —
  keyed by a **structural module fingerprint** (no tracing needed), so a
  warm `InferenceServer.warmup()` performs zero fresh lowers: the serve
  bucket ladder's N compiles become N cache reads.

Entries are CRC-framed pickles written through :mod:`.file_io` (the PR-1
checkpoint framing — local, ``memory://`` and fsspec schemes all work, so a
remote cache dir warms a whole pod).  A corrupt *file* (CRC mismatch,
truncated pickle, foreign format) is **quarantined** (renamed ``*.corrupt``)
and recompiled; an intact entry whose executable the runtime refuses to load
is left in place and logged once at WARNING with the runtime's error — that
is a bug in this module or a changed installation, not bit rot, and must not
be hidden.  Either way the cache can never make a run fail.

Each entry records the ids of the devices its executable was compiled for and
hands them back to ``deserialize_and_load(execution_devices=...)``: without
them JAX binds a loaded executable to *every* device of the process, and a
one-device serve executable loaded in a multi-device process is rejected at
its first call.

Keying / invalidation: every key fingerprints (jax, jaxlib, bigdl_tpu
versions; backend + device kind + device/process count; mesh shape+axes;
arg avals incl. shardings; an optional ``BIGDL_TPU_AOT_CACHE_TAG``), plus
the HLO hash (train) or the module fingerprint (forward).  Change any
of them and the entry simply misses; stale entries are never served.

Knobs:

| env var | meaning | default |
|---|---|---|
| ``BIGDL_TPU_AOT_CACHE`` | cache directory (any file_io scheme); empty/0 = disabled | off |
| ``BIGDL_TPU_AOT_CACHE_TAG`` | free-form fingerprint salt (bump to invalidate en masse) | "" |

Telemetry: ``aot.load`` / ``aot.store`` / ``compile`` spans, plus an ``aot``
counter track (hits / misses / stores) so a trace proves whether a run was
warm.  Multi-process (multi-host) runs disable the cache: a serialized SPMD
executable embeds the global topology and per-host deserialize ordering is
not worth the risk — each host still benefits from the XLA persistent cache.
"""

from __future__ import annotations

import hashlib
import json
import logging
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional

logger = logging.getLogger("bigdl_tpu")

__all__ = ["enabled", "cache_dir", "get_cache", "reset", "stats",
           "AOTCache", "fingerprint", "base_fingerprint",
           "aval_fingerprint", "module_fingerprint", "hlo_hash",
           "cached_compile", "get_or_compile"]

_FORMAT = "bigdl_tpu-aot-v2"
_SUFFIX = ".aotx"

# process-wide counters: the "did this run compile anything?" ledger that
# tests, tool records, and the telemetry counter track all read
_lock = threading.Lock()
_STATS_KEYS = ("hits", "misses", "stores", "lowers", "compiles",
               "corrupt", "errors", "compile_s", "load_s")
_stats: Dict[str, float] = {k: 0 for k in _STATS_KEYS}
_cache_singleton: Dict[str, Any] = {}


_TRACK_KEYS = ("hits", "misses", "stores", "lowers", "compiles")


def _bump(key: str, amount: float = 1) -> None:
    from . import telemetry
    with _lock:
        _stats[key] += amount
        snap = {k: _stats[k] for k in _TRACK_KEYS}
    if key in _TRACK_KEYS:
        # the full warm-start ledger rides the `aot` counter track so
        # trace_report's aot section (and Perfetto) can prove whether a
        # run compiled anything, not just whether the cache hit
        telemetry.counter("aot", **snap)


def stats() -> Dict[str, float]:
    """Snapshot of the process-wide cache counters (hits/misses/stores/
    lowers/compiles/corrupt/errors + cumulative compile_s/load_s)."""
    with _lock:
        return dict(_stats)


def reset() -> None:
    """Zero the counters and drop the cache singleton (tests)."""
    with _lock:
        for k in _STATS_KEYS:
            _stats[k] = 0
        _cache_singleton.clear()


def cache_dir() -> Optional[str]:
    """The configured cache directory, or None when disabled."""
    from . import config
    d = config.get_str("AOT_CACHE", "").strip()
    if not d or d == "0":
        return None
    return d


def enabled() -> bool:
    """True when a cache dir is configured AND this is a single-process
    run (serialized SPMD executables embed the global topology; multi-host
    replay is disabled by design — the XLA persistent cache still warms
    those)."""
    if cache_dir() is None:
        return False
    try:
        import jax
        return jax.process_count() == 1
    except Exception:  # noqa: BLE001 — backend not up yet
        return False


def get_cache() -> Optional["AOTCache"]:
    """The process AOTCache for the configured dir (singleton per dir)."""
    d = cache_dir()
    if d is None or not enabled():
        return None
    cache = _cache_singleton.get(d)
    if cache is None:
        cache = AOTCache(d)
        _cache_singleton.clear()
        _cache_singleton[d] = cache
    return cache


# ----------------------------------------------------------------------
# fingerprinting
# ----------------------------------------------------------------------

def fingerprint(fields: Dict[str, Any]) -> str:
    """Stable sha256 over a canonical-JSON rendering of the key fields."""
    blob = json.dumps(fields, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def base_fingerprint(mesh=None) -> Dict[str, Any]:
    """The environment half of every key: versions, backend, device kind,
    topology, mesh, and the free-form cache tag."""
    import jax
    import jaxlib

    from . import config
    dev = jax.devices()[0]
    fields = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "bigdl_tpu": _pkg_version(),
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "?"),
        "n_devices": len(jax.devices()),
        "processes": jax.process_count(),
        "tag": config.get_str("AOT_CACHE_TAG", ""),
    }
    if mesh is not None:
        # device ids too: a serialized executable is bound to its device
        # assignment, so the same mesh shape over another subset of the
        # host's devices (serve/router.py's pinned replicas) is a miss
        fields["mesh"] = {"shape": dict(mesh.shape),
                          "axes": list(mesh.axis_names),
                          "devices": [int(d.id) for d in mesh.devices.flat]}
    return fields


def _pkg_version() -> str:
    try:
        import bigdl_tpu
        return getattr(bigdl_tpu, "__version__", "0")
    except Exception:  # noqa: BLE001
        return "0"


def aval_fingerprint(tree) -> list:
    """Flattened (shape, dtype, sharding) triples for an arg pytree —
    concrete arrays, ShapeDtypeStructs and avals all work; no tracing."""
    import jax
    out = []
    for leaf in jax.tree.leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        sh = getattr(leaf, "sharding", None)
        spec = str(getattr(sh, "spec", "")) if sh is not None else ""
        out.append([list(shape), dtype, spec])
    return out


def module_fingerprint(module) -> str:
    """Structural hash of an nn.Module tree: class names + primitive
    config attributes + children, recursively.  Deliberately excludes the
    uid-bearing ``name`` and all array state (weights enter the key via
    :func:`aval_fingerprint` of the placed params).  No tracing, no
    lowering — this is what lets a warm serve ladder skip lowering
    entirely."""
    _VOLATILE = {"name", "params", "state", "grads", "output", "grad_input",
                 "_last_rng", "modules", "weight_initializer",
                 "bias_initializer", "training_mode"}

    def walk(m):
        d: Dict[str, Any] = {
            "cls": f"{type(m).__module__}.{type(m).__qualname__}"}
        attrs = {}
        for k, v in sorted(vars(m).items()):
            if k in _VOLATILE:
                continue
            if isinstance(v, (bool, int, float, str, type(None))):
                attrs[k] = v
            elif isinstance(v, (tuple, list)) and all(
                    isinstance(x, (bool, int, float, str, type(None)))
                    for x in v):
                attrs[k] = list(v)
        if attrs:
            d["attrs"] = attrs
        children = getattr(m, "modules", None)
        if isinstance(children, (list, tuple)) and children:
            d["children"] = [walk(c) for c in children]
        return d

    return fingerprint(walk(module))


def hlo_hash(lowered) -> str:
    """sha256 of the lowered StableHLO text — the strongest possible key
    component: any change to the computation (model edit, donation,
    sharding, env-dependent lowering like the tiny-channel conv pad)
    changes it."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


# ----------------------------------------------------------------------
# the cache proper
# ----------------------------------------------------------------------

class AOTCache:
    """One cache directory of CRC-framed serialized executables.

    All IO goes through :mod:`.file_io` (local / ``memory://`` / fsspec,
    retried remote writes) and every entry carries the PR-1 integrity
    frame; a CRC mismatch or an unreadable pickle quarantines the entry
    (``*.corrupt``) and reports a miss — the caller recompiles and the
    fresh store overwrites nothing (new entries are written to a temp name
    and renamed into place)."""

    def __init__(self, root: str):
        from . import file_io
        self.root = file_io._strip_file_scheme(str(root))
        self._fs = file_io.get_filesystem(self.root)
        try:
            self._fs.makedirs(self.root)
        except Exception:  # noqa: BLE001 — unwritable root = every op misses
            logger.warning("aot: cache dir %s not creatable", self.root)

    def _path(self, key: str) -> str:
        from . import file_io
        return file_io._join(self.root, key + _SUFFIX)

    def load(self, key: str):
        """Deserialize the executable stored under ``key``; None on miss.
        Corrupt/stale entries are quarantined and count as misses."""
        from . import file_io, telemetry
        path = self._path(key)
        t0 = time.perf_counter()
        with telemetry.span("aot.load", cat="aot", key=key[:16]):
            try:
                if not self._fs.exists(path):
                    _bump("misses")
                    return None
            except Exception as e:  # noqa: BLE001 — cache must never raise
                logger.warning("aot: exists(%s) failed: %s", path, e)
                _bump("errors")
                _bump("misses")
                return None
            try:
                entry = file_io.load(path)
                if not (isinstance(entry, dict)
                        and entry.get("format") == _FORMAT):
                    raise ValueError(f"not a {_FORMAT} entry")
            except Exception as e:  # noqa: BLE001 — a corrupt FILE
                # (CRC mismatch, truncated pickle, foreign format):
                # quarantine so the next process does not trip over it
                # again, then recompile
                self._quarantine(path, e, key=key)
                _bump("corrupt")
                _bump("misses")
                return None
            try:
                from jax.experimental.serialize_executable import \
                    deserialize_and_load
                compiled = deserialize_and_load(
                    entry["exe"], entry["in_tree"], entry["out_tree"],
                    execution_devices=_devices_by_id(entry["device_ids"]))
            except Exception as e:  # noqa: BLE001 — cache must never raise
                # an intact entry the runtime will not load: not bit rot,
                # so no quarantine — say so (once) and recompile
                _warn_rejected(path, key, e)
                _bump("errors")
                _bump("misses")
                return None
        _bump("load_s", time.perf_counter() - t0)
        _bump("hits")
        return compiled

    def store(self, key: str, compiled, meta: Optional[dict] = None) -> bool:
        """Serialize + frame + write ``compiled`` under ``key`` (temp name
        then rename: concurrent writers race benignly).  Returns False —
        never raises — when the executable does not support serialization
        or the write fails."""
        from . import file_io, telemetry
        path = self._path(key)
        with telemetry.span("aot.store", cat="aot", key=key[:16]):
            try:
                from jax.experimental.serialize_executable import serialize
                exe, in_tree, out_tree = serialize(compiled)
                entry = {"format": _FORMAT, "exe": exe, "in_tree": in_tree,
                         "out_tree": out_tree, "meta": meta or {},
                         "device_ids": _device_ids(compiled)}
                tmp = f"{path}.tmp.{_token()}"
                file_io.save(entry, tmp)
                try:
                    self._fs.rename(tmp, path)
                except Exception:  # noqa: BLE001 — loser of a store race
                    try:
                        self._fs.remove(tmp)
                    except Exception:  # noqa: BLE001
                        pass
            except Exception as e:  # noqa: BLE001 — cache must never raise
                logger.warning("aot: store(%s) failed: %s: %s", key[:16],
                               type(e).__name__, e)
                _bump("errors")
                return False
        _bump("stores")
        return True

    def _quarantine(self, path: str, err: Exception,
                    key: Optional[str] = None) -> None:
        # the full fingerprint in the log line: corrupt-entry forensics
        # (which env/model/avals produced this key?) can start from the
        # entry's meta without attaching a debugger
        logger.warning("aot: quarantining %s (fingerprint %s; %s: %s); "
                       "recompiling", path, key or "?",
                       type(err).__name__, err)
        try:
            self._fs.rename(path, path + ".corrupt")
        except Exception:  # noqa: BLE001 — e.g. a concurrent quarantine
            try:
                self._fs.remove(path)
            except Exception:  # noqa: BLE001
                pass

    def entries(self) -> list:
        """Keys currently stored (diagnostics/tests)."""
        try:
            return sorted(n[:-len(_SUFFIX)] for n in
                          self._fs.listdir(self.root)
                          if n.endswith(_SUFFIX))
        except Exception:  # noqa: BLE001
            return []


def _device_ids(compiled) -> list:
    """Ids of the devices ``compiled`` executes on, in assignment order."""
    return [d.id for d in compiled.runtime_executable().local_devices()]


def _devices_by_id(ids) -> list:
    import jax
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in ids]


_rejected_warned = False


def _warn_rejected(path: str, key: str, err: Exception) -> None:
    global _rejected_warned
    if _rejected_warned:
        return
    _rejected_warned = True
    logger.warning("aot: the runtime rejected cached executable %s "
                   "(fingerprint %s; %s: %s); compiling instead — further "
                   "rejections in this process are counted under "
                   "stats()['errors'] and not logged", path, key,
                   type(err).__name__, err)


def _token() -> str:
    import os
    return f"{os.getpid()}.{threading.get_ident()}"


# ----------------------------------------------------------------------
# the two compile-site entry points
# ----------------------------------------------------------------------

#: jax.monitoring event of a persistent-XLA-cache hit; the listener counts
#: them per thread (the event fires inside `lowered.compile()`, on the
#: compiling thread), so `_compile_timed` can tell where its executable
#: came from
_XLA_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_xla_hits = threading.local()
_listening = False


def _count_xla_hit(event: str, **_kw) -> None:
    if event == _XLA_CACHE_HIT:
        _xla_hits.n = getattr(_xla_hits, "n", 0) + 1


def _compile_timed(lowered, label: str):
    """``lowered.compile()``, timed.  Returns ``(compiled, storable)``:
    an executable that XLA read back from its persistent cache is not
    storable — serialized a second time it loses its kernels (XLA:CPU:
    `Function ... not found` at the first call of the loaded copy), and
    the cache it came from already makes the next start cheap."""
    global _listening
    import jax

    from . import telemetry
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_count_xla_hit)
            _listening = True
    before = getattr(_xla_hits, "n", 0)
    t0 = time.perf_counter()
    with telemetry.span("compile", cat="aot", label=label):
        compiled = lowered.compile()
    _bump("compiles")
    _bump("compile_s", time.perf_counter() - t0)
    return compiled, getattr(_xla_hits, "n", 0) == before


def cached_compile(lowered, *, label: str, mesh=None,
                   example_args=None, extra: Optional[dict] = None,
                   card_extra: Optional[dict] = None):
    """HLO-hash-keyed compile of an already-lowered computation (the train
    step path: tracing+lowering is cheap, the XLA compile is not).  Cache disabled -> plain ``lowered.compile()``.

    Every executable leaving here — freshly compiled OR deserialized from
    the cache — emits a compile card (utils/hlostats.py) when cards are
    armed; ``card_extra`` rides in the card (NOT the cache key): the train
    step's knob/bucket/buffer self-description."""
    from . import hlostats
    _bump("lowers")
    cache = get_cache()
    key = None
    if cache is not None:
        fields = dict(base_fingerprint(mesh))
        fields["label"] = label
        fields["hlo"] = hlo_hash(lowered)
        if example_args is not None:
            fields["args"] = aval_fingerprint(example_args)
        if extra:
            fields.update(extra)
        key = fingerprint(fields)
        compiled = cache.load(key)
        if compiled is not None:
            logger.info("aot: %s warm-started from cache (%s)", label,
                        key[:16])
            hlostats.capture(compiled, lowered, label=label, key=key,
                             example_args=example_args, extra=card_extra,
                             source="aot-hit")
            return compiled
    compiled, storable = _compile_timed(lowered, label)
    if cache is not None and storable:
        cache.store(key, compiled, meta={"label": label,
                                         "fields": _meta_fields(fields)})
    hlostats.capture(compiled, lowered, label=label, key=key,
                     example_args=example_args, extra=card_extra,
                     source="compile")
    return compiled


def get_or_compile(key_fields: Dict[str, Any], lower_fn: Callable[[], Any],
                   *, label: str, card_extra: Optional[dict] = None):
    """Logical-key lookup that skips lowering entirely on a hit (the serve
    bucket-ladder path: ``key_fields`` must identify the computation
    without tracing — module fingerprint + avals + base fingerprint).
    On miss, ``lower_fn()`` is invoked once and the compile is stored.
    Hit or miss, the executable emits a compile card when armed (a hit's
    card has no StableHLO section — nothing was lowered, by design)."""
    from . import hlostats
    cache = get_cache()
    fields = dict(key_fields)
    fields["label"] = label
    key = fingerprint(fields)
    if cache is None:
        _bump("lowers")
        lowered = lower_fn()
        compiled, _ = _compile_timed(lowered, label)
        hlostats.capture(compiled, lowered, label=label, key=key,
                         extra=card_extra, source="compile")
        return compiled
    compiled = cache.load(key)
    if compiled is not None:
        logger.info("aot: %s warm-started from cache (%s)", label, key[:16])
        hlostats.capture(compiled, None, label=label, key=key,
                         extra=card_extra, source="aot-hit")
        return compiled
    _bump("lowers")
    lowered = lower_fn()
    compiled, storable = _compile_timed(lowered, label)
    if storable:
        cache.store(key, compiled, meta={"label": label,
                                         "fields": _meta_fields(fields)})
    hlostats.capture(compiled, lowered, label=label, key=key,
                     extra=card_extra, source="compile")
    return compiled


def _meta_fields(fields: Dict[str, Any]) -> Dict[str, Any]:
    """Human-inspectable copy of the key fields for the entry's meta
    (avals can be long; everything else is small and invaluable when
    debugging why a key missed)."""
    out = {k: v for k, v in fields.items() if k != "args"}
    out["n_args"] = len(fields.get("args", []))
    return out
