"""Compile cards: every compiled executable self-describes its program.

The reference's ``getTimes()`` contract observes *runtime* (SURVEY §7.6 —
PR 4's tracer reproduced it); nothing observed the *compiled program*, yet
every perf claim since PR 6 is a structural property of the HLO: the
matmul conv route deletes every ``convolution`` from the train step, the
bucketed wire turns ~160 per-leaf casts/reduces into a handful of
bucket-sized ones, the fused update runs over a few dtype-homogeneous 1-D
buffers, and donation shows up as input/output aliases.  A **compile
card** pins those properties down at the moment an executable is born, so
a perf regression is a *diffable artifact*, not a hope — the MLPerf
TPU-pods work treats per-op compiled breakdowns as the primary
optimization instrument, and this is the always-on program-level
introspection TensorFlow ships for the same reason.

One card per (label, program), captured at the three compile choke points
(they all funnel through :func:`utils.aot.cached_compile` /
:func:`utils.aot.get_or_compile`):

- the Optimizer's pjit train step (``optim.optimizer._build_step``) —
  with ``card_extra`` carrying the step knobs, the wire-bucket count and
  the fused-buffer count, so structural claims about the step are in the
  card even before reading the HLO;
- Evaluator/Predictor/serve forward (``optim.optimizer._ShardedForward``)
  — the serve bucket ladder emits one card per bucket shape.

What a card holds (see :func:`compile_card`): the op histogram of the
**optimized HLO** text (``convolution`` / ``dot`` / ``convert`` /
all-reduce-family / ``custom-call`` counts), convert *direction* pairs
(the wire's per-bucket up-casts are distinguishable from its per-leaf
down-casts), ``cost_analysis()`` flops + bytes accessed when the backend
reports them, the ``input_output_alias`` (donation) count, the StableHLO
op histogram when the lowered computation is available, argument avals,
and the AOT cache fingerprint the executable is (or would be) stored
under.

Emission, when armed (:func:`enabled`):

- **process ledger**: :func:`cards` / :func:`stats` — the ``stats()``-
  style counter surface tests and ``InferenceServer.stats()`` read;
- **telemetry**: a ``compile.card`` instant + a ``compile`` counter track
  (convolutions / dots / converts / collectives / custom_calls /
  total_ops) on the active tracer, so ``tools/trace_report.py`` prints
  the compiled-program shape next to the runtime phases;
- **JSON artifact**: one ``card.<label>.<n>.json`` per card into the
  cards dir — ``BIGDL_TPU_COMPILE_CARDS=<dir>`` (any file_io scheme), or
  ``<trace-dir>/cards`` automatically when only tracing is armed.

Knobs:

| env var | meaning | default |
|---|---|---|
| ``BIGDL_TPU_COMPILE_CARDS`` | ``<dir>``: arm cards + write JSON artifacts there (any file_io scheme); ``1``: arm (ledger+telemetry only); ``0``: force off; empty: armed iff ``BIGDL_TPU_TRACE`` is set (artifacts land in ``<trace>/cards``) | "" |

Disabled (the default with tracing off) the whole module is inert: the
choke points pay one ``enabled()`` check — no HLO text is rendered, no
events, no files.  Card capture can never fail a compile: every error is
counted (``stats()["errors"]``) and logged, never raised.
"""

from __future__ import annotations

import json
import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional

logger = logging.getLogger("bigdl_tpu")

__all__ = ["enabled", "cards_dir", "op_histogram", "convert_pairs",
           "alias_count", "collective_count", "compile_card", "capture",
           "cards", "last_card", "stats", "reset", "write_card",
           "read_cards", "ledger"]

_FORMAT = "bigdl_tpu-compile-card-v1"

#: opcodes summed into the card's ``collectives`` count — the
#: all-reduce family GSPMD emits for gradient reduction, gathers, and
#: resharding moves
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute", "collective-broadcast")

# the in-process ledger is bounded: a long serve process warming many
# bucket ladders must not grow it without limit (oldest dropped)
_MAX_CARDS = 256

_lock = threading.Lock()
_cards: List[dict] = []
_seq = 0
_stats: Dict[str, int] = {"cards": 0, "writes": 0, "errors": 0, "dropped": 0}


# ----------------------------------------------------------------------
# arming
# ----------------------------------------------------------------------

def _knob() -> str:
    from . import config
    return config.get_str("COMPILE_CARDS", "").strip()


def enabled() -> bool:
    """True when compile cards are armed: ``BIGDL_TPU_COMPILE_CARDS`` set
    to anything but ``0``, or (with the knob empty) whenever run tracing
    (``BIGDL_TPU_TRACE``) is armed — a traced run always self-describes
    its executables."""
    k = _knob()
    if k == "0":
        return False
    if k:
        return True
    from . import telemetry
    return telemetry.enabled()


def cards_dir() -> Optional[str]:
    """Where card JSON artifacts go: the knob's dir, or ``<trace>/cards``
    beside an armed trace dir; None = no artifacts (ledger + telemetry
    only, e.g. ``BIGDL_TPU_COMPILE_CARDS=1``)."""
    k = _knob()
    if k == "0":
        return None
    if k and k != "1":
        return k
    from . import file_io, telemetry
    td = telemetry.trace_dir()
    if td:
        return file_io._join(file_io._strip_file_scheme(td), "cards")
    return None


# ----------------------------------------------------------------------
# HLO text analysis (pure functions; unit-testable without a backend)
# ----------------------------------------------------------------------

# optimized-HLO instruction, one a line: `%name = f32[8,8]{1,0} opcode(...)`.
# The result type may be a tuple `(f32[...], s32[...])` and, on a TPU, carry
# tiled layouts with parentheses of their own (`{1,0:T(8,128)(2,1)}`), so it
# is skipped, not parsed: the opcode is the first lowercase-with-dashes word
# (all-reduce, custom-call) that follows a blank and opens a parenthesis
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S.*?\s([a-z][a-z0-9\-]*)\(",
    re.M)
# StableHLO op: `%4 = stablehlo.convert %3 : ...`
_SHLO_OP_RE = re.compile(r"=\s*stablehlo\.([a-z_]+)")
# convert with visible operand type: `bf16[...] convert(f32[...] %x)`
_CONVERT_PAIR_RE = re.compile(
    r"=\s*([a-z0-9]+)\[[^\]]*\](?:\{[^}]*\})?\s*convert\(([a-z0-9]+)\[")
# StableHLO convert: `(tensor<8x8xf32>) -> tensor<8x8xbf16>` — the dtype
# is the trailing token after the dim prefix (`128xbf16` -> `bf16`)
_SHLO_CONVERT_RE = re.compile(
    r"stablehlo\.convert[^:]*:\s*\(tensor<(?:[0-9]+x)*([a-z][a-z0-9]*)>\)"
    r"\s*->\s*tensor<(?:[0-9]+x)*([a-z][a-z0-9]*)>")


def op_histogram(hlo_text: str) -> Dict[str, int]:
    """Opcode -> count over an HLO module text (optimized HLO or
    StableHLO, auto-detected).  Counts every instruction, including those
    inside fusion computations — a convert fused into a loop fusion is
    still a convert the backend executes."""
    hist: Dict[str, int] = {}
    matcher = (_SHLO_OP_RE if "stablehlo." in hlo_text else _HLO_OP_RE)
    for m in matcher.finditer(hlo_text):
        op = m.group(1)
        if op == "parameter":  # declarations, not work
            continue
        hist[op] = hist.get(op, 0) + 1
    return hist


def convert_pairs(hlo_text: str) -> Dict[str, int]:
    """``"<dst><-<src>" -> count`` for every convert in the text.  This is
    what separates the wire's **per-bucket up-casts** (``f32<-bf16``: one
    per bucket after concatenation) from its **per-leaf down-casts**
    (``bf16<-f32``: one per gradient leaf) — the wire-card test bounds the
    former by the bucket count, not the leaf count."""
    pairs: Dict[str, int] = {}
    if "stablehlo." in hlo_text:
        for m in _SHLO_CONVERT_RE.finditer(hlo_text):
            key = f"{m.group(2)}<-{m.group(1)}"
            pairs[key] = pairs.get(key, 0) + 1
    else:
        for m in _CONVERT_PAIR_RE.finditer(hlo_text):
            key = f"{m.group(1)}<-{m.group(2)}"
            pairs[key] = pairs.get(key, 0) + 1
    return pairs


def alias_count(hlo_text: str) -> int:
    """Number of input/output aliases in the module header — donation
    (``donate_argnums``) compiles into ``input_output_alias={ {0}: (0, {},
    may-alias), ... }``; 0 means no buffer is updated in place.  Counted
    on the header LINE (the alias spec nests braces, and `may-alias`
    tokens appear nowhere else in an HLO module)."""
    header = hlo_text.split("\n", 1)[0]
    if "input_output_alias" not in header:
        return 0
    return header.count("may-alias") + header.count("must-alias")


def collective_count(hist: Dict[str, int]) -> int:
    """Sum of the all-reduce-family opcodes in an op histogram (the ops
    ``-start``/``-done`` async pairs count once each)."""
    total = 0
    for op, n in hist.items():
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base.endswith("-done"):
            continue  # the matching -start was already counted
        if base in COLLECTIVE_OPS:
            total += n
    return total


# ----------------------------------------------------------------------
# card construction + emission
# ----------------------------------------------------------------------

def compile_card(compiled=None, lowered=None, *, label: str,
                 key: Optional[str] = None, example_args=None,
                 extra: Optional[dict] = None,
                 source: str = "compile") -> dict:
    """Build a card dict for a compiled (and/or lowered) computation.

    ``compiled`` is a jax Compiled (``.as_text()`` = optimized HLO,
    ``.cost_analysis()`` when the backend supports it); ``lowered`` a jax
    Lowered (``.as_text()`` = StableHLO) — either may be None (an AOT
    cache hit through ``get_or_compile`` never lowered).  ``key`` is the
    AOT cache fingerprint the executable lives under (None when the cache
    is disabled).  ``extra`` is the caller's structural self-description
    (the train step passes its knobs + wire-bucket + fused-buffer
    counts)."""
    card: Dict[str, Any] = {"format": _FORMAT, "label": label,
                            "source": source, "aot_key": key,
                            "ts": round(time.time(), 3)}
    try:
        import jax
        card["backend"] = jax.default_backend()
        card["device_kind"] = getattr(jax.devices()[0], "device_kind", "?")
    except Exception:  # noqa: BLE001 — backend introspection is optional
        pass
    hist: Dict[str, int] = {}
    if compiled is not None:
        try:
            txt = compiled.as_text()
            hist = op_histogram(txt)
            card["ops"] = hist
            card["convert_pairs"] = convert_pairs(txt)
            aliases = alias_count(txt)
            card["input_output_aliases"] = aliases
            card["donation"] = aliases > 0
        except Exception as e:  # noqa: BLE001 — e.g. a deserialized
            # executable whose runtime refuses to re-render HLO text
            card["hlo_error"] = f"{type(e).__name__}: {e}"
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                card["cost"] = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
        except Exception:  # noqa: BLE001 — cost analysis is best-effort
            pass
    if lowered is not None:
        try:
            shlo = lowered.as_text()
            card["stablehlo_ops"] = op_histogram(shlo)
            # as-WRITTEN convert directions: the optimizer may push the
            # wire's per-bucket up-cast through the split slices (per-leaf
            # again in the optimized text), so the bucket-bounded count
            # lives here, pre-optimization
            card["stablehlo_convert_pairs"] = convert_pairs(shlo)
        except Exception as e:  # noqa: BLE001
            card.setdefault("hlo_error", f"{type(e).__name__}: {e}")
    # the headline counts the perf gate diffs (derived from the optimized
    # histogram; 0s when only StableHLO was available)
    card["convolutions"] = hist.get("convolution", 0)
    card["dots"] = hist.get("dot", 0) + hist.get("dot_general", 0)
    card["converts"] = hist.get("convert", 0)
    card["collectives"] = collective_count(hist)
    card["custom_calls"] = hist.get("custom-call", 0)
    card["total_ops"] = sum(hist.values())
    if example_args is not None:
        try:
            from . import aot
            card["args"] = aot.aval_fingerprint(example_args)
        except Exception:  # noqa: BLE001
            pass
    if extra:
        card["extra"] = dict(extra)
    return card


def capture(compiled=None, lowered=None, *, label: str,
            key: Optional[str] = None, example_args=None,
            extra: Optional[dict] = None,
            source: str = "compile") -> Optional[dict]:
    """The choke-point hook: build + record a card when armed; a no-op
    returning None when disabled.  Never raises — a card must never take
    down the compile it describes."""
    if not enabled():
        return None
    try:
        card = compile_card(compiled, lowered, label=label, key=key,
                            example_args=example_args, extra=extra,
                            source=source)
    except Exception as e:  # noqa: BLE001
        logger.warning("hlostats: card capture for %s failed: %s: %s",
                       label, type(e).__name__, e)
        with _lock:
            _stats["errors"] += 1
        return None
    _record(card)
    return card


def _record(card: dict) -> None:
    global _seq
    from . import telemetry
    with _lock:
        _seq += 1
        seq = _seq
        _cards.append(card)
        if len(_cards) > _MAX_CARDS:
            del _cards[0]
            _stats["dropped"] += 1
        _stats["cards"] += 1
    # telemetry: one instant (the event: what compiled, when) + one
    # counter sample (the trend: op counts over the run's compiles)
    telemetry.instant("compile.card", cat="compile", label=card["label"],
                      source=card["source"],
                      convolutions=card["convolutions"],
                      converts=card["converts"],
                      total_ops=card["total_ops"])
    telemetry.counter("compile", convolutions=card["convolutions"],
                      dots=card["dots"], converts=card["converts"],
                      collectives=card["collectives"],
                      custom_calls=card["custom_calls"],
                      total_ops=card["total_ops"])
    d = cards_dir()
    if d is not None:
        try:
            write_card(card, d, seq=seq)
            with _lock:
                _stats["writes"] += 1
        except Exception as e:  # noqa: BLE001 — artifacts are best-effort
            logger.warning("hlostats: card write to %s failed: %s: %s",
                           d, type(e).__name__, e)
            with _lock:
                _stats["errors"] += 1


# ----------------------------------------------------------------------
# artifacts (plain JSON through file_io — local / memory:// / fsspec)
# ----------------------------------------------------------------------

def _safe_label(label: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", label)


def write_card(card: dict, dir_: str, *, seq: Optional[int] = None) -> str:
    """Write one card as ``card.<label>.<seq>.json`` under ``dir_`` (any
    file_io scheme).  Returns the path."""
    from . import file_io
    base = file_io._strip_file_scheme(str(dir_))
    fs = file_io.get_filesystem(base)
    fs.makedirs(base)
    if seq is None:
        global _seq
        with _lock:
            _seq += 1
            seq = _seq
    name = f"card.{_safe_label(card.get('label', 'unknown'))}.{seq}.json"
    path = file_io._join(base, name)
    fs.write_bytes(path, json.dumps(card, sort_keys=True).encode())
    return path


def read_cards(dir_: str) -> List[dict]:
    """Every ``card.*.json`` under ``dir_``, in emission (seq) order."""
    from . import file_io
    base = file_io._strip_file_scheme(str(dir_))
    fs = file_io.get_filesystem(base)
    out = []
    for name in fs.listdir(base):
        m = re.fullmatch(r"card\..*\.(\d+)\.json", name)
        if not m:
            continue
        out.append((int(m.group(1)), json.loads(
            fs.read_bytes(file_io._join(base, name)))))
    return [c for _, c in sorted(out, key=lambda t: t[0])]


# ----------------------------------------------------------------------
# the process ledger
# ----------------------------------------------------------------------

def cards(label: Optional[str] = None) -> List[dict]:
    """Cards captured by this process (newest last), optionally filtered
    by label."""
    with _lock:
        snap = [dict(c) for c in _cards]
    if label is not None:
        snap = [c for c in snap if c.get("label") == label]
    return snap


def last_card(label: Optional[str] = None) -> Optional[dict]:
    """The newest card (for ``label``, when given), or None."""
    got = cards(label)
    return got[-1] if got else None


def stats() -> Dict[str, int]:
    """Process-wide counters: cards captured, artifacts written, errors,
    ledger drops."""
    with _lock:
        return dict(_stats)


def ledger() -> Dict[str, int]:
    """Per-label card counts — the ``stats()``-style summary
    ``InferenceServer.stats()`` embeds (a warm serve ladder shows one
    card per bucket shape)."""
    with _lock:
        out: Dict[str, int] = {}
        for c in _cards:
            lb = c.get("label", "?")
            out[lb] = out.get(lb, 0) + 1
        return dict(sorted(out.items()))


def reset() -> None:
    """Zero the ledger and counters (tests)."""
    global _seq
    with _lock:
        _cards.clear()
        _seq = 0
        for k in _stats:
            _stats[k] = 0
