"""Driver ``decode_closed_routed_positions``: ``decode_closed_routed``,
loop, window, metrics and ``correct`` as they are, whose counters also carry
``slot_positions``.

``decode_closed``'s ``_counts`` keeps five of ``DecodeEngine.stats()``'s
counters, by name, and ``counts`` / ``trace_counts`` are their deltas over
the window and over the traced part of it.  A cell whose attention reads
keys and values of every position a row stands at needs one more to say how
many: ``slot_positions``, the positions + 1 of the decode steps' rows,
summed (``layer_metrics/kv_read_roofline_pct.decode.py``).  An engine that
has no such counter (a parent commit's) gives the five alone.

A ``benchmark`` PR that lets ``decode_closed`` take every integer counter of
``stats()`` makes this file one with it (PERF.md Open question 19).
"""

from __future__ import annotations

import os

from benchmark import harness

# a copy of decode_closed_routed of this driver's own (with its own copy of
# decode_closed), whose counters are this file's
routed = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "decode_closed_routed.py"),
    "bench_driver_decode_closed_routed_positions_base")

EXTRA = ("slot_positions",)
_kept = routed.base._counts


def _counts(engine) -> dict:
    out = _kept(engine)
    s = engine.stats()
    out.update({k: s[k] for k in EXTRA if k in s})
    return out


routed.base._counts = _counts

run = routed.run
control = routed.control
