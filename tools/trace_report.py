#!/usr/bin/env python
"""Merge multi-rank run traces and print the phase breakdown.

Every traced process (``BIGDL_TPU_TRACE=<dir>``)
writes ``trace.<rank>.json`` (Chrome trace-event JSON,
``bigdl_tpu.utils.telemetry``).  This tool merges all ranks onto one
wall-clock timeline and prints the diagnosis a TensorBoard-less operator
needs: per-phase p50/p95/max, the ``data_wait_fraction`` (input-bound vs
compute-bound), straggler
ranks (one slow host's ``step`` spans stand out against the median),
counter-track series in deterministic (sorted) order — including the
``compile`` track compile cards emit (utils/hlostats.py) — and, when the
``aot`` track is present, the AOT warm-start ledger
(hits/misses/stores/lowers/compiles) as its own section.  The serving
autoscaler's track and the continuous-deployment ``deploy`` track
(publishes from the trainer rank, deploy/promote/rollback/reject totals
from the controller — serve/continuous.py) are promoted to their own
sections the same way, so a merged trainer+server trace shows training
steps, publishes, and promotions on one timeline.  Elastic episodes get
the same treatment: the ``elastic:`` line counts the ``elastic.*``
instants (detect/negotiate/agree/join/reform/resume) and reports
``joined`` — the last value of the ``peers`` counter track, the world
size after the most recent shrink or grow (parallel/elastic.py).  The
``decode:`` line holds the decode engine's track (serve/decode.py) and what
its deepest spans say: the medians of ``decode.call`` and ``decode.fetch``
by program (the host's own part of a device call, and its wait for the
device and the transfer), the bytes a fetch brought, the seconds asleep in
``decode.idle``, and a request's time per token after its first.

Usage::

    python tools/trace_report.py <trace-dir> [--out merged.json] [--json]
    python tools/trace_report.py <trace-dir> --requests [--slowest N]
    python tools/trace_report.py --diff <trace-dir-A> <trace-dir-B> [--json]
    python tools/trace_report.py --xplane <profiler-dir> [--json]

``--xplane`` reads a JAX profiler trace (``utils/profiling.profiler_session``
writes one whose host events include the ``bigdl:<span>`` annotation every
open span holds) and prints the device's idle time by cause
(``telemetry.idle_by_cause``): each idle gap under the deepest span that
covered it on the thread that drives the device, the rest ``unattributed``.

``--requests`` reconstructs per-request critical paths from the flow
events (``ph:"s"/"t"/"f"``, one chain per ``X-BigDL-Request-Id``) the
serving tiers emit when traced: latency attributed by segment (queue
vs device vs transport vs failover) at p50/p95/p99, plus the slowest-N
requests' hop-by-hop timelines across front, worker, and controller
ranks.

``--out`` writes the merged timeline (loadable in Perfetto as one file);
``--json`` prints the breakdown (or diff) as machine-readable JSON
instead of the table.  ``--diff A B`` compares two runs' phase
breakdowns and counter tracks (A = baseline, B = new run) — per-phase
total-time B/A ratios and per-series last-value deltas, the "what did
this change do to the run" view `tools/perf_gate.py` automates for the
committed proxies.  Exit status is non-zero when an input dir holds no
trace files or the breakdown is empty (no spans) — the error names the
offending path.

The heavy lifting (merge + breakdown + diff + formatting) lives in
``bigdl_tpu.utils.telemetry`` so tests exercise it directly; this file is
the CLI shell, like tools/supervise_smoke.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# runnable as `python tools/trace_report.py` from the repo root: sys.path[0]
# is tools/, so add the repo root (same dance as supervise_smoke.py)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _load_breakdown(telemetry, trace_dir):
    """(breakdown, merged) for one trace dir; exits 2 naming the path
    when it holds no trace files."""
    try:
        merged = telemetry.merge_traces(trace_dir)
    except FileNotFoundError as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return None, None
    return telemetry.phase_breakdown(merged), merged


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", nargs="?",
                    help="dir holding trace.<rank>.json files (any file_io "
                         "scheme: local, memory://, gs://, ...)")
    ap.add_argument("--xplane", default=None, metavar="PROFILER_DIR",
                    help="a JAX profiler trace dir instead: print the "
                         "device's idle time by cause (the program's spans "
                         "on the profiler's clock)")
    ap.add_argument("--diff", default=None, metavar="TRACE_DIR_B",
                    help="compare TWO runs: trace_dir is the baseline (A), "
                         "this dir the new run (B); prints per-phase B/A "
                         "ratios and counter-track deltas")
    ap.add_argument("--out", default=None, metavar="MERGED_JSON",
                    help="also write the merged single-timeline trace here")
    ap.add_argument("--json", action="store_true",
                    help="print the breakdown as JSON instead of the table")
    ap.add_argument("--requests", action="store_true",
                    help="per-request critical paths from the flow events: "
                         "segment attribution (queue/device/transport/"
                         "failover) p50/p95/p99 + slowest-N hop timelines")
    ap.add_argument("--slowest", type=int, default=5,
                    help="with --requests: how many slowest requests get "
                         "a full hop timeline (default 5)")
    args = ap.parse_args(argv)

    from bigdl_tpu.utils import telemetry

    if args.xplane:
        from bigdl_tpu.utils import profiling
        try:
            rows = profiling.xplane_rows(args.xplane)
        except FileNotFoundError as e:
            print(f"trace_report: {e}", file=sys.stderr)
            return 2
        if not any(r[0].startswith("/device:") for r in rows):
            print(f"trace_report: {args.xplane}: the trace holds no device "
                  "operations", file=sys.stderr)
            return 3
        causes = telemetry.idle_by_cause(rows)
        print(json.dumps(causes) if args.json
              else telemetry.format_idle(causes))
        return 0
    if not args.trace_dir:
        ap.error("a trace dir, or --xplane <profiler-dir>, is required")

    breakdown, merged = _load_breakdown(telemetry, args.trace_dir)
    if breakdown is None:
        return 2

    if args.requests:
        rb = telemetry.request_breakdown(merged, slowest=args.slowest)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(merged, f)
            print(f"merged trace -> {args.out}", file=sys.stderr)
        if args.json:
            print(json.dumps(rb))
        else:
            print(telemetry.format_requests(rb))
        if not rb["requests"]:
            print(f"trace_report: {args.trace_dir}: trace holds no "
                  "request flows (run the serving tier with "
                  "BIGDL_TPU_TRACE armed)", file=sys.stderr)
            return 3
        return 0

    if args.diff:
        breakdown_b, _ = _load_breakdown(telemetry, args.diff)
        if breakdown_b is None:
            return 2
        diff = telemetry.diff_breakdowns(breakdown, breakdown_b)
        if args.json:
            print(json.dumps(diff))
        else:
            print(f"A: {args.trace_dir}\nB: {args.diff}")
            print(telemetry.format_diff(diff))
        for name, which in (("A", breakdown), ("B", breakdown_b)):
            if not which["phases"]:
                path = args.trace_dir if name == "A" else args.diff
                print(f"trace_report: {path}: trace holds no spans "
                      "(empty breakdown)", file=sys.stderr)
                return 3
        return 0

    if args.out:
        with open(args.out, "w") as f:
            json.dump(merged, f)
        print(f"merged trace -> {args.out}", file=sys.stderr)
    if args.json:
        print(json.dumps(breakdown))
    else:
        print(telemetry.format_report(breakdown, merged))
    if not breakdown["phases"]:
        print(f"trace_report: {args.trace_dir}: trace holds no spans "
              "(empty breakdown)", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
