#!/usr/bin/env python
"""Input-pipeline overlap smoke: prove wall clock ~= max(data, step), not sum.

A slow-transformer fixture (DATA_MS of host work per batch) feeds a
consumer that spends STEP_MS per step, through the background prefetcher
(bigdl_tpu.dataset.prefetch.PrefetchIterator, depth 2).  With overlap,
N batches complete near the single-cost bound N * max(DATA_MS, STEP_MS);
serialized execution would take N * (DATA_MS + STEP_MS) ~= 2x.  PASS is
overlapped wall < --ratio-limit (default 1.6) x the single-cost bound —
the same margin the tier-1 test asserts (tests/test_prefetch.py).

No jax, no accelerator, no backend init — a parent may run it while
another process holds the chip; safe anywhere, seconds of wall clock.
Prints ONE JSON line and exits 0 on PASS, 1 on FAIL.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python tools/input_bench.py` from the repo root (the
# usual invocation): sys.path[0] is tools/, so add the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--data-ms", type=float, default=50.0)
    ap.add_argument("--step-ms", type=float, default=50.0)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--ratio-limit", type=float, default=1.6,
                    help="PASS when overlapped wall < limit x the "
                         "single-cost bound (serialized ~= 2x)")
    args = ap.parse_args(argv)

    from bigdl_tpu.dataset.prefetch import PrefetchIterator

    data_s, step_s = args.data_ms / 1e3, args.step_ms / 1e3

    def source():
        for i in range(args.batches):
            time.sleep(data_s)  # the slow transformer chain
            yield i

    # serialized reference: the synchronous loop pays data + step per batch
    t0 = time.perf_counter()
    for _ in range(args.batches):
        time.sleep(data_s)
        time.sleep(step_s)
    serialized = time.perf_counter() - t0

    # overlapped: the worker produces batch i+1 while the consumer "steps"
    t0 = time.perf_counter()
    consumed = 0
    with PrefetchIterator(source(), depth=args.depth) as pipe:
        for _ in pipe:
            time.sleep(step_s)  # the device step the data work hides under
            consumed += 1
    overlapped = time.perf_counter() - t0

    bound = args.batches * max(data_s, step_s)  # perfect-overlap wall
    ratio = overlapped / bound
    ok = consumed == args.batches and ratio < args.ratio_limit
    print(json.dumps({
        "metric": "input_pipeline_overlap", "value": round(ratio, 3),
        "unit": "x-single-cost-bound", "vs_baseline": None, "pass": ok,
        "batches": args.batches, "consumed": consumed,
        "data_ms": args.data_ms, "step_ms": args.step_ms,
        "depth": args.depth,
        "single_cost_bound_seconds": round(bound, 3),
        "overlapped_seconds": round(overlapped, 3),
        "serialized_seconds": round(serialized, 3),
        "ratio_limit": args.ratio_limit}))
    sys.stdout.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
