#!/usr/bin/env python3
"""Run one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip(s) from start to end: load, warm up,
measure for ``--seconds``, print observations as it goes (one JSON object a
line) and the contract's result as the last line.  Anything but the cell's
``chips`` TPU devices is an error: exit code 1 and no result line.

``--rehearse`` (not used by the driver) runs the same code at the tiny sizes
kept in the data files' ``rehearse`` keys on whatever backend is there, names
that backend in ``device`` and writes "not measured" for every metric.
"""

import time

_T0 = time.perf_counter()   # process start, as near as Python lets us read it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench-dir", default=None,
                    help="another copy of benchmark/ (tests)")
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.Cell(args.workload,
                        bench_dir=args.bench_dir or harness.BENCH_DIR,
                        benchmark_json=args.benchmark_json)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      args.rehearse, _T0)
    try:
        harness.device_phase(run)
        out = cell.driver_mod.run(run)
        line = harness.result_line(run, out["e2e"], out["facts"],
                                   out["attempted"], out["failed"])
    except BaseException:  # noqa: BLE001 -- the one handler: report and fail
        traceback.print_exc()
        sys.stderr.flush()
        return 1
    run.say("done", compile_s=round(run.compiles.seconds(), 2),
            **harness.compile_cache_state())
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
