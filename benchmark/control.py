#!/usr/bin/env python3
"""Read the numbers ``correct`` is decided on, over many seeds in one
process: the program's (sound runs) and the control's, which is the plain
reference computed in the nearest precision below the configuration's, put in
the program's place.  The limits in a configuration's file are set from these
readings (PERF.md gives them); the benchmark's own runs never run this.

    python3 benchmark/control.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 11,12,13] [--seconds 20] [--rehearse]

Prints one JSON line a seed and a last line with, for each number, the sound
runs' largest and the control's smallest.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None,
                    help="seeds that also compute the control (default: all)")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window for drivers whose readings need one")
    ap.add_argument("--set", action="append", default=[],
                    help="traffic override key=json, e.g. records=1024")
    ap.add_argument("--set-cfg", action="append", default=[],
                    help="configuration override key=json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.Cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    csel = set(seeds if args.control_seeds is None else
               (int(s) for s in args.control_seeds.split(",") if s))
    sound, low = {}, {}
    first = True
    for seed in seeds:
        run = harness.Run(cell, seed, args.seconds, False, args.rehearse, _T0)
        for kv in args.set:
            k, v = kv.split("=", 1)
            run.traffic[k] = json.loads(v)
        for kv in args.set_cfg:
            k, v = kv.split("=", 1)
            run.cfg[k] = json.loads(v)
        if first:
            harness.device_phase(run)
            first = False
        else:
            run.device = {}
        precs = tuple(cell.cfg["control_precisions"]) if seed in csel else ()
        out = cell.driver_mod.control(run, precs)
        print(json.dumps({"seed": seed, **out}), flush=True)
        for name, v in out["program"].items():
            sound.setdefault(name, []).append(v)
        for prec in precs:
            for name, v in out[prec].items():
                low.setdefault(f"{prec}:{name}", []).append(v)
    print(json.dumps({
        "sound_largest": {k: max(v) for k, v in sound.items()},
        "control_smallest": {k: min(v) for k, v in low.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
