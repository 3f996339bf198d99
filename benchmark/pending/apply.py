#!/usr/bin/env python3
"""Write a copy of the benchmark with a pending cell merged in, to run the
cell from before a ``benchmark`` PR admits it:

    python3 benchmark/pending/apply.py <out-dir> [<name>]
    python3 benchmark/run.py --workload <name> --seed <n> --seconds 40 \
        --trace <0|1> --bench-dir <out-dir>/benchmark \
        --benchmark-json <out-dir>/BENCHMARK.json

``<name>.json`` beside this file holds what ``BENCHMARK.json`` would gain:
the ``workloads`` entry, the accepted metrics whose ``workloads`` list the
cell is appended to, and the new ``per_layer`` entries, whose readers lie in
``pending/layer_metrics/`` (kept out of ``layer_metrics/`` because every
file there has to have its entry).  Nothing in the repository is changed.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def merged(name: str) -> dict:
    """``BENCHMARK.json`` with the pending cell ``name`` at the end of each
    list it belongs to."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, name + ".json")) as f:
        pending = json.load(f)
    bench["workloads"] += pending["workloads"]
    cells = [w["name"] for w in pending["workloads"]]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in pending["append_cell_to_workloads_of"]:
                m["workloads"] = m["workloads"] + cells
    bench["per_layer"] += pending["per_layer"]
    return bench


def apply(out_dir: str, name: str = "gpt2m.decode.open") -> None:
    bench_out = os.path.join(out_dir, "benchmark")
    shutil.copytree(BENCH, bench_out,
                    ignore=shutil.ignore_patterns("__pycache__", "pending"))
    readers = os.path.join(HERE, "layer_metrics")
    for fn in os.listdir(readers):
        if fn.endswith(".py"):
            shutil.copy(os.path.join(readers, fn),
                        os.path.join(bench_out, "layer_metrics", fn))
    with open(os.path.join(out_dir, "BENCHMARK.json"), "w") as f:
        json.dump(merged(name), f, indent=2)


if __name__ == "__main__":
    if len(sys.argv) < 2:
        sys.exit(__doc__)
    apply(*sys.argv[1:3])
