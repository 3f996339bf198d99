#!/usr/bin/env python3
"""Run a benchmark cell with one fault planted in the program, to read on the
chip what the cell's limits of ``correct`` make of it.

A test file of a configuration keeps its faults as ``PLANTERS``, a name to a
function that takes a ``pytest.MonkeyPatch`` (the CPU tests run each at the
rehearse size).  This plants one and hands the rest of the command line to
``benchmark/run.py``, from the root of a checkout:

    python3 tools/plant_fault.py tests/benchmark/test_benchmark_jamba.py \
        state_carry --workload jamba2.decode --seed 11 --seconds 40 --trace 0

The run's ``check`` lines show each number beside its limit; its last line
should read ``"correct": false``.  ``--set key=json`` (any number of them,
before ``run.py``'s own arguments) overrides a key of the cell's traffic, as
``benchmark/control.py``'s does: what ``correct`` compares does not depend on
the load, so a fault can be read with fewer callers and slots than the cell
times (``--set clients=48 --set slots=32``).
"""

import importlib.util
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import pytest                                          # noqa: E402


def main(argv) -> int:
    path, name, rest = argv[0], argv[1], argv[2:]
    spec = importlib.util.spec_from_file_location("planters", path)
    tests = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tests)
    tests.PLANTERS[name](pytest.MonkeyPatch())
    from benchmark import harness, run
    over = {}
    while rest[:1] == ["--set"]:
        key, value = rest[1].split("=", 1)
        over[key] = json.loads(value)
        rest = rest[2:]
    if over:
        init = harness.Run.__init__

        def with_overrides(self, *args, **kwargs):
            init(self, *args, **kwargs)
            self.traffic.update(over)

        harness.Run.__init__ = with_overrides
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
