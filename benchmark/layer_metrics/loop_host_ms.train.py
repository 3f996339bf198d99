"""Host time of one pass of the optimizer loop in which it waits neither
for data nor for the device: over the window's iterations, the median of
the loop's ``iteration`` span less its ``data`` and ``loss_fetch`` spans of
the same ``neval`` (``optim/optimizer.py`` ``_optimize_impl``; the spans are
kept in memory in the traced run).  The median, not the mean: the driver's
own hook starts and stops the profiler inside two of the window's
iterations."""

import statistics

NAME = "loop_host_ms.train"
UNIT = "ms"
LAYER = "optimizer loop"
MOVES = "train_records_per_s"


def read(facts):
    steps = set(facts.get("window_steps") or ())
    whole, waits = {}, {}
    for ev in facts.get("spans") or ():
        n = (ev.get("args") or {}).get("neval")
        if ev.get("ph") != "X" or n not in steps:
            continue
        if ev.get("name") == "iteration":
            whole[n] = ev["dur"] / 1e3
        elif ev.get("name") in ("data", "loss_fetch"):
            waits[n] = waits.get(n, 0.0) + ev["dur"] / 1e3
    own = [ms - waits.get(n, 0.0) for n, ms in whole.items()]
    return statistics.median(own) if own else None
