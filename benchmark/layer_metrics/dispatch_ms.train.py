"""Host time of the call of the compiled step, its argument expressions
included: the median of the optimizer loop's ``dispatch`` spans over the
window's iterations (``optim/optimizer.py`` ``_optimize_impl``; kept in
memory in the traced run)."""

import statistics

NAME = "dispatch_ms.train"
UNIT = "ms"
LAYER = "optimizer loop"
MOVES = "train_records_per_s"


def read(facts):
    steps = set(facts.get("window_steps") or ())
    durs = [ev["dur"] / 1e3 for ev in facts.get("spans") or ()
            if ev.get("name") == "dispatch" and ev.get("ph") == "X"
            and (ev.get("args") or {}).get("neval") in steps]
    return statistics.median(durs) if durs else None
