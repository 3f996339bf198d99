"""Mean time the optimizer loop's blocking ``next(data_iter)`` took, over
the window's iterations: the Optimizer's own ``data`` spans
(``telemetry.complete("data", ..., neval=n)``), kept in memory in the traced
run."""

NAME = "data_wait_ms.train"
UNIT = "ms"
LAYER = "dataset"
MOVES = "train_records_per_s"


def read(facts):
    steps = set(facts.get("window_steps") or ())
    durs = [ev["dur"] / 1e3 for ev in facts.get("spans") or ()
            if ev.get("name") == "data" and ev.get("ph") == "X"
            and (ev.get("args") or {}).get("neval") in steps]
    return sum(durs) / len(durs) if durs else None
