"""Share of the traced window in which no operation ran on the device
(1 - busy / window, trace_reduce), training cells."""

NAME = "device_idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_records_per_s"


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
