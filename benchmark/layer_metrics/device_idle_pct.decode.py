"""Share of the traced window in which no operation ran on the device
(1 - busy / window, trace_reduce), decode cells."""

NAME = "device_idle_pct.decode"
UNIT = "%"
LAYER = "device"
MOVES = "decode_tokens_per_s"


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
