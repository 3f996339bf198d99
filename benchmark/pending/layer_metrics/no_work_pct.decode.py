"""Share of the window in which the engine slept with nothing to do: the
seconds of the ``decode.idle`` spans (one a sleep of ``DecodeQueue
.wait_for_work``: no active slot and no request), cut to the window, over the
window's seconds.  It is the part of ``device_idle_pct.decode`` that is the
traffic's and not the host's.  0 where the engine never slept; nothing only
where the run kept no spans."""

NAME = "no_work_pct.decode"
UNIT = "%"
LAYER = "decode engine"
MOVES = "request_p95_ms"


def read(facts):
    from benchmark import span_reduce
    w = span_reduce.window(facts)
    if w is None or not facts.get("spans"):
        return None
    asleep = sum(max(0.0, min(e["ts"] + e["dur"], w[1]) - max(e["ts"], w[0]))
                 for e in span_reduce.named(facts, "decode.idle"))
    return 100.0 * asleep / (w[1] - w[0])
