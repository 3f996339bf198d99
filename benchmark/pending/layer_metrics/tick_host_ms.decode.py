"""Host time of a tick in which the device is not being waited for: the
median, over the window's ``decode.tick`` spans (each a pass that did work),
of the tick less the ``decode.fetch`` spans inside it (the one
``jax.device_get`` of a step's or a prefill's tokens: the host blocked on
the device and the transfer).  What is left is the host's own: the queue,
building ``tok`` and ``pos``, the calls up to their return, the loop over
the slots, the counters.  Nothing where the window holds no ``decode.fetch``
(a program before the spans were split: the whole tick is not the host's)."""

import statistics

NAME = "tick_host_ms.decode"
UNIT = "ms"
LAYER = "decode engine"
MOVES = "request_p95_ms"


def read(facts):
    from benchmark import span_reduce
    w = span_reduce.window(facts)
    if w is None:
        return None
    fetches = span_reduce.named(facts, "decode.fetch")
    starts = [f["ts"] for f in fetches]
    own, any_fetch = [], False
    for t in span_reduce.started_in(span_reduce.named(facts, "decode.tick"),
                                    w):
        inside = span_reduce.held_by(t, fetches, starts)
        any_fetch = any_fetch or bool(inside)
        own.append((t["dur"] - sum(f["dur"] for f in inside)) / 1e3)
    return statistics.median(own) if any_fetch else None
