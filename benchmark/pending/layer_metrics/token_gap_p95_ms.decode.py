"""The gap a caller sees between two tokens, 95th percentile.  Every active
slot gets one token a tick, so the gaps are the start-to-start periods of
consecutive ``decode.tick`` spans of the window that hold a ``decode.step``,
each counted once for every slot that waited through it: the later tick's
``active`` argument, the slots it carried over from the tick before.  A
tick that admits holds every such slot still for its prefills, which shows
here and in no mean."""

NAME = "token_gap_p95_ms.decode"
UNIT = "ms"
LAYER = "decode engine"
MOVES = "request_p95_ms"


def read(facts):
    from benchmark import span_reduce
    w = span_reduce.window(facts)
    if w is None:
        return None
    steps = span_reduce.named(facts, "decode.step")
    starts = [s["ts"] for s in steps]
    ticks = [t for t in span_reduce.started_in(
        span_reduce.named(facts, "decode.tick"), w)
        if span_reduce.held_by(t, steps, starts)]
    gaps = [(b["ts"] - a["ts"]) / 1e3 for a, b in zip(ticks, ticks[1:])]
    waited = [(b.get("args") or {}).get("active", 0) for b in ticks[1:]]
    return span_reduce.weighted_quantile(gaps, waited, 0.95)
