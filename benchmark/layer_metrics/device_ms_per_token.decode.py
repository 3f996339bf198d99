"""Device busy time for each generated token: the busy share of the traced
window (trace_reduce) times the seconds the profiler ran, over the tokens
the engine put out meanwhile (``stats()`` deltas)."""

NAME = "device_ms_per_token.decode"
UNIT = "ms"
LAYER = "model step"
MOVES = "decode_tokens_per_s"


def read(facts):
    trace, c = facts.get("trace"), facts.get("trace_counts")
    if not trace or not c or not c.get("tokens_out"):
        return None
    busy_share = trace["busy_s"] / trace["window_s"]
    return busy_share * c["seconds"] * 1e3 / c["tokens_out"]
