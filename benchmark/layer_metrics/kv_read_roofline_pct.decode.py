"""Share of a decode step's time that the keys and values it cannot avoid
reading would take at the chip's memory bandwidth: the configuration's own
``kv_read_min_bytes(cfg, active, mean_position)`` (every window layer's ring
whole and, of every layer whose cache grows, the rows a slot has behind it)
over the mean run of the program named ``jit_decode_step`` on the trace's
``XLA Modules`` line times ``hbm_bytes_per_s`` (benchmark/peaks.json).

It counts the same work whatever implements the read, so it is what a
decode-attention kernel would be held to; ``step_hbm_roofline_pct.decode``
counts the weights of the same step over the same time, so the two together
cannot pass 100 %.  ``active`` is the tokens a decode step put out, as that
reader takes it; ``mean_position`` is the mean, over the traced window's
decode steps and their rows, of the positions a row's attention may read
(``slot_positions`` over the tokens those steps put out, both ``stats()``
deltas).  Nothing where the configuration has no such function, the engine
has no such counter, no program has that name, or no step ran."""

import os

NAME = "kv_read_roofline_pct.decode"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_per_s"

PROGRAM = "jit_decode_step"


def read(facts):
    from benchmark import harness
    trace, c, cfg = (facts.get("trace"), facts.get("trace_counts"),
                     facts.get("cfg"))
    if not trace or not c or not cfg or not c.get("decode_steps") \
            or c.get("slot_positions") is None:
        return None
    runs = [(n, s) for name, n, s in trace.get("modules") or ()
            if name == PROGRAM and n]
    path = os.path.join(harness.BENCH_DIR, "configs", cfg["name"] + ".py")
    if not runs or not os.path.exists(path):
        return None
    cm = harness.load_module(path, "bench_config_" + cfg["name"])
    if not hasattr(cm, "kv_read_min_bytes"):
        return None
    tokens = c["tokens_out"] - c["prefill_steps"]
    if tokens <= 0:
        return None
    active = tokens / c["decode_steps"]
    mean_position = c["slot_positions"] / tokens
    step_s = sum(s for _n, s in runs) / sum(n for n, _s in runs)
    peak = harness.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * cm.kv_read_min_bytes(cfg, active, mean_position) \
        / (step_s * peak)
