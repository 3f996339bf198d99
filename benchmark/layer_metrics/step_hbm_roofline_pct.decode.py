"""Share of the memory roofline that a decode step reaches: the bytes the
step cannot avoid reading (the configuration's own ``decode_step_min_bytes``:
every held weight outside the routed experts once, the routed experts' that
some token of the step selects; cache and activations left out) over the mean
run of the program named ``jit_decode_step`` on the trace's ``XLA Modules``
line times the chip's memory bandwidth (benchmark/peaks.json).  The step is
bound by memory, not by arithmetic: a token multiplies each weight it reads
once.  ``active`` is the tokens a decode step put out, from ``stats()``
deltas over the traced window (the prefills' first tokens taken off).
Nothing where the configuration has no such function, no program has that
name, or no step ran."""

import os

NAME = "step_hbm_roofline_pct.decode"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_per_s"

PROGRAM = "jit_decode_step"


def read(facts):
    from benchmark import harness
    trace, c, cfg = (facts.get("trace"), facts.get("trace_counts"),
                     facts.get("cfg"))
    if not trace or not c or not cfg or not c.get("decode_steps"):
        return None
    runs = [(n, s) for name, n, s in trace.get("modules") or ()
            if name == PROGRAM and n]
    path = os.path.join(harness.BENCH_DIR, "configs", cfg["name"] + ".py")
    if not runs or not os.path.exists(path):
        return None
    cm = harness.load_module(path, "bench_config_" + cfg["name"])
    if not hasattr(cm, "decode_step_min_bytes"):
        return None
    active = (c["tokens_out"] - c["prefill_steps"]) / c["decode_steps"]
    step_s = sum(s for _n, s in runs) / sum(n for n, _s in runs)
    peak = harness.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * cm.decode_step_min_bytes(cfg, active) / (step_s * peak)
