"""Share of the device's program time that is admission prefill: seconds of
the programs named ``jit_decode_prefill`` over the seconds of all programs
of the traced window (the trace's ``XLA Modules`` line, trace_reduce;
``DecodeEngine`` names its two jitted functions ``decode_prefill`` and
``decode_step``).  Nothing where no program has that name."""

NAME = "prefill_share_pct.decode"
UNIT = "%"
LAYER = "decode engine"
MOVES = "decode_tokens_per_s"


def read(facts):
    trace = facts.get("trace")
    modules = trace.get("modules") if trace else None
    if not modules:
        return None
    prefill = sum(s for name, _runs, s in modules
                  if name == "jit_decode_prefill")
    if not prefill:
        return None
    return 100.0 * prefill / sum(s for _name, _runs, s in modules)
