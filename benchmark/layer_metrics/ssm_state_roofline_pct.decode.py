"""Share of the memory roofline that a decode step's recurrent-state update
reaches: the bytes it cannot avoid (the configuration's own
``ssm_update_min_bytes``: every ``ssm`` leaf of every slot read once and
written once) over the self time a step of the operations that do it, times
the chip's memory bandwidth (benchmark/peaks.json).

The operations are found by what is the same whatever implements the update:
an operation whose *result* holds a ``ssm`` leaf, by the leaf's shape as the
trace prints it (the configuration's ``ssm_leaf_shape``, built from ``cfg``
and the traffic's ``slots``: ``f32[<slots>,<heads held>,64,128]``), or whose
instruction's text names ``ssm_step`` (a kernel of that name).  Those read
the old state and write the new, which are the bytes counted; ``ALSO`` names,
by a text the trace prints, operations that the compiled step shows reading
the leaf a second time: their time is added, and nothing is taken off the
bytes.  A prefill's write of one row has the whole leaf as its result too,
but it is a write of part of the leaf in place (the compiled prefill shows
``dynamic-update-slice`` fusions, ``PART``), not an update of every row: it
is no part of a step and is left out, so the share does not move with the
window's mix of prefills.  The window's self seconds (``ops`` of
trace_reduce) are scaled to one step as ``flash_fwd_ms.train`` scales to
one: their share of the window's busy time, times the seconds of all
programs on the ``XLA Modules`` line, over the runs of the program named
``jit_decode_step``.  Nothing where the configuration has no such
functions, no operation matches, or no step ran."""

import os

NAME = "ssm_state_roofline_pct.decode"
UNIT = "%"
LAYER = "kernels"
MOVES = "decode_tokens_per_s"

PROGRAM = "jit_decode_step"
KERNEL = "ssm_step"
#: operations that read a ``ssm`` leaf a second time, by a text their
#: instruction holds (none: the compiled step reads each leaf once)
ALSO = ()
#: a write of part of a leaf in place (a prefill's one row), by its opcode
#: or the name a fusion of it gets: not a step's update
PART = ("dynamic-update-slice", "dynamic_update_slice")


def read(facts):
    from benchmark import harness
    trace, cfg, traffic = (facts.get("trace"), facts.get("cfg"),
                           facts.get("traffic"))
    if not trace or not cfg or not traffic:
        return None
    path = os.path.join(harness.BENCH_DIR, "configs", cfg["name"] + ".py")
    if not os.path.exists(path):
        return None
    cm = harness.load_module(path, "bench_config_" + cfg["name"])
    if not hasattr(cm, "ssm_update_min_bytes"):
        return None
    modules = trace.get("modules") or ()
    steps = sum(n for name, n, _s in modules if name == PROGRAM)
    leaf = cm.ssm_leaf_shape(cfg, traffic["slots"])
    seconds = sum(s for text, s in trace.get("ops") or ()
                  if (leaf in _result(text)
                      and not any(p in text for p in PART))
                  or KERNEL in text or any(a in text for a in ALSO))
    if not steps or not seconds or not trace.get("busy_s"):
        return None
    step_s = seconds / trace["busy_s"] * sum(s for _n, _r, s in modules) \
        / steps
    peak = harness.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * cm.ssm_update_min_bytes(cfg, traffic["slots"]) \
        / (step_s * peak)


def _result(text: str) -> str:
    """The result's shapes in an instruction's text (``%name = <shape or
    (tuple of shapes)> opcode(...)``); a layout's own brackets nest."""
    rhs = text.partition(" = ")[2]
    if not rhs.startswith("("):
        return rhs.split(" ")[0]
    depth = 0
    for i, ch in enumerate(rhs):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rhs[:i + 1]
    return rhs
