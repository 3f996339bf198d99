"""Device milliseconds of the Pallas flash-attention backward in one training
step: the kernels' self time in the traced window (``ops`` of trace_reduce)
over the window's busy time, times the step's device time (the dominant
``XLA Modules`` program's seconds over its runs, as flash_fwd_ms.train).

The kernels are the ``pallas_call``s named ``flash_bwd_dkv`` and
``flash_bwd_dq`` (``ops/attention.py``, PR 46).  The name reaches the trace's
``XLA Ops`` line in the name of the HLO instruction, behind the
transformations it was traced under: in the train step
``%transpose_jvp_flash_bwd_dkv__.<n> = (...) custom-call(...),
custom_call_target="tpu_custom_call"``, one of each a layer.  An operation
is a kernel if ``flash_bwd`` is in its instruction's name, left of `` = ``,
and it is a ``custom-call`` or a ``fusion ... kind=kCustom`` that XLA wrapped
round one (as it wrapped ``selective_scan`` in PR 45).  A tree whose backward
is ``jnp`` (the parent of PR 46) has no such operation and reads ``None``."""

NAME = "attn_bwd_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s"

KERNEL = "flash_bwd"


def _is_kernel(op):
    name, _, rest = op.partition(" = ")
    return KERNEL in name and (
        " custom-call(" in rest
        or (" fusion(" in rest and "kind=kCustom" in rest))


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("modules") or not trace.get("busy_s"):
        return None
    kernel_s = sum(s for op, s in trace.get("ops") or () if _is_kernel(op))
    if not kernel_s:
        return None
    _name, runs, seconds = trace["modules"][0]
    return kernel_s / trace["busy_s"] * seconds / runs * 1e3
