"""Device milliseconds of the Pallas flash-attention forward in one training
step: the kernel's self time in the traced window (``ops`` of trace_reduce)
over the window's busy time, times the step's device time (the dominant
``XLA Modules`` program's seconds over its runs, as step_device_ms.train).

The kernel is the ``pallas_call`` named ``flash_fwd`` (``ops/attention.py``).
The name reaches the trace's ``XLA Ops`` line in the name of the HLO
instruction, behind the transformations it was traced under: in the train
step ``%jvp_flash_fwd_.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``, one a layer (my chip run, PR 25).
An operation is the kernel if it is a custom call with ``flash_fwd`` in its
instruction's name; the backward pass is ``jnp`` and has no such call."""

NAME = "flash_fwd_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s"

KERNEL = "flash_fwd"


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("modules") or not trace.get("busy_s"):
        return None
    kernel_s = sum(
        s for name, s in trace.get("ops") or ()
        if KERNEL in name.split(" = ")[0] and " custom-call(" in name)
    if not kernel_s:
        return None
    _name, runs, seconds = trace["modules"][0]
    return kernel_s / trace["busy_s"] * seconds / runs * 1e3
