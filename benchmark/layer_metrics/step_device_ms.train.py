"""Device time of one training step, from the trace alone: the mean length
of the runs of the compiled program that took most of the traced time (the
train step; ``XLA Modules`` line, trace_reduce).  Needs no name inside the
program and no clock of the host."""

NAME = "step_device_ms.train"
UNIT = "ms"
LAYER = "model step"
MOVES = "train_records_per_s"


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("modules"):
        return None
    _name, runs, seconds = trace["modules"][0]
    return seconds / runs * 1e3
