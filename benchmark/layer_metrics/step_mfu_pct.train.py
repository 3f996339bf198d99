"""Utilisation of the compiled step while it runs: the configuration's own
``model_flops_per_record`` x the global batch, over the step's device time
(step_device_ms.train) and the bf16 peak of the chips used
(benchmark/peaks.json).  Not a kernel's roofline share and not an end-to-end
MFU: idle time is left out on purpose (device_idle_pct.train has it)."""

import os

NAME = "step_mfu_pct.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_records_per_s"


def read(facts):
    from benchmark import harness
    step = harness.load_module(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "step_device_ms.train.py"), "_step_device_ms").read(facts)
    if step is None:
        return None
    peak = harness.peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    flops = facts["flops_per_record"] * facts["batch"]
    return 100.0 * flops / (step / 1e3) / (peak * facts["n_dev"])
