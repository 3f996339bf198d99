#!/usr/bin/env python
"""Fused-arithmetic smoke: prove the multi-tensor optimizer update and
bucketed gradient wire preserve training numerics end-to-end
(optim/fused.py + parallel/wire.py — docs/performance.md "Step
arithmetic & overlap").

Runs the SAME 5-step LeNet training twice in one process — baseline,
then with BIGDL_TPU_FUSED_UPDATE=1 and a bucketed wire
(BIGDL_TPU_WIRE_BUCKET_MB) — and asserts the per-step loss sequence and
final params are BIT-identical (replicated mesh: fusing changes kernel
granularity, never the scalar expression).

Prints ONE JSON line:

    {"metric": "fused_smoke", "ok": true, "steps": 5,
     "losses_bit_identical": true, "params_bit_identical": true, ...}

A CPU drill of the fused step arithmetic; safe anywhere (tiny model,
seconds of wall clock).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _train(steps, batch_size):
    import numpy as np

    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import set_seed
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import Adam, Optimizer, Trigger

    set_seed(7)
    rng = np.random.default_rng(0)
    n = batch_size * steps
    xs = rng.normal(0.0, 0.1, size=(n, 28, 28, 1)).astype(np.float32)
    ys = rng.integers(0, 10, size=n)
    model = LeNet5(10)
    ds = DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(batch_size, drop_last=True))

    losses = []

    class Cap:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(Cap()))
    opt.optimize()
    params = [np.asarray(p) for p in jax.tree.leaves(model.params)]
    return losses, params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for smoke runs")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--bucket-mb", type=float, default=0.25,
                    help="BIGDL_TPU_WIRE_BUCKET_MB for the fused run")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    import numpy as np

    import jax

    for knob in ("BIGDL_TPU_FUSED_UPDATE", "BIGDL_TPU_WIRE_BUCKET_MB"):
        os.environ.pop(knob, None)
    t0 = time.perf_counter()
    losses0, params0 = _train(args.steps, args.batch_size)
    os.environ["BIGDL_TPU_FUSED_UPDATE"] = "1"
    os.environ["BIGDL_TPU_WIRE_BUCKET_MB"] = str(args.bucket_mb)
    losses1, params1 = _train(args.steps, args.batch_size)
    wall = time.perf_counter() - t0

    losses_ok = losses1 == losses0 and len(losses0) >= args.steps
    params_ok = len(params1) == len(params0) and all(
        a.dtype == b.dtype and np.array_equal(a, b)
        for a, b in zip(params1, params0))
    ok = losses_ok and params_ok
    record = {
        "metric": "fused_smoke",
        "ok": ok,
        "steps": args.steps,
        "losses_bit_identical": losses_ok,
        "params_bit_identical": params_ok,
        "loss_first": losses0[0] if losses0 else None,
        "loss_last": losses0[-1] if losses0 else None,
        "bucket_mb": args.bucket_mb,
        "wall_s": round(wall, 2),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
