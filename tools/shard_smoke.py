#!/usr/bin/env python
"""Mesh-layout smoke: prove FSDP/TP sharding on a simulated 4-device
host mesh preserves training numerics AND delivers the 1/N per-device
parameter footprint (parallel/layout.py + LayoutSharding —
docs/parallelism.md).

Runs the SAME 5-step MLP training three times in one process on 4
virtual CPU devices — pure data parallelism ``(4,1,1)`` as the
baseline, then ``(2,2,1)`` (DP x FSDP) and ``(1,2,2)`` (FSDP x TP) —
and asserts:

- per-device parameter bytes match the layout's expected shard
  fraction (1/fsdp, and 1/(fsdp*tp) where tp splits the kernels too);
- the per-step loss sequence matches the data-parallel baseline within
  the documented reassociation tolerance (grads reduce in a different
  collective order under sharding; the scalar math is unchanged).

Prints ONE JSON line:

    {"metric": "shard_smoke", "ok": true, "layouts": {...}, ...}

A CPU drill of the mesh/layout subsystem; safe anywhere (tiny model,
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

#: |loss(layout) - loss(DP)| bound per step: sharded grads reduce in a
#: different association order (documented in docs/parallelism.md)
LOSS_TOL = 2e-3


def _build_model():
    import bigdl_tpu.nn as nn
    # bias-free so the shard-fraction arithmetic is exact (biases are
    # small and replicated by the role table); every dim divides 4
    return nn.Sequential(
        nn.Linear(64, 256, with_bias=False), nn.ReLU(),
        nn.Linear(256, 256, with_bias=False), nn.ReLU(),
        nn.Linear(256, 8, with_bias=False))


def _train(layout_sizes, steps, batch_size):
    import numpy as np

    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import set_seed
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.parallel import LayoutSharding, MeshLayout
    from bigdl_tpu.utils import memstats
    from bigdl_tpu.utils.engine import Engine

    set_seed(7)
    rng = np.random.default_rng(0)
    n = batch_size * steps
    xs = rng.normal(0.0, 1.0, size=(n, 64)).astype(np.float32)
    ys = rng.integers(0, 8, size=n)
    ds = DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(batch_size, drop_last=True))

    model = _build_model()
    layout = MeshLayout(*layout_sizes)
    Engine.reset()
    layout.install(jax.devices()[: layout.size])

    losses = []

    class Cap:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = (Optimizer(model, ds, nn.CrossEntropyCriterion(),
                     strategy=LayoutSharding(model, min_size=0))
           .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(Cap()))
    opt.optimize()

    frac = (memstats.tree_device_bytes(model.params)
            / max(memstats.tree_total_bytes(model.params), 1))
    return losses, frac


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--devices", type=int, default=4)
    args = ap.parse_args(argv)

    # the simulated multi-device host mesh (the conftest trick):
    # XLA_FLAGS=--xla_force_host_platform_device_count=N equivalent
    from bigdl_tpu.utils.platform import force_cpu
    force_cpu(args.devices)
    import numpy as np

    import jax

    if jax.device_count() < args.devices:
        print(json.dumps({"metric": "shard_smoke", "ok": False,
                          "error": f"need {args.devices} devices, have "
                                   f"{jax.device_count()} (backend "
                                   "initialized early?)"}))
        return 1

    t0 = time.perf_counter()
    base_losses, base_frac = _train((args.devices, 1, 1), args.steps,
                                    args.batch_size)
    results = {}
    ok = len(base_losses) >= args.steps and abs(base_frac - 1.0) < 0.01
    for sizes, expect in (((2, 2, 1), 1 / 2), ((1, 2, 2), 1 / 4)):
        losses, frac = _train(sizes, args.steps, args.batch_size)
        diff = float(max(abs(a - b) for a, b in zip(losses, base_losses))) \
            if len(losses) == len(base_losses) and losses else None
        frac_ok = abs(frac - expect) < 0.05
        parity_ok = diff is not None and diff <= LOSS_TOL
        results[f"{sizes[0]}x{sizes[1]}x{sizes[2]}"] = {
            "param_fraction_per_device": round(frac, 4),
            "param_fraction_expected": expect,
            "fraction_ok": frac_ok,
            "max_loss_diff_vs_dp": diff,
            "parity_ok": parity_ok,
        }
        ok = ok and frac_ok and parity_ok
    print(json.dumps({
        "metric": "shard_smoke",
        "ok": ok,
        "steps": args.steps,
        "loss_first": base_losses[0] if base_losses else None,
        "loss_last": base_losses[-1] if base_losses else None,
        "loss_tol": LOSS_TOL,
        "layouts": results,
        "wall_s": round(time.perf_counter() - t0, 2),
        "backend": jax.default_backend(),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
