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

``--collective-check`` additionally VERIFIES the
PR 7 overlap telemetry instead of trusting it: a short traced training
on a multi-axis ``(2,2,1)`` layout mesh emits
``train.collective_s``/``collective_fraction``, and the smoke asserts
(a) every emitted fraction is exactly ``min(1, collective_s/step_s)``
of the same counter sample, and (b) the armed ``collective_s`` agrees
with an independent ``wire.measure_collective_seconds`` probe over the
same data x fsdp axes within a wall-clock band — so the overlap flags
are a checked claim before the next TPU round.

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


def _collective_check(steps, batch_size, bucket_mb):
    """Traced (2,2,1)-layout training; returns (record, ok) asserting
    the emitted collective counters against themselves and against an
    independent wire probe (see module docstring)."""
    import json as _json
    import tempfile

    import numpy as np

    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import get_policy, set_seed
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.parallel import LayoutSharding, MeshLayout
    from bigdl_tpu.parallel import wire as wire_mod
    from bigdl_tpu.utils.engine import Engine

    set_seed(11)
    rng = np.random.default_rng(3)
    n = batch_size * steps
    xs = rng.normal(0.0, 1.0, size=(n, 64)).astype(np.float32)
    ys = rng.integers(0, 8, size=n)
    model = nn.Sequential(nn.Linear(64, 64, with_bias=False), nn.ReLU(),
                          nn.Linear(64, 8, with_bias=False))
    ds = DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(batch_size, drop_last=True))

    Engine.reset()
    layout = MeshLayout(2, 2, 1)
    layout.install(jax.devices()[:4])
    trace_dir = tempfile.mkdtemp(prefix="fused_smoke_trace_")
    os.environ["BIGDL_TPU_TRACE"] = trace_dir
    os.environ["BIGDL_TPU_WIRE_BUCKET_MB"] = str(bucket_mb)
    try:
        opt = (Optimizer(model, ds, nn.CrossEntropyCriterion(),
                         strategy=LayoutSharding(model, min_size=0))
               .set_optim_method(SGD(learning_rate=0.05))
               .set_end_when(Trigger.max_iteration(steps))
               .set_log_interval(1))
        opt.optimize()
    finally:
        os.environ.pop("BIGDL_TPU_TRACE", None)
        os.environ.pop("BIGDL_TPU_WIRE_BUCKET_MB", None)

    samples = []
    for name in os.listdir(trace_dir):
        if not name.startswith("trace."):
            continue
        with open(os.path.join(trace_dir, name)) as f:
            try:
                events = _json.load(f).get("traceEvents", [])
            except ValueError:
                continue
        for ev in events:
            if ev.get("ph") == "C" and ev.get("name") == "train":
                a = ev.get("args", {})
                if "collective_s" in a and "step_s" in a:
                    samples.append((float(a["collective_s"]),
                                    float(a["collective_fraction"]),
                                    float(a["step_s"])))
    # (a) internal consistency: fraction IS min(1, collective_s/step_s)
    # of the same sample — the counter plumbing cannot drift.  Trace
    # counter args are rounded to 1e-6 (telemetry.Tracer.counter), so
    # the recompute carries a small relative band.
    def _frac_ok(cs, frac, ss):
        expect = min(1.0, cs / max(ss, 1e-9))
        return abs(frac - expect) <= 0.02 * expect + 1e-5

    consistent = bool(samples) and all(_frac_ok(*s) for s in samples)
    # (b) independent probe over the same multi-axis reduce
    mesh = Engine.mesh()
    probe_s = wire_mod.measure_collective_seconds(
        mesh, model.params, get_policy().wire_dtype, bucket_mb=bucket_mb,
        axis=("data", "fsdp"))
    armed_s = samples[0][0] if samples else 0.0
    ratio = armed_s / probe_s if probe_s > 0 else None
    # generous wall-clock band: both measure the SAME jitted reduce, but
    # on separate runs of a ~10us CPU kernel
    in_band = (armed_s > 0 and probe_s > 0
               and ratio is not None and 0.02 <= ratio <= 50.0)
    rec = {
        "samples": len(samples),
        "fraction_consistent": consistent,
        "armed_collective_s": round(armed_s, 8),
        "probe_collective_s": round(probe_s, 8),
        "armed_over_probe": round(ratio, 4) if ratio is not None else None,
        "probe_in_band": in_band,
    }
    return rec, consistent and in_band


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for smoke runs")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--bucket-mb", type=float, default=0.25,
                    help="BIGDL_TPU_WIRE_BUCKET_MB for the fused run")
    ap.add_argument("--collective-check", action="store_true",
                    help="also verify the collective_s/collective_fraction "
                         "counters against an independent wire probe on a "
                         "(2,2,1) layout mesh (forces 4 virtual CPU "
                         "devices)")
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU devices for --collective-check")
    args = ap.parse_args(argv)

    if args.collective_check:
        # multi-axis mesh needs virtual devices BEFORE backend init
        from bigdl_tpu.utils.platform import force_cpu
        force_cpu(args.devices)
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
    if args.collective_check and jax.device_count() >= 4:
        cc, cc_ok = _collective_check(max(args.steps, 3), args.batch_size,
                                      args.bucket_mb)
        record["collective_check"] = cc
        record["ok"] = ok = ok and cc_ok
        record["wall_s"] = round(time.perf_counter() - t0, 2)
    elif args.collective_check:
        record["collective_check"] = {
            "skipped": f"need >= 4 devices, have {jax.device_count()}"}
    print(json.dumps(record))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
