#!/usr/bin/env python
"""Pipeline + expert-parallel smoke: prove the 5-axis MeshLayout's two
new axes AND the pipeline-schedule A/B on a simulated 4-device host mesh
(parallel/pipeline + parallel/schedule + parallel/expert + LayoutSharding
— docs/parallelism.md).

Runs 5-step trainings in one process on 4 virtual CPU devices:

- **pipe**: a Sequential MLP is split by ``partition_pipeline`` into 2
  structurally identical stages and trained on a ``(1,1,1,2,1)`` layout
  — stacked stage params shard ``P('pipe')``, the GPipe microbatched
  schedule runs inside the ordinary compiled step.  Asserts per-device
  stage-stack bytes == 1/2, loss parity vs the unpartitioned ``(4,1,1)``
  DP baseline, and that the traced run emits the
  ``train.pipe_bubble_fraction`` counter.
- **expert**: the same body with a capacity-routed ``MoEFFN`` trained on
  ``(1,1,1,1,2)`` — expert tables (role ``expert_table``) shard
  ``P('expert')``.  Asserts per-device table bytes == 1/2 and loss
  parity vs the single-device run of the identical model.
- **schedule A/B (ISSUE 13)**: a 4-block MLP trained twice at equal
  m=8 on the pipe=2 mesh — classic GPipe (2 stages) vs 1F1B with 2
  virtual stages per device (4 interleaved slices).  Asserts the
  emitted ``train.pipe_bubble_fraction`` is STRICTLY lower under 1F1B
  (1/17 vs 1/9), the 5-step loss sequences match within the pinned
  reassociation tolerance, the compiled step's XLA temp budget (peak
  live activations) is <= GPipe's, and the schedule table's analytic
  in-flight microbatch count is below GPipe's keep-all-m.

Prints ONE JSON line:

    {"metric": "pipeline_smoke", "ok": true, "runs": {...}, ...}

A CPU drill of the pipeline/expert promotion AND the schedule claims;
safe anywhere (tiny models, seconds of wall clock).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

#: |loss(layout) - loss(baseline)| bound per step: sharded programs
#: reduce in a different association order (docs/parallelism.md); the
#: 1F1B backward accumulates stage grads in its own deterministic
#: (schedule) order, pinned by the same bound
LOSS_TOL = 2e-3


def _mlp():
    """Two identical blocks + a head: the repeated-block body
    partition_pipeline needs; every dim divides 4, bias-free so the
    shard-fraction arithmetic is exact."""
    import bigdl_tpu.nn as nn
    return nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))


def _mlp4():
    """Four identical blocks + a head — splits into 2 stages (GPipe)
    or 4 virtual slices (interleaved 1F1B) of the same params."""
    import bigdl_tpu.nn as nn
    return nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))


def _moe_mlp():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.parallel import MoEFFN
    return nn.Sequential(
        nn.Linear(64, 32, with_bias=False), nn.ReLU(),
        MoEFFN(32, 64, num_experts=4, capacity_factor=4.0),
        nn.Linear(32, 8, with_bias=False))


def _dataset(steps, batch):
    import numpy as np
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    n = batch * steps
    xs = rng.normal(0.0, 1.0, size=(n, 64)).astype(np.float32)
    ys = rng.integers(0, 8, size=n)
    return DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(batch, drop_last=True))


def _train(model, layout_sizes, steps, batch):
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.parallel import LayoutSharding, MeshLayout
    from bigdl_tpu.utils.engine import Engine

    layout = MeshLayout(*layout_sizes)
    Engine.reset()
    layout.install(jax.devices()[: layout.size])

    losses = []

    class Cap:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = (Optimizer(model, _dataset(steps, batch), nn.CrossEntropyCriterion(),
                     strategy=LayoutSharding(model, min_size=0))
           .set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(Cap()))
    opt.optimize()
    return losses, opt


def _frac(tree):
    from bigdl_tpu.utils import memstats
    return (memstats.tree_device_bytes(tree)
            / max(memstats.tree_total_bytes(tree), 1))


def _traced_train(model, layout_sizes, steps, batch):
    """_train under an armed tracer; returns (losses, opt, trace blob,
    last emitted train.pipe_bubble_fraction counter value)."""
    trace_dir = tempfile.mkdtemp(prefix="pipeline_smoke_trace_")
    os.environ["BIGDL_TPU_TRACE"] = trace_dir
    try:
        losses, opt = _train(model, layout_sizes, steps, batch)
    finally:
        os.environ.pop("BIGDL_TPU_TRACE", None)
    blob, bubble = "", None
    for name in os.listdir(trace_dir):
        if not name.startswith("trace."):
            continue
        with open(os.path.join(trace_dir, name)) as f:
            text = f.read()
        blob += text
        try:
            for ev in json.loads(text).get("traceEvents", []):
                if ev.get("ph") == "C" and ev.get("name") == "train":
                    val = ev.get("args", {}).get("pipe_bubble_fraction")
                    if val is not None:
                        bubble = float(val)
        except ValueError:
            pass
    return losses, opt, blob, bubble


def _compiled_temp_bytes(model_fn, num_stages, batch):
    """XLA temp (peak scratch) budget of the real compiled train step
    for the CURRENT schedule env knobs — the memstats proxy the A/B
    memory claim is asserted on."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.parallel import (LayoutSharding, MeshLayout,
                                    partition_pipeline)
    from bigdl_tpu.utils import memstats
    from bigdl_tpu.utils.engine import Engine

    jax.clear_caches()
    Engine.reset()
    mesh = MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
    model = model_fn()
    model.build(jax.random.key(0))
    model = partition_pipeline(model, num_stages)
    opt = Optimizer(model, dataset=None, criterion=nn.CrossEntropyCriterion(),
                    end_trigger=Trigger.max_iteration(1),
                    strategy=LayoutSharding(model, min_size=0))
    opt.set_optim_method(SGD(learning_rate=0.05))
    step, param_sh, data_sh = opt._build_step(mesh)
    rng = np.random.default_rng(0)
    inp = jax.device_put(
        jnp.asarray(rng.normal(size=(batch, 64)), jnp.float32), data_sh)
    tgt = jax.device_put(
        jnp.asarray(rng.integers(0, 8, size=batch), jnp.int32), data_sh)
    params = jax.device_put(model.params, param_sh)
    opt_state = jax.device_put(opt.optim_method.init_state(model.params),
                               opt._opt_sh)
    args = (params, model.state, opt_state, inp, tgt, jnp.float32(0.05),
            jax.random.key(1))
    ma = memstats.compiled_memory_analysis(step.lower(*args).compile())
    return (ma or {}).get("temp_bytes")


def _set_env(**kv):
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--ab-microbatches", type=int, default=8)
    ap.add_argument("--ab-mem-batch", type=int, default=256,
                    help="batch for the A/B compiled-memory comparison "
                         "(activations must dominate the fixed stash)")
    args = ap.parse_args(argv)

    from bigdl_tpu.utils.platform import force_cpu
    force_cpu(args.devices)
    import jax

    if jax.device_count() < args.devices:
        print(json.dumps({"metric": "pipeline_smoke", "ok": False,
                          "error": f"need {args.devices} devices, have "
                                   f"{jax.device_count()} (backend "
                                   "initialized early?)"}))
        return 1

    from bigdl_tpu.common import set_seed
    from bigdl_tpu.parallel import (GPipeSequential, build_schedule,
                                    partition_pipeline)

    t0 = time.perf_counter()
    runs = {}

    # ---- pipe=2 vs the (4,1,1) DP baseline ---------------------------
    set_seed(7)
    base = _mlp()
    base_losses, _ = _train(base, (4, 1, 1), args.steps, args.batch_size)
    set_seed(7)
    plain = _mlp()
    plain.build()  # same seed -> identical init as the baseline run
    piped = partition_pipeline(plain, 2)
    # the traced run must emit the bubble counter: arm the tracer
    pipe_losses, _, trace_blob, _ = _traced_train(
        piped, (1, 1, 1, 2, 1), args.steps, args.batch_size)
    bubble_emitted = "pipe_bubble_fraction" in trace_blob
    stacked = next(p for c, p in zip(piped.modules, piped.params)
                   if isinstance(c, GPipeSequential))
    pipe_frac = _frac(stacked)
    pipe_diff = (max(abs(a - b) for a, b in zip(pipe_losses, base_losses))
                 if len(pipe_losses) == len(base_losses) and pipe_losses
                 else None)
    runs["pipe_1x1x1x2x1"] = {
        "stage_param_fraction_per_device": round(pipe_frac, 4),
        "fraction_ok": abs(pipe_frac - 0.5) < 0.01,
        "max_loss_diff_vs_dp": pipe_diff,
        "parity_ok": pipe_diff is not None and pipe_diff <= LOSS_TOL,
        "pipe_bubble_fraction_emitted": bubble_emitted,
    }

    # ---- expert=2 vs the single-device run of the same model ---------
    set_seed(7)
    moe_base = _moe_mlp()
    moe_base_losses, _ = _train(moe_base, (1, 1, 1), args.steps,
                                args.batch_size)
    set_seed(7)
    moe = _moe_mlp()
    moe_losses, _ = _train(moe, (1, 1, 1, 1, 2), args.steps,
                           args.batch_size)
    tables = {k: moe.params[2][k] for k in ("w1", "w2", "b1", "b2")}
    moe_frac = _frac(tables)
    moe_diff = (max(abs(a - b) for a, b in zip(moe_losses, moe_base_losses))
                if len(moe_losses) == len(moe_base_losses) and moe_losses
                else None)
    runs["expert_1x1x1x1x2"] = {
        "table_param_fraction_per_device": round(moe_frac, 4),
        "fraction_ok": abs(moe_frac - 0.5) < 0.01,
        "max_loss_diff_vs_dense": moe_diff,
        "parity_ok": moe_diff is not None and moe_diff <= LOSS_TOL,
    }

    # ---- schedule A/B: GPipe vs interleaved 1F1B at equal m ----------
    m_ab = args.ab_microbatches
    virt = 2
    _set_env(BIGDL_TPU_PIPE_MICROBATCHES=m_ab,
             BIGDL_TPU_PIPE_SCHEDULE=None,
             BIGDL_TPU_PIPE_VIRTUAL_STAGES=None)
    set_seed(13)
    g_model = _mlp4()
    g_model.build()
    g_piped = partition_pipeline(g_model, 2)
    g_losses, _, _, g_bubble = _traced_train(
        g_piped, (1, 1, 1, 2, 1), args.steps, args.batch_size)
    g_temp = _compiled_temp_bytes(_mlp4, 2, args.ab_mem_batch)

    _set_env(BIGDL_TPU_PIPE_SCHEDULE="1f1b",
             BIGDL_TPU_PIPE_VIRTUAL_STAGES=virt)
    set_seed(13)
    f_model = _mlp4()
    f_model.build()
    f_piped = partition_pipeline(f_model, 2 * virt)
    f_losses, _, _, f_bubble = _traced_train(
        f_piped, (1, 1, 1, 2, 1), args.steps, args.batch_size)
    f_temp = _compiled_temp_bytes(_mlp4, 2 * virt, args.ab_mem_batch)
    _set_env(BIGDL_TPU_PIPE_SCHEDULE=None,
             BIGDL_TPU_PIPE_VIRTUAL_STAGES=None,
             BIGDL_TPU_PIPE_MICROBATCHES=None)

    ab_diff = (max(abs(a - b) for a, b in zip(f_losses, g_losses))
               if len(f_losses) == len(g_losses) and f_losses else None)
    # analytic in-flight bound off the actual table: GPipe's autodiff
    # backward keeps every microbatch's activations (m * v slices)
    f_inflight = build_schedule("1f1b", 2, m_ab, virt).peak_inflight
    g_inflight = m_ab  # v=1: one stage slice per device, all m live
    runs["ab_gpipe_vs_1f1b"] = {
        "microbatches": m_ab,
        "virtual_stages": virt,
        "gpipe_bubble_fraction": g_bubble,
        "onef1b_bubble_fraction": f_bubble,
        "bubble_strictly_lower": (g_bubble is not None
                                  and f_bubble is not None
                                  and f_bubble < g_bubble),
        "max_loss_diff": ab_diff,
        "parity_ok": ab_diff is not None and ab_diff <= LOSS_TOL,
        "gpipe_step_temp_bytes": g_temp,
        "onef1b_step_temp_bytes": f_temp,
        "mem_batch": args.ab_mem_batch,
        "temp_bytes_ok": (g_temp is not None and f_temp is not None
                          and f_temp <= g_temp),
        "gpipe_inflight_microbatches": g_inflight,
        "onef1b_inflight_microbatches": f_inflight,
        "inflight_ok": f_inflight < g_inflight,
    }

    ab = runs["ab_gpipe_vs_1f1b"]
    ok = (len(base_losses) >= args.steps
          and all(r.get("fraction_ok", True) and r.get("parity_ok")
                  for r in runs.values())
          and bubble_emitted
          and ab["bubble_strictly_lower"]
          and ab["temp_bytes_ok"]
          and ab["inflight_ok"])
    print(json.dumps({
        "metric": "pipeline_smoke",
        "ok": ok,
        "steps": args.steps,
        "loss_tol": LOSS_TOL,
        "runs": runs,
        "wall_s": round(time.perf_counter() - t0, 2),
        "backend": jax.default_backend(),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
