#!/usr/bin/env python
"""Cold-compile timing for the LeNet train step.

Why LeNet: its C_in<8 conv backward is the case the tiny-channel pad
(nn/conv.py `_pad_tiny_cin`) exists for; on a v5e its compile time with
and without the pad is not measured (docs/benchmarking.md).  Run it
twice against fresh cache dirs for the pad A/B:

    JAX_COMPILATION_CACHE_DIR=/tmp/xla_cold_pad   python tools/lenet_cold.py
    BIGDL_TPU_CONV_PAD_MIN_CIN=0 \
    JAX_COMPILATION_CACHE_DIR=/tmp/xla_cold_nopad python tools/lenet_cold.py

Prints one JSON line: wall seconds for the first optimizer iteration
(compile-dominated: the step itself is milliseconds) plus the knob state,
so the A/B is self-describing.  `--platform cpu` dry-runs the same code
path off-TPU.

`--aot-cache DIR` switches to the AOT executable-cache A/B
(utils/aot.py): the SAME training run twice in one process against DIR —
cold (compile + store) then warm (jit caches cleared, executable
deserialized from DIR) — emitting one JSON line with `compile_s_cold` /
`compile_s_warm` (time spent compiling + loading, from the aot counters)
and the hit/miss ledger.  The XLA persistent cache is disabled in this
mode so the warm number is attributable to the AOT layer alone.

`--conv-route matmul` switches to the conv-lowering A/B (ISSUE 7): the
LeNet train step built twice in one process — pad route (the default
zero-pad mitigation) vs the reshaped-matmul route
(BIGDL_TPU_CONV_ROUTE=matmul, ops/convmm.py) — emitting one JSON line
with, per route, the conv-op count of the compiled train step (the
CPU-side proxy for the 809 s TPU compile: the pathology lives in the TPU
backend's grad-of-conv emitter, so the HLO that matters is the
convolution subprogram, which the matmul route deletes outright), total
HLO size for context, compile seconds, and steady-state step seconds.
Exit 1 unless the matmul route eliminates every conv from the step AND
its step time is no worse (<= 1.25x, measurement slack).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python tools/lenet_cold.py` from the repo root (or anywhere)
# without an installed wheel — same trick as tests/conftest.py
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _make_run(batch_size):
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    rng = np.random.default_rng(0)
    n = batch_size
    xs = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    ys = rng.integers(0, 10, size=n)

    def run():
        """One fresh optimizer, one iteration: cold compile + one step.
        Returns wall seconds."""
        ds = DataSet.array(
            [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
            SampleToMiniBatch(n, drop_last=True))
        opt = (Optimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
               .set_optim_method(SGD(learning_rate=0.01))
               .set_end_when(Trigger.max_iteration(1)))
        t0 = time.perf_counter()
        opt.optimize()
        return time.perf_counter() - t0

    return run


def _aot_mode(args):
    """Cold-vs-warm A/B against the AOT executable cache: one JSON line."""
    # attribute the warm number to the AOT layer alone — no XLA disk cache
    os.environ["BIGDL_TPU_AOT_CACHE"] = args.aot_cache
    os.environ.setdefault("BIGDL_TPU_XLA_CACHE", "0")

    import jax

    from bigdl_tpu.utils import aot

    run = _make_run(args.batch_size)

    def compile_cost(before, after):
        # XLA compile time + executable-deserialize time: the "how long
        # until the step is runnable" number the acceptance bound reads
        return (after["compile_s"] - before["compile_s"] +
                after["load_s"] - before["load_s"])

    s0 = aot.stats()
    wall_cold = run()
    s1 = aot.stats()
    # drop every in-memory jit/pjit cache so the second run re-lowers and
    # must go through the persistent AOT cache, as a fresh process would
    jax.clear_caches()
    wall_warm = run()
    s2 = aot.stats()

    cold = compile_cost(s0, s1)
    warm = compile_cost(s1, s2)
    print(json.dumps({
        "metric": "lenet_aot_cold_warm",
        "compile_s_cold": round(cold, 3),
        "compile_s_warm": round(warm, 3),
        "warm_over_cold": round(warm / max(cold, 1e-9), 4),
        "wall_s_cold": round(wall_cold, 3),
        "wall_s_warm": round(wall_warm, 3),
        "aot": {k: (int(v) if k not in ("compile_s", "load_s")
                    else round(v, 3)) for k, v in s2.items()},
        "batch_size": args.batch_size,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "aot_cache_dir": args.aot_cache,
    }))
    # acceptance bound (ISSUE 6): warm must be < 20% of cold
    return 0 if warm < 0.2 * cold else 1


def _build_step(batch_size):
    """The real compiled train step (Optimizer._build_step) on device 0;
    returns (step_fn, args, hlo_text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(devices=[jax.devices()[0]])
    mesh = Engine.mesh()
    model = LeNet5(10)
    model.build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=nn.ClassNLLCriterion(),
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.01))
    step, param_sh, _ = opt._build_step(mesh)

    rng = np.random.default_rng(0)
    inp = jnp.asarray(rng.normal(size=(batch_size, 28, 28, 1)),
                      jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 10, size=batch_size), jnp.int32)
    params = jax.device_put(model.params, param_sh)
    args = (params, model.state, opt.optim_method.init_state(params),
            inp, tgt, jnp.float32(0.01), jax.random.key(1))
    hlo = step.lower(*args).as_text()
    return step, args, hlo


def _conv_route_mode(args):
    """Pad-vs-matmul conv-lowering A/B on the LeNet train step."""
    import jax

    results = {}
    for route in ("pad", args.conv_route):
        os.environ["BIGDL_TPU_CONV_ROUTE"] = route
        jax.clear_caches()
        step, step_args, hlo = _build_step(args.batch_size)
        t0 = time.perf_counter()
        compiled = step.lower(*step_args).compile()
        compile_s = time.perf_counter() - t0
        opt_hlo = compiled.as_text()
        out = step(*step_args)
        jax.block_until_ready(out)
        # steady state: params/opt_state threaded so shapes stay fixed
        params, net_state, opt_state = out[0], out[1], out[2]
        t0 = time.perf_counter()
        iters = 10
        for _ in range(iters):
            params, net_state, opt_state, loss = step(
                params, net_state, opt_state, *step_args[3:])
        jax.block_until_ready(loss)
        results[route] = {
            # the pathology metric: convolution ops in the COMPILED step
            # (each is a program the TPU conv emitter must lower; the
            # 809 s case is one grad-of-conv among these)
            "hlo_conv_ops": opt_hlo.count(" convolution"),
            "hlo_ops": opt_hlo.count("\n"),
            "stablehlo_ops": hlo.count("\n"),
            "compile_s": round(compile_s, 3),
            "step_s": round((time.perf_counter() - t0) / iters, 6),
        }
    pad, mm = results["pad"], results[args.conv_route]
    ok = (mm["hlo_conv_ops"] == 0 and pad["hlo_conv_ops"] > 0
          and mm["step_s"] <= 1.25 * pad["step_s"])
    print(json.dumps({
        "metric": "lenet_conv_route_ab",
        "routes": results,
        "conv_ops_eliminated": pad["hlo_conv_ops"] - mm["hlo_conv_ops"],
        "step_ratio": round(mm["step_s"] / max(pad["step_s"], 1e-9), 4),
        "batch_size": args.batch_size,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "ok": ok,
    }))
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for smoke runs")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--aot-cache", metavar="DIR", default=None,
                    help="AOT executable-cache mode: run cold then warm "
                         "against DIR, emit compile_s_cold/compile_s_warm; "
                         "exit 1 unless warm < 20%% of cold")
    ap.add_argument("--conv-route", metavar="ROUTE", default=None,
                    choices=["matmul", "lax"],
                    help="conv-lowering A/B mode: pad route vs ROUTE on "
                         "the train step, one JSON line; exit 1 unless "
                         "ROUTE's HLO is smaller with step time no worse")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass
    if args.aot_cache:
        return _aot_mode(args)
    if args.conv_route:
        return _conv_route_mode(args)
    import jax

    run = _make_run(args.batch_size)
    dt = run()
    # where Engine.init put the persistent cache (utils/platform.py)
    from bigdl_tpu.utils import config
    from bigdl_tpu.utils.platform import CHECKOUT_CACHE_DIR
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or CHECKOUT_CACHE_DIR) \
        if config.get_bool("XLA_CACHE", True) else None
    print(json.dumps({
        "metric": "lenet_cold_compile_seconds",
        "value": round(dt, 3),
        "batch_size": args.batch_size,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "conv_pad_min_cin": os.environ.get("BIGDL_TPU_CONV_PAD_MIN_CIN",
                                           "default(8)"),
        "xla_cache_dir": cache_dir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
