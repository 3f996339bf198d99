"""Throughput micro-benchmark CLI.

Reference: models/utils/DistriOptimizerPerf.scala (:91-95 — inception_v1/v2,
vgg16/19 at batch x 3 x 224 x 224, synthetic data, no loading) and
LocalOptimizerPerf.scala.  Same role here: time the compiled train step on
synthetic batches per model, print records/s.

Usage:
    python -m bigdl_tpu.tools.perf --model inception_v1 --batch-size 32 \
        [--iters 20] [--warmup 3]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.timing import fetch_scalar, measure_step_seconds

# zoo names, resolved through models/run._build_model so the benched step
# uses the SAME model/criterion pairing as real training (LogSoftMax heads
# pair with ClassNLL, logits heads with CrossEntropy)
_MODELS = {"inception_v1": ("inception", 1000),
           "inception_v2": ("inception_v2", 1000),
           "vgg16": ("vgg16", 1000),
           "vgg19": ("vgg19", 1000), "resnet50": ("resnet50", 1000),
           "alexnet": ("alexnet", 1000), "lenet": ("lenet", 10),
           "transformer": ("transformer", 32000)}


def run(model_name: str, batch_size: int, iters: int = 20, warmup: int = 3,
        profile_dir: str = None, num_experts: int = 0):
    from ..models.run import _build_model, build_criterion
    from ..optim import SGD, Optimizer, Trigger
    from ..utils.engine import Engine
    Engine.reset()
    Engine.init()
    mesh = Engine.mesh()
    zoo_name, classes = _MODELS[model_name]
    if num_experts and zoo_name != "transformer":
        raise ValueError(f"--num-experts applies to the transformer only; "
                         f"{model_name} would silently bench the dense "
                         "model")
    model, input_hw, crit = _build_model(zoo_name, classes, num_experts)
    criterion = build_criterion(crit)
    model.build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=criterion,
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.01, momentum=0.9))
    step, param_sh, data_sh = opt._build_step(mesh)

    params = jax.device_put(model.params, param_sh)
    net_state = model.state
    opt_state = opt.optim_method.init_state(params)
    if input_hw and input_hw[0] == "tokens":  # LM: int token sequences
        _, seq, vocab = input_hw
        r = np.random.default_rng(0)
        inp = jnp.asarray(r.integers(0, vocab, (batch_size, seq)), jnp.int32)
        tgt = jnp.asarray(r.integers(0, vocab, (batch_size, seq)), jnp.int32)
    else:
        inp = jnp.asarray(np.random.default_rng(0).standard_normal(
            (batch_size,) + input_hw), jnp.float32)
        tgt = jnp.asarray(np.random.default_rng(1).integers(
            0, classes, batch_size), jnp.float32)
    rng = jax.random.key(1)

    def one():
        nonlocal params, net_state, opt_state
        params, net_state, opt_state, loss = step(
            params, net_state, opt_state, inp, tgt, jnp.float32(0.01), rng)
        return loss

    # fetch-synced timing (utils/timing.py)
    t0 = time.perf_counter()
    fetch_scalar(one())
    compile_s = time.perf_counter() - t0
    for _ in range(max(warmup - 1, 0)):
        one()
    fetch_scalar(one())
    dt, detail = measure_step_seconds(one, n2=max(iters, 8))
    out = {"model": model_name,
        **({"num_experts": num_experts} if num_experts else {}), "batch_size": batch_size,
           "step_seconds": dt, "records_per_second": batch_size / dt,
           "compile_seconds": compile_s, "timing": detail,
           "device": str(jax.devices()[0])}
    if profile_dir:
        # xplane trace of the real compiled step (SURVEY.md §7.6)
        from ..utils.profiling import trace_steps
        out["profile_dir"] = trace_steps(one, max(iters // 2, 3), profile_dir)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description="train-step throughput bench "
                                 "(reference: DistriOptimizerPerf)")
    ap.add_argument("--model", default="inception_v1",
                    choices=sorted(_MODELS))
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler xplane trace of the step here")
    ap.add_argument("--num-experts", type=int, default=0,
                    help="transformer only: bench the Switch-style MoE "
                         "variant (parallel/expert.MoEFFN)")
    args = ap.parse_args(argv)
    print(json.dumps(run(args.model, args.batch_size, args.iters,
                         args.warmup, profile_dir=args.profile_dir,
                         num_experts=args.num_experts)))


if __name__ == "__main__":
    main()
