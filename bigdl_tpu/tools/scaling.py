"""Scaling-efficiency measurement on a virtual device mesh.

BASELINE.md's scaling target ("linear, 8 -> 64 chips") cannot be measured
without that many chips, so this tool produces a simulated form on virtual
CPU devices (counts and proxies, never device numbers):

1. **Collective introspection** — compile the real distributed train step
   (Optimizer._build_step) over an n-device mesh and count the XLA
   collectives in the optimized HLO.  Sync data-parallel SGD must lower to
   gradient all-reduce(s) riding the mesh (the in-XLA form of the
   reference's reduce-scatter + lazy allgather over the Spark block manager,
   parameters/AllReduceParameter.scala:53-60) — and must NOT contain
   host transfers.
2. **Virtual throughput ratio** — per-device throughput with the same
   per-device batch on a 1-device vs an n-device CPU mesh.  On virtual CPU
   devices all n "chips" share the host's cores, so this UNDERSTATES real
   efficiency (ICI is free of core contention); it is a smoke check that
   per-step overhead does not explode with mesh width, not a TPU number.

Usage:  python -m bigdl_tpu.tools.scaling [--devices 8] [--batch-per-device 64]
Prints one JSON object.
"""

from __future__ import annotations

import argparse
import json
import sys


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def collective_counts(hlo_text: str) -> dict:
    """Collective instructions in optimized HLO text, by opcode.  A result
    of any shape counts (XLA combines many gradients' all-reduces into one
    instruction with a tuple result); an async `-start`/`-done` pair counts
    once."""
    from ..utils.hlostats import op_histogram
    hist = op_histogram(hlo_text)
    counts = {}
    for name in _COLLECTIVES:
        n = hist.get(name, 0) + hist.get(name + "-start", 0)
        if n:
            counts[name] = n
    return counts


def _devices(n: int):
    import jax

    devices = jax.devices()[:n]
    assert len(devices) == n, (
        f"need {n} devices, have {len(devices)} — launch with "
        f"JAX_PLATFORMS=cpu (fresh process) so the virtual-device config "
        f"can take effect")
    return devices


def _build(n_devices: int, batch_per_device: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ..models.lenet import LeNet5
    from ..nn import ClassNLLCriterion
    from ..optim import Optimizer, SGD, Trigger

    devices = _devices(n_devices)
    mesh = Mesh(np.asarray(devices).reshape(n_devices), ("data",))
    model = LeNet5(10).build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=ClassNLLCriterion(),
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
    step, param_sh, data_sh = opt._build_step(mesh)

    batch = batch_per_device * n_devices
    params = jax.device_put(model.params, param_sh)
    opt_state = opt.optim_method.init_state(params)
    inp = jax.device_put(jnp.zeros((batch, 28, 28, 1), jnp.float32), data_sh)
    tgt = jax.device_put(jnp.ones((batch,), jnp.int32), data_sh)
    lr, rng = jnp.float32(0.05), jax.random.key(1)

    lowered = step.lower(params, model.state, opt_state, inp, tgt, lr, rng)
    compiled = lowered.compile()

    box = {"p": params, "s": model.state, "o": opt_state}

    def run():
        box["p"], box["s"], box["o"], loss = compiled(
            box["p"], box["s"], box["o"], inp, tgt, lr, rng)
        return loss

    return run, compiled, batch


def measure(n_devices: int, batch_per_device: int = 64) -> dict:
    from ..utils.timing import measure_step_seconds

    run1, compiled1, batch1 = _build(1, batch_per_device)
    dt1, _ = measure_step_seconds(run1, n1=2, n2=8, reps=2)
    runn, compiledn, batchn = _build(n_devices, batch_per_device)
    dtn, _ = measure_step_seconds(runn, n1=2, n2=8, reps=2)

    thr1 = batch1 / dt1            # records/s on 1 device
    thrn = batchn / dtn            # records/s on n devices
    per_dev_eff = (thrn / n_devices) / thr1

    hlo = compiledn.as_text()
    colls = collective_counts(hlo)
    return {
        "n_devices": n_devices,
        "batch_per_device": batch_per_device,
        "throughput_1dev_records_s": round(thr1, 1),
        "throughput_ndev_records_s": round(thrn, 1),
        "per_device_efficiency": round(per_dev_eff, 3),
        "note": ("virtual CPU mesh: all devices share host cores, so "
                 "efficiency here is a contention-bound LOWER bound; "
                 "collectives confirm the compiled step is genuinely "
                 "distributed"),
        "collectives_ndev_step": colls,
    }


def strategy_signatures(n_devices: int) -> dict:
    """Collective signature of every parallelism strategy, compiled on the
    virtual mesh: evidence that each strategy lowers to the expected ICI
    collectives (not a Python-side simulation of them).

    Expected shapes — DP: gradient all-reduce; ZeRO/ShardedDP:
    reduce-scatter (or windowed all-reduce) + all-gather of sharded
    params/opt-state; DP x TP: all-reduces on both the gradient and the
    activation path; ring SP: collective-permute chain (the shard_map
    ppermute ring); Ulysses SP: all-to-alls re-sharding heads<->sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ..models.lenet import LeNet5
    from ..nn import ClassNLLCriterion
    from ..optim import Optimizer, SGD, Trigger
    from ..parallel.ring_attention import ring_attention, ulysses_attention
    from ..parallel.sharding import (DataParallel, ShardedDataParallel,
                                     TensorParallel)

    devices = _devices(n_devices)
    out = {}

    def train_step_hlo(mesh, strategy):
        model = LeNet5(10).build(jax.random.key(0))
        opt = Optimizer(model, dataset=None, criterion=ClassNLLCriterion(),
                        end_trigger=Trigger.max_iteration(1),
                        strategy=strategy)
        opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
        step, param_sh, data_sh = opt._build_step(mesh)
        batch = 8 * mesh.devices.size
        args = (jax.device_put(model.params, param_sh), model.state,
                opt.optim_method.init_state(model.params),
                jax.device_put(jnp.zeros((batch, 28, 28, 1), jnp.float32),
                               data_sh),
                jax.device_put(jnp.ones((batch,), jnp.int32), data_sh),
                jnp.float32(0.05), jax.random.key(1))
        return step.lower(*args).compile().as_text()

    mesh1d = Mesh(np.asarray(devices).reshape(n_devices), ("data",))
    out[f"dp{n_devices}"] = collective_counts(
        train_step_hlo(mesh1d, DataParallel()))
    out[f"zero{n_devices}"] = collective_counts(
        train_step_hlo(mesh1d, ShardedDataParallel(min_size=1)))
    if n_devices % 2 == 0:
        mesh2d = Mesh(np.asarray(devices).reshape(n_devices // 2, 2),
                      ("data", "model"))

        def tp_rule(path, leaf):
            # shard every even last axis: TensorParallel's default rule has
            # a 2^16-element floor that (correctly) leaves LeNet's small
            # weights replicated, which would make this signature a plain
            # DP one — the point here is the ENGAGED-TP collective shape
            from jax.sharding import PartitionSpec as P
            if leaf.ndim >= 2 and leaf.shape[-1] % 2 == 0:
                return P(*([None] * (leaf.ndim - 1) + ["model"]))
            return P()

        out[f"dp{n_devices // 2}xtp2"] = collective_counts(
            train_step_hlo(mesh2d, TensorParallel(rule=tp_rule)))

    seq_mesh = Mesh(np.asarray(devices).reshape(n_devices), ("seq",))
    B, H, T, D = 2, n_devices, 4 * n_devices, 8
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in jax.random.split(jax.random.key(2), 3))
    out[f"ring_sp{n_devices}"] = collective_counts(
        jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh=seq_mesh, causal=True, batch_axis=None)
        ).lower(q, k, v).compile().as_text())
    out[f"ulysses_sp{n_devices}"] = collective_counts(
        jax.jit(lambda a, b, c: ulysses_attention(
            a, b, c, mesh=seq_mesh, causal=True, batch_axis=None)
        ).lower(q, k, v).compile().as_text())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--batch-per-device", type=int, default=64)
    ap.add_argument("--no-strategies", action="store_true",
                    help="skip the per-strategy collective signatures")
    args = ap.parse_args(argv)

    from ..utils.platform import force_cpu
    force_cpu(args.devices)
    result = measure(args.devices, args.batch_per_device)
    if not args.no_strategies:
        result["strategy_collectives"] = strategy_signatures(args.devices)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
