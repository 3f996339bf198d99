#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which owns the TPU from start to end, drives the main path once
through the entry points a user would call, at the full width of two models
the repo supports, with data and weights made from ``--seed``:

  device  Engine.init(); the first device's platform must be ``tpu``
  sync    one large matmul timed under block_until_ready and under a host
          fetch (what utils/timing.py rests on)
  train   ResNet-50 (ImageNet graph, 1000 classes, 224x224x3, batch 256,
          bf16 compute): Optimizer(...).optimize() fed by an in-memory
          DataSet through SampleToMiniBatch
  serve   InferenceServer over that model: start(), warmup(), requests
          that land in more than one batch bucket, checked against a direct
          eval-mode forward of the same parameters
  lm      TransformerLM 8 x 512 (vocab 32000, 16 x 512 tokens, bf16):
          optimize(), proof that the Pallas flash kernel is in the compiled
          step (``tpu_custom_call`` in the program text), then DecodeEngine
          against the offline greedy oracle ``cached_generate``

``--chips 4`` runs instead the path across chips and what it is compared
with, and no other phase: the same ResNet-50 batch on the four-device
``data`` mesh and on one device, in this one process.

It sets no ``BIGDL_TPU_*`` path-selecting knob: it runs what a user gets by
default.  Earlier lines of the output are observations of this run (one JSON
object each), not metrics of a benchmark.  The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A phase that raises ends the run: exit code 1 and ``"ok": false``.  With no
TPU (``JAX_PLATFORMS=cpu``, or no accelerator) the device phase raises.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import threading
import time
import traceback
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase's cost depends on.  FULL is what the chip runs;
    tests/test_chip_smoke.py drives the same functions with a tiny one."""
    # train / serve / data-parallel: ResNet on the ImageNet graph
    resnet_depth: int = 50
    classes: int = 1000
    image: int = 224
    batch: int = 256
    train_iters: int = 8
    train_lr: float = 0.02
    serve_buckets: Tuple[int, ...] = (2, 8)
    serve_waves: Tuple[int, ...] = (1, 2, 7)   # concurrent requests per wave
    # lm: TransformerLM + DecodeEngine
    vocab: int = 32000
    max_len: int = 512
    d_model: int = 512
    heads: int = 8
    layers: int = 8
    lm_batch: int = 16
    lm_iters: int = 30
    lm_lr: float = 3e-3
    lm_alphabet: int = 64          # distinct tokens in the training data
    decode_slots: int = 4
    decode_page: int = 64
    prompt_lens: Tuple[int, ...] = (5, 7, 12)   # prefill buckets 8, 8, 16
    gen_tokens: int = 8
    # sync
    matmul_n: int = 8192


FULL = Sizes()

_T0 = time.perf_counter()


def say(obs: str, **fields) -> None:
    """One observation of this run, on a line of its own."""
    print(json.dumps({"obs": obs, "t": round(time.perf_counter() - _T0, 1),
                      **fields}), flush=True)


def _bf16_policy():
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))


class _LossLog:
    """The train-summary hook of Optimizer: keeps every logged loss."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append(float(value))
        return self

    def get_summary_trigger(self, name):
        return None


def _peak_bytes():
    """Peak bytes in use per device, where the backend reports it."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def _compile_seconds():
    """Seconds jax has spent in backend compiles so far in this process
    (jax.monitoring's `/jax/core/compile/backend_compile_duration`)."""
    return round(_COMPILE["s"], 2)


_COMPILE = {"s": 0.0, "armed": False}


def _arm_compile_clock():
    if _COMPILE["armed"]:
        return
    import jax.monitoring

    def on(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on)
    _COMPILE["armed"] = True


# ---------------------------------------------------------------- device


def device_phase(chips: int):
    """Engine.init() on what jax finds; anything but `chips` TPU devices is
    a failure, never a CPU run that prints ok."""
    import jax
    from bigdl_tpu import Engine
    from bigdl_tpu.utils import native

    _arm_compile_clock()
    mesh = Engine.init()
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    say("device", **info, mesh=dict(mesh.shape), jax=jax.__version__,
        native_library_loaded=native.is_native_loaded(),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    require_tpu(info, chips)
    return info


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU: jax found platform "
            f"{info['platform']!r} ({info['kind']})")
    if info["count"] != chips:
        raise RuntimeError(
            f"chip_smoke --chips {chips} needs exactly {chips} device(s), "
            f"jax found {info['count']}")


# ------------------------------------------------------------------ sync


def sync_phase(sz: Sizes):
    """Does block_until_ready wait for the device?  One n^3 bf16 matmul,
    timed to block_until_ready and timed to a host fetch of one element."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.utils.timing import fetch_scalar

    n = sz.matmul_n
    a = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    f = jax.jit(lambda x: (x @ x) * jnp.bfloat16(1.0 / n))
    fetch_scalar(f(a))  # compile + drain

    def timed(wait):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            wait(f(a))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    t_block = timed(jax.block_until_ready)
    t_fetch = timed(fetch_scalar)
    t_enqueue = timed(lambda y: None)
    jax.block_until_ready(f(a))
    flops = 2.0 * n ** 3
    say("sync", matmul_n=n, block_until_ready_s=t_block,
        host_fetch_s=t_fetch, enqueue_only_s=t_enqueue,
        tflops_block_until_ready=round(flops / t_block / 1e12, 1),
        tflops_host_fetch=round(flops / t_fetch / 1e12, 1))
    return {"block": t_block, "fetch": t_fetch, "enqueue": t_enqueue}


# ----------------------------------------------------------------- train


def _image_dataset(sz: Sizes, seed: int):
    import numpy as np
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    r = np.random.default_rng(seed)
    x = r.standard_normal(
        (sz.batch, sz.image, sz.image, 3)).astype(np.float32)
    y = r.integers(0, sz.classes, sz.batch).astype(np.int32)
    ds = DataSet.array([Sample(x[i], y[i]) for i in range(sz.batch)],
                       seed=seed)
    return ds.transform(SampleToMiniBatch(sz.batch, drop_last=True)), x, y


def _resnet(sz: Sizes, seed: int):
    import jax
    from bigdl_tpu.models.resnet import ResNet
    model = ResNet(sz.resnet_depth, class_num=sz.classes, dataset="imagenet")
    model.build(jax.random.key(seed))
    return model


def _run_optimizer(model, dataset, criterion, method, iters, label,
                   first_near):
    """Optimizer(...).optimize() — the real loop — with every iteration's
    loss kept and checked.  Returns (optimizer, losses, wall seconds,
    compile seconds)."""
    from bigdl_tpu.optim import Optimizer, Trigger
    log = _LossLog()
    opt = (Optimizer(model, dataset, criterion)
           .set_optim_method(method)
           .set_end_when(Trigger.max_iteration(iters)))
    opt.set_train_summary(log)
    c0, t0 = _compile_seconds(), time.perf_counter()
    opt.optimize()
    wall = time.perf_counter() - t0
    compile_s = round(_compile_seconds() - c0, 2)
    check_losses(log.losses, iters, label, first_near)
    return opt, log.losses, wall, compile_s


def check_losses(losses, iters, label, first_near):
    if len(losses) != iters:
        raise RuntimeError(f"{label}: {len(losses)} losses for {iters} "
                           "iterations")
    if not all(math.isfinite(v) for v in losses):
        raise RuntimeError(f"{label}: non-finite loss in {losses}")
    if abs(losses[0] - first_near) > 1.0:
        raise RuntimeError(f"{label}: first loss {losses[0]:.4f} is not "
                           f"near {first_near:.4f}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"{label}: last loss {losses[-1]:.4f} is not "
                           f"below the first {losses[0]:.4f} on a batch "
                           "that repeats")


def train_phase(sz: Sizes, seed: int):
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD

    _bf16_policy()
    ds, x, _y = _image_dataset(sz, seed)
    model = _resnet(sz, seed)
    opt, losses, wall, compile_s = _run_optimizer(
        model, ds, CrossEntropyCriterion(),
        SGD(learning_rate=sz.train_lr, momentum=0.9), sz.train_iters,
        "train", math.log(sz.classes))
    say("train", model=f"resnet{sz.resnet_depth}", batch=sz.batch,
        image=sz.image, classes=sz.classes, compute="bfloat16",
        iterations=sz.train_iters, wall_s=round(wall, 2),
        compile_s=compile_s, first_loss=losses[0], last_loss=losses[-1],
        losses=[round(v, 4) for v in losses],
        ln_classes=round(math.log(sz.classes), 4),
        peak_bytes_in_use=_peak_bytes())
    return model, x


# ----------------------------------------------------------------- serve


def serve_phase(sz: Sizes, model, x, rtol: float = 0.02):
    """InferenceServer over the trained model.  Each wave submits its
    requests at once, so the batcher coalesces them and the waves land in
    different buckets; every answer must agree with a direct eval-mode
    forward of the same parameters on the same device to `rtol` of the
    largest reference logit (bf16 compute: the two run at different batch
    shapes, so XLA may order their reductions differently)."""
    import jax
    import numpy as np
    from bigdl_tpu.serve import InferenceServer

    rows = sum(sz.serve_waves)
    direct = jax.jit(lambda p, s, inp: model.apply(
        p, s, inp, training=False, rng=None)[0])
    ref = np.asarray(direct(model.params, model.state, x[:rows]),
                     np.float32)
    if not np.isfinite(ref).all():
        raise RuntimeError("serve: the reference forward is not finite")

    c0, t0 = _compile_seconds(), time.perf_counter()
    server = InferenceServer(model, max_batch=max(sz.serve_buckets),
                             buckets=sz.serve_buckets, max_wait_ms=200.0)
    server.start()
    try:
        server.warmup(x[0])
        warm_s = time.perf_counter() - t0
        warm_compile_s = round(_compile_seconds() - c0, 2)
        got, buckets_hit, row = [], [], 0
        for n in sz.serve_waves:
            before = server.stats()
            outs = [None] * n

            def ask(i, r):
                outs[i] = server.predict(x[r], timeout=300)

            threads = [threading.Thread(target=ask, args=(i, row + i))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            after = server.stats()
            buckets_hit.append(
                {"requests": n,
                 "batches": after["batches"] - before["batches"],
                 "bucket_rows": after["bucket_rows"] - before["bucket_rows"]})
            got.extend(outs)
            row += n
        serve_compile_s = round(_compile_seconds() - c0, 2) - warm_compile_s
    finally:
        server.stop()
    got = np.asarray(np.stack(got), np.float32)
    if got.shape != ref.shape:
        raise RuntimeError(f"serve: answers {got.shape} vs reference "
                           f"{ref.shape}")
    if not np.isfinite(got).all():
        raise RuntimeError("serve: non-finite answer")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    if err > rtol * scale:
        raise RuntimeError(f"serve: answers differ from the direct forward "
                           f"by {err:.4g} > {rtol} x {scale:.4g}")
    # a wave flushes as one or more padded buckets; waves whose padded
    # rows differ cannot all have used the same bucket
    if len({w["bucket_rows"] for w in buckets_hit}) < 2:
        raise RuntimeError(f"serve: waves {buckets_hit} did not reach more "
                           f"than one bucket of {sz.serve_buckets}")
    say("serve", buckets=list(sz.serve_buckets), waves=buckets_hit,
        answers=int(got.shape[0]),
        warmup_wall_s=round(warm_s, 2), warmup_compile_s=warm_compile_s,
        compile_s_after_warmup=round(serve_compile_s, 2),
        wall_s=round(time.perf_counter() - t0, 2),
        max_abs_err=err, ref_max_abs=scale, rtol=rtol,
        peak_bytes_in_use=_peak_bytes())


# -------------------------------------------------------------------- lm


def _lm_dataset(sz: Sizes, seed: int):
    """Sequences that walk a cycle over `lm_alphabet` tokens spread through
    the vocabulary (next = current + stride, wrapping): learnable in a few
    steps, so the trained model's greedy choice is far from a tie."""
    import numpy as np
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    r = np.random.default_rng(seed)
    stride = sz.vocab // sz.lm_alphabet
    starts = r.integers(0, sz.lm_alphabet, sz.lm_batch)
    idx = (starts[:, None] + np.arange(sz.max_len + 1)[None, :]) \
        % sz.lm_alphabet
    toks = (idx * stride).astype(np.int32)
    samples = [Sample(t[:-1], t[1:]) for t in toks]
    ds = DataSet.array(samples, seed=seed).transform(
        SampleToMiniBatch(sz.lm_batch, drop_last=True))
    return ds, toks


def step_program_text(opt, inp, tgt) -> str:
    """The compiled text of the step `opt.optimize()` just ran: lowered
    again from the same jitted function at the same avals (JAX's compile
    cache returns the executable it already built)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.common import next_rng_key
    from bigdl_tpu.optim.optimizer import _put_batch
    step_fn, _param_sh, data_sh = opt._compiled
    inp, tgt = _put_batch((inp, tgt), data_sh)
    model = opt.model
    lowered = step_fn.lower(model.params, model.state, opt._final_opt_state,
                            inp, tgt, jnp.float32(0.0), next_rng_key())
    return lowered.compile().as_text()


def require_flash_kernel(program_text: str, layers: int) -> int:
    n = program_text.count("tpu_custom_call")
    if n < layers:
        raise RuntimeError(
            f"lm: {n} tpu_custom_call(s) in the compiled step, expected one "
            f"per layer ({layers}): the Pallas flash kernel is not what ran")
    return n


def lm_phase(sz: Sizes, seed: int, expect_kernel: bool = True):
    import jax
    import numpy as np
    from bigdl_tpu.models.decode import cached_generate
    from bigdl_tpu.models.transformer_lm import TransformerLM
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.serve import DecodeEngine

    _bf16_policy()
    ds, toks = _lm_dataset(sz, seed)
    model = TransformerLM(vocab_size=sz.vocab, max_len=sz.max_len,
                          d_model=sz.d_model, num_heads=sz.heads,
                          num_layers=sz.layers)
    model.build(jax.random.key(seed))
    opt, losses, wall, compile_s = _run_optimizer(
        model, ds,
        TimeDistributedCriterion(ClassNLLCriterion(), size_average=True),
        Adam(sz.lm_lr), sz.lm_iters, "lm", math.log(sz.vocab))
    c0 = _compile_seconds()
    text = step_program_text(opt, toks[:, :-1], toks[:, 1:])
    n_kernel = require_flash_kernel(text, sz.layers) if expect_kernel \
        else text.count("tpu_custom_call")
    say("lm.train", vocab=sz.vocab, max_len=sz.max_len, d_model=sz.d_model,
        heads=sz.heads, layers=sz.layers, batch=sz.lm_batch,
        compute="bfloat16", iterations=sz.lm_iters, wall_s=round(wall, 2),
        compile_s=compile_s, first_loss=losses[0], last_loss=losses[-1],
        ln_vocab=round(math.log(sz.vocab), 4),
        tpu_custom_calls_in_step=n_kernel,
        recompile_for_text_s=round(_compile_seconds() - c0, 2),
        peak_bytes_in_use=_peak_bytes())

    # decode: prompts of different lengths cut from the training walk,
    # continuous batching against the offline oracle at the engine's page
    prompts = [toks[i % len(toks), :n].copy()
               for i, n in enumerate(sz.prompt_lens)]
    c0, t0 = _compile_seconds(), time.perf_counter()
    eng = DecodeEngine(model, slots=sz.decode_slots, page=sz.decode_page)
    with eng:
        handles = [eng.submit(p, sz.gen_tokens) for p in prompts]
        got = [np.asarray(h.result(600)) for h in handles]
        stats = eng.stats()
    engine_wall = time.perf_counter() - t0
    engine_compile_s = round(_compile_seconds() - c0, 2)
    c0, t0 = _compile_seconds(), time.perf_counter()
    want = [np.asarray(cached_generate(model, p, sz.gen_tokens,
                                       max_len=sz.decode_page))
            for p in prompts]
    oracle_wall = time.perf_counter() - t0
    for p, g, w in zip(prompts, got, want):
        if g.shape != (len(p) + sz.gen_tokens,) or not np.array_equal(g, w):
            raise RuntimeError(
                f"lm: DecodeEngine output for a {len(p)}-token prompt "
                f"differs from cached_generate: {g.tolist()} vs "
                f"{w.tolist()}")
    stride = sz.vocab // sz.lm_alphabet
    follows_walk = all(
        np.array_equal(np.diff(g[len(p) - 1:].astype(np.int64)) %
                       (stride * sz.lm_alphabet),
                       np.full(sz.gen_tokens, stride))
        for p, g in zip(prompts, got))
    say("lm.decode", slots=sz.decode_slots, page=sz.decode_page,
        prompt_lens=list(sz.prompt_lens), gen_tokens=sz.gen_tokens,
        matches_oracle=True, continues_trained_walk=follows_walk,
        prefill_steps=stats["prefill_steps"],
        decode_steps=stats["decode_steps"], tokens_out=stats["tokens_out"],
        engine_wall_s=round(engine_wall, 2),
        engine_compile_s=engine_compile_s,
        oracle_wall_s=round(oracle_wall, 2),
        oracle_compile_s=round(_compile_seconds() - c0, 2),
        peak_bytes_in_use=_peak_bytes())


# --------------------------------------------------------- data parallel


def _placement(tree):
    """Distinct devices holding addressable shards of the leaves."""
    import jax
    devs = set()
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            devs.add(s.device.id)
    return sorted(devs)


def data_parallel_phase(sz: Sizes, seed: int, first_tol: float = 0.1,
                        traj_tol: float = 0.5):
    """The path across chips and what it is compared with: the same seeded
    ResNet batch through optimize() on the `data` mesh over every device
    (bf16 gradient wire, the default policy's) and on one device, in one
    process.  First-step losses must agree to `first_tol` (three bf16 steps
    at a loss near 7: the loss is a bf16 value) and every later pair to
    `traj_tol`; the batch must live on every device, the step must
    all-reduce, and no device may hold much more than the others."""
    import jax
    import numpy as np
    from bigdl_tpu import Engine
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import _put_batch
    from bigdl_tpu.tools.scaling import collective_counts

    _bf16_policy()
    n_dev = len(jax.devices())
    runs = {}
    for name, devices in (("mesh", None), ("one", [jax.devices()[0]])):
        Engine.reset()
        mesh = Engine.init(devices=devices)
        ds, x, y = _image_dataset(sz, seed)
        model = _resnet(sz, seed)
        opt, losses, wall, compile_s = _run_optimizer(
            model, ds, CrossEntropyCriterion(),
            SGD(learning_rate=sz.train_lr, momentum=0.9), sz.train_iters,
            f"data_parallel[{name}]", math.log(sz.classes))
        obs = {"devices": int(mesh.size), "wall_s": round(wall, 2),
               "compile_s": compile_s,
               "losses": [round(v, 4) for v in losses]}
        if name == "mesh":
            _step, _psh, data_sh = opt._compiled
            inp, _tgt = _put_batch((x, y), data_sh)
            obs["batch_on_devices"] = _placement(inp)
            obs["params_on_devices"] = _placement(model.params)
            obs["opt_state_on_devices"] = _placement(opt._final_opt_state)
            obs["batch_shard_rows"] = sorted(
                {s.data.shape[0] for s in inp.addressable_shards})
            del inp
            text = step_program_text(opt, x, y)
            obs["collectives"] = collective_counts(text)
            obs["peak_bytes_in_use"] = _peak_bytes()
        runs[name] = (obs, losses)
        say(f"data_parallel.{name}", **obs)
        del opt, model, ds

    mesh_obs, mesh_losses = runs["mesh"]
    _one_obs, one_losses = runs["one"]
    if mesh_obs["devices"] != n_dev:
        raise RuntimeError(f"data_parallel: mesh has {mesh_obs['devices']} "
                           f"devices, jax has {n_dev}")
    for what in ("batch_on_devices", "params_on_devices",
                 "opt_state_on_devices"):
        if len(mesh_obs[what]) != n_dev:
            raise RuntimeError(f"data_parallel: {what} = {mesh_obs[what]}, "
                               f"expected {n_dev} distinct devices")
    if mesh_obs["batch_shard_rows"] != [sz.batch // n_dev]:
        raise RuntimeError("data_parallel: batch shards of "
                           f"{mesh_obs['batch_shard_rows']} rows, expected "
                           f"{sz.batch // n_dev}")
    if n_dev > 1 and mesh_obs["collectives"].get("all-reduce", 0) < 1:
        raise RuntimeError("data_parallel: no all-reduce in the compiled "
                           f"step: {mesh_obs['collectives']}")
    peaks = mesh_obs["peak_bytes_in_use"]
    if n_dev > 1 and all(peaks) and \
            peaks[0] > 2.0 * sum(peaks[1:]) / (n_dev - 1):
        # read before the one-device comparison ran on device 0, so a
        # lopsided peak here is the mesh run's own
        raise RuntimeError("data_parallel: device 0 peaked at "
                           f"{peaks[0]} bytes against {peaks[1:]}")
    first_gap = abs(mesh_losses[0] - one_losses[0])
    gaps = [abs(a - b) for a, b in zip(mesh_losses, one_losses)]
    if first_gap > first_tol:
        raise RuntimeError(f"data_parallel: first-step losses differ by "
                           f"{first_gap:.4g} > {first_tol}")
    if max(gaps) > traj_tol:
        raise RuntimeError(f"data_parallel: trajectories drift apart by "
                           f"{max(gaps):.4g} > {traj_tol}: "
                           f"{mesh_losses} vs {one_losses}")
    say("data_parallel", devices=n_dev, first_loss_gap=first_gap,
        max_loss_gap=max(gaps), first_tol=first_tol, traj_tol=traj_tol,
        all_reduces=mesh_obs["collectives"].get("all-reduce", 0))


# ------------------------------------------------------------------ main


def run(args, sz: Sizes = FULL) -> dict:
    info = device_phase(args.chips)
    if args.chips == 1:
        sync_phase(sz)
        model, x = train_phase(sz, args.seed)
        serve_phase(sz, model, x)
        del model, x
        lm_phase(sz, args.seed)
    else:
        data_parallel_phase(sz, args.seed)
    say("done", total_wall_s=round(time.perf_counter() - _T0, 1),
        total_compile_s=_compile_seconds())
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight and every datum")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: device, sync, train, serve, lm on one chip "
                         "(what the driver runs).  4: only the data-"
                         "parallel path across four chips and its one-"
                         "device comparison")
    args = ap.parse_args(argv)
    try:
        info = run(args)
    except BaseException:  # noqa: BLE001 — the ONE handler: report, fail
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
