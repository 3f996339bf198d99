#!/usr/bin/env python
"""Perf-regression gate: CPU-measurable proxies diffed against a committed
baseline (ROADMAP open item 1a).

Every perf claim since PR 6 is a *structural property of the compiled
program* — the matmul conv route deletes every ``convolution`` from the
train step, the bucketed wire's up-cast count equals the bucket count
(not the leaf count), the fused update runs over N dtype-homogeneous
buffers, donation compiles into input/output aliases, and a warm AOT
cache makes the second compile nearly free.  Bench rounds 3-5 all died at
backend init with zero artifacts, so none of this is hardware-verified;
this gate makes each claim a *tested invariant* on CPU, every PR, so the
next real-TPU round measures exactly what we think it does.

Proxies (all on the LeNet train step, compile cards armed —
utils/hlostats.py):

1. **conv route**: the compiled step under ``BIGDL_TPU_CONV_ROUTE``
   (defaulted to ``matmul`` — exporting ``=pad`` is the regression demo)
   must contain 0 convolutions (``lenet_matmul.conv_ops``), and its
   steady-state step time must stay within the baseline ratio of the pad
   route's (``conv_route.step_ratio``, à la ``tools/lenet_cold.py``).
2. **wire + fused card**: with ``BIGDL_TPU_WIRE_BUCKET_MB=4`` and
   ``BIGDL_TPU_FUSED_UPDATE=1``, the card must report the expected
   wire-leaf / wire-bucket counts, a StableHLO up-cast (``f32<-bf16``)
   count bounded by the BUCKET count, the expected fused-buffer count,
   and donation aliases present.
3. **AOT cold/warm**: the same step compiled cold (compile+store) then
   warm (executable deserialized from a fresh cache dir, jit caches
   cleared) — warm-over-cold compile-cost ratio under the baseline bound.
4. **pipeline step card** (needs >= 2 devices — the cpu platform runs on
   a forced 4-virtual-device host): a ``partition_pipeline``'d MLP train
   step on a ``(1,1,1,2,1)`` MeshLayout — the card's ``pipe_microbatches``
   count, the GPipe ``pipe_bubble_fraction`` bound, and the schedule's
   ``collective-permute`` ops in the compiled program.
5. **expert step card**: a ``MoEFFN`` train step on ``(1,1,1,1,2)`` — the
   GSPMD expert-sharded step's collective count — plus the explicit
   ``expert_parallel_ffn`` program's ``all-to-all`` op count, so the next
   TPU round measures the dispatch/combine schedule we think it does.
6. **1F1B schedule card** (ISSUE 13): the same pipe=2 mesh running the
   interleaved 1F1B schedule (``BIGDL_TPU_PIPE_SCHEDULE=1f1b``, v=2,
   m=8) — the card's bubble fraction must stay under the interleaved
   bound, the compiled program's ``collective-permute`` count is pinned
   (fwd ring + the two bwd-table rings), the schedule table's analytic
   peak in-flight microbatches and their ratio to GPipe's keep-all
   ``m*v`` are pinned, and the XLA temp budget of the 1F1B step over the
   GPipe step (batch 256, activations dominating) must stay <= 1 — a
   schedule memory regression fails the gate.
7. **generative decode** (ISSUE 18): (a) the KV-cache O(L) claim as
   the ``kv_cache``/``full_fwd`` seconds ratio from
   ``bigdl_tpu/tools/serving_bench.py``, pinned on a CPU-sized LM so
   every PR gates the decode fast path against the full re-forward;
   (b) the continuous-batching ``DecodeEngine`` end-to-end tokens/s
   floor and its per-slot KV-cache footprint
   (``decode.cache_bytes_per_slot``, exact — a cache-layout or
   page-ladder regression changes the byte count before it changes a
   benchmark).
8. **router dispatch overhead** (ISSUE 14): the serving topology
   router's per-request (bucket, queue-depth) routing decision
   (``TopologyRouter._pick``) over a 4-member pool, bounded in host
   microseconds — the tax scale-out routing adds in front of every
   request must stay negligible.  The cross-process fleet front
   (ISSUE 17) pins the same decision computed off the cached member
   registry (``FleetFront._pick``) — a cache-bypass regression that
   re-lists the registry per request fails the gate.
9. **observability tax** (ISSUE 19): (a) the fleet dispatch decision
   re-run with request tracing ARMED — a request id minted plus the
   admit/send/done flow events every pick — bounded as a ratio over the
   untraced decision, so the per-request cost of end-to-end flow
   tracing stays a small multiple of the routing tax it annotates;
   (b) ``MetricsRegistry.render()`` host microseconds over a
   representative registry, so a ``GET /metrics`` scrape can never
   perturb serving.

``PERF_BASELINE.json`` match kinds: ``exact`` (structural counts — any
drift fails), ``max`` (time/ratio metrics — measured must stay <=
``value * slack * BIGDL_TPU_GATE_TIME_SLACK``), ``min`` (measured >=
value).  Intentional perf changes are a *reviewed diff* to the baseline:
run ``--update-baseline`` and commit the result (structural values are
overwritten with the measured program; ratio bounds are preserved).

Prints a readable per-metric diff, then ONE JSON line
(``metric=perf_gate``), and exits non-zero on any regression.
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

DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "PERF_BASELINE.json")
BASELINE_FORMAT = "bigdl_tpu-perf-baseline-v1"

#: bounds written by --update-baseline for the time-ratio metrics (never
#: overwritten with a measured value: a lucky fast run must not ratchet
#: the bound down for every later CI machine)
DEFAULT_RATIO_BOUNDS = {
    "conv_route.step_ratio": {"value": 1.25, "match": "max",
                              "note": "matmul-route steady step time / "
                                      "pad-route (lenet_cold bound)"},
    "aot.warm_over_cold": {"value": 0.5, "match": "max",
                           "note": "warm AOT compile cost / cold "
                                   "(measured ~0.035 on CPU; CI slack)"},
    "pipe.bubble_fraction": {"value": 0.25, "match": "max",
                             "note": "GPipe idle bound (n-1)/(m+n-1) for "
                                     "the pipe=2 proxy step (0.2 at the "
                                     "default 4 microbatches)"},
    "pipe_1f1b.bubble_fraction": {
        "value": 0.1, "match": "max",
        "note": "interleaved 1F1B idle bound for the pipe=2, v=2, m=8 "
                "proxy (schedule table gives 1/17 ~= 0.0588)"},
    "pipe.inflight_bytes_ratio": {
        "value": 0.5, "match": "max",
        "note": "1F1B peak in-flight stage-input activations / GPipe's "
                "keep-all m*v at equal stage granularity (table gives "
                "5/16 = 0.3125 for the proxy)"},
    "pipe_1f1b.temp_bytes_ratio": {
        "value": 1.0, "match": "max",
        "note": "XLA temp budget of the compiled 1F1B step / GPipe step "
                "at batch 256 (activations dominate) — the schedule "
                "memory claim as a compiled-program invariant"},
    "serving.kv_over_full": {
        "value": 0.5, "match": "max",
        "note": "cached_generate (KV decode) seconds / greedy_generate "
                "(full re-forward) seconds at equal generated tokens — "
                "serving_bench's kv_cache/full_fwd row as a gate "
                "(measured ~0.06 on CPU; the bound just has to catch "
                "the fast path degenerating to the O(L^2) one)"},
    "decode.tokens_per_s": {
        "value": 50.0, "match": "min",
        "note": "continuous-batching DecodeEngine end-to-end tokens/s "
                "on the CPU proxy LM (measured ~1000+; conservative "
                "floor, catches a pathological per-step stall)"},
    "router.dispatch_us": {
        "value": 100.0, "match": "max",
        "note": "TopologyRouter._pick host microseconds per routing "
                "decision over a 4-member pool (measured ~2-5us; the "
                "bound caps the per-request tax topology routing adds "
                "over the shared queue)"},
    "fleet.dispatch_us": {
        "value": 150.0, "match": "max",
        "note": "FleetFront._pick host microseconds per routing decision "
                "over a 4-member registry with a warm cache (measured "
                "~3-10us; catches a cache-bypass regression that would "
                "re-list the registry per request)"},
    "fleet.dispatch_traced_ratio": {
        "value": 10.0, "match": "max", "slack": 3.0,
        "note": "the same _pick loop with request tracing ARMED (id "
                "minted + admit/send/done flow events per pick) over the "
                "untraced fleet.dispatch_us; catches a flow path that "
                "flushes or allocates per event (100x and more).  A ratio "
                "of two host-clock loops of milliseconds: 5.0-9.9 over 8 "
                "readings on an idle sandbox CPU, up to 21.4 beside 12 "
                "busy processes on its 8 cores (PR 22), hence this row's "
                "own slack; no other row has one"},
    "metrics.render_us": {
        "value": 5000.0, "match": "max",
        "note": "MetricsRegistry.render() host microseconds over a "
                "representative registry (request histograms + sheds + "
                "fed counter tracks) — one GET /metrics scrape must "
                "stay far too cheap to perturb serving"},
}


def _build_step(batch_size):
    """The real compiled train step (Optimizer._build_step) on device 0;
    fresh Optimizer per call so env knobs re-bake."""
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
    inp = jnp.asarray(rng.normal(size=(batch_size, 28, 28, 1)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 10, size=batch_size), jnp.int32)
    params = jax.device_put(model.params, param_sh)
    args = (params, model.state, opt.optim_method.init_state(params),
            inp, tgt, jnp.float32(0.01), jax.random.key(1))
    return step, args


def _build_layout_step(layout_sizes, model_fn, batch_size=32, in_dim=64,
                       classes=8):
    """A real compiled train step (Optimizer._build_step) on a MeshLayout
    mesh — the pipe/expert proxies' harness."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.parallel import LayoutSharding, MeshLayout
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    layout = MeshLayout(*layout_sizes)
    mesh = layout.install(jax.devices()[: layout.size])
    model = model_fn()
    model.build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=nn.CrossEntropyCriterion(),
                    end_trigger=Trigger.max_iteration(1),
                    strategy=LayoutSharding(model, min_size=0))
    opt.set_optim_method(SGD(learning_rate=0.05))
    step, param_sh, data_sh = opt._build_step(mesh)
    rng = np.random.default_rng(0)
    inp = jax.device_put(
        jnp.asarray(rng.normal(size=(batch_size, in_dim)), jnp.float32),
        data_sh)
    tgt = jax.device_put(
        jnp.asarray(rng.integers(0, classes, size=batch_size), jnp.int32),
        data_sh)
    params = jax.device_put(model.params, param_sh)
    opt_state = jax.device_put(opt.optim_method.init_state(model.params),
                               opt._opt_sh)
    args = (params, model.state, opt_state, inp, tgt, jnp.float32(0.05),
            jax.random.key(1))
    return step, args


def _pipe_model():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.parallel import partition_pipeline
    model = nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))
    return partition_pipeline(model, 2)


def _moe_model():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.parallel import MoEFFN
    return nn.Sequential(
        nn.Linear(64, 32, with_bias=False), nn.ReLU(),
        MoEFFN(32, 64, num_experts=4, capacity_factor=4.0),
        nn.Linear(32, 8, with_bias=False))


def _mlp4():
    import bigdl_tpu.nn as nn
    return nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))


def _pipe4_gpipe_model():
    """4 identical blocks as 2 GPipe stages of 2 (the v=1 comparator)."""
    from bigdl_tpu.parallel import partition_pipeline
    return partition_pipeline(_mlp4(), 2)


def _pipe4_1f1b_model():
    """4 identical blocks as 4 interleaved slices, 2 per device (reads
    the 1f1b/v=2 env knobs set around the proxy)."""
    from bigdl_tpu.parallel import partition_pipeline
    return partition_pipeline(_mlp4(), 4)


def _step_temp_bytes(layout_sizes, model_fn, batch_size):
    """XLA temp (peak scratch) bytes of the compiled step under the
    CURRENT env knobs — lower+compile only, never executed."""
    from bigdl_tpu.utils import memstats
    step, args = _build_layout_step(layout_sizes, model_fn,
                                    batch_size=batch_size)
    ma = memstats.compiled_memory_analysis(step.lower(*args).compile())
    return (ma or {}).get("temp_bytes")


def _run_steps(step, args, iters=10):
    """First call (compile + card) then steady-state seconds/step with
    the threaded-state pattern from tools/lenet_cold.py (donation-safe:
    outputs replace the donated inputs every iteration)."""
    import jax
    out = step(*args)
    jax.block_until_ready(out[3])
    params, net_state, opt_state = out[0], out[1], out[2]
    t0 = time.perf_counter()
    for _ in range(iters):
        params, net_state, opt_state, loss = step(
            params, net_state, opt_state, *args[3:])
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / iters


def _fresh(env_updates):
    """Apply env updates (None = delete) and clear jax caches so the next
    build re-lowers and re-compiles under the new knobs."""
    import jax
    for k, v in env_updates.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    jax.clear_caches()


def measure(batch_size=64):
    """Run every proxy; returns (measured metrics dict, context dict)."""
    from bigdl_tpu.common import DTypePolicy, set_policy
    from bigdl_tpu.utils import aot, hlostats

    measured, context = {}, {}
    set_policy(DTypePolicy())  # default policy: bf16 wire

    # ---- proxy 1: conv route (pad baseline, then the env's route) ----
    route = os.environ["BIGDL_TPU_CONV_ROUTE"]  # defaulted in main()
    _fresh({"BIGDL_TPU_CONV_ROUTE": "pad",
            "BIGDL_TPU_FUSED_UPDATE": None,
            "BIGDL_TPU_WIRE_BUCKET_MB": None})
    hlostats.reset()
    step, args = _build_step(batch_size)
    pad_step_s = _run_steps(step, args)
    pad_card = hlostats.last_card("optim.step")
    context["pad"] = {"conv_ops": pad_card["convolutions"],
                      "step_s": round(pad_step_s, 6)}

    _fresh({"BIGDL_TPU_CONV_ROUTE": route})
    hlostats.reset()
    step, args = _build_step(batch_size)
    route_step_s = _run_steps(step, args)
    card = hlostats.last_card("optim.step")
    measured["lenet_matmul.conv_ops"] = card["convolutions"]
    measured["conv_route.step_ratio"] = round(
        route_step_s / max(pad_step_s, 1e-9), 4)
    context["route"] = {"route": route, "conv_ops": card["convolutions"],
                        "step_s": round(route_step_s, 6),
                        "total_ops": card["total_ops"]}

    # ---- proxy 2: wire + fused card ----------------------------------
    _fresh({"BIGDL_TPU_WIRE_BUCKET_MB": "4",
            "BIGDL_TPU_FUSED_UPDATE": "1"})
    hlostats.reset()
    step, args = _build_step(batch_size)
    _run_steps(step, args, iters=1)
    card = hlostats.last_card("optim.step")
    extra = card.get("extra", {})
    measured["wire.leaves"] = extra.get("wire_leaves", 0)
    measured["wire.buckets"] = extra.get("wire_buckets", 0)
    measured["wire.upcasts"] = card.get(
        "stablehlo_convert_pairs", {}).get("f32<-bf16", 0)
    measured["fused.buffers"] = extra.get("fused_buffers", 0)
    measured["fused.donation_aliases"] = card.get("input_output_aliases", 0)
    context["wire_fused"] = {"convert_pairs": card.get("convert_pairs"),
                             "stablehlo_convert_pairs":
                                 card.get("stablehlo_convert_pairs"),
                             "step_knobs": {k: extra.get(k) for k in
                                            ("fused_update",
                                             "wire_bucket_mb", "donate")}}
    _fresh({"BIGDL_TPU_WIRE_BUCKET_MB": None,
            "BIGDL_TPU_FUSED_UPDATE": None})

    # ---- proxy 3: AOT cold vs warm -----------------------------------
    cache_dir = tempfile.mkdtemp(prefix="perf_gate_aot_")
    _fresh({"BIGDL_TPU_AOT_CACHE": cache_dir})
    aot.reset()

    def compile_cost(before, after):
        return (after["compile_s"] - before["compile_s"] +
                after["load_s"] - before["load_s"])

    s0 = aot.stats()
    step, args = _build_step(batch_size)
    _run_steps(step, args, iters=1)
    s1 = aot.stats()
    _fresh({})  # clear jit caches: the warm build must go through disk
    step, args = _build_step(batch_size)
    _run_steps(step, args, iters=1)
    s2 = aot.stats()
    cold = compile_cost(s0, s1)
    warm = compile_cost(s1, s2)
    measured["aot.warm_over_cold"] = round(warm / max(cold, 1e-9), 4)
    context["aot"] = {"compile_s_cold": round(cold, 3),
                      "compile_s_warm": round(warm, 3),
                      "hits": int(s2["hits"]), "misses": int(s2["misses"]),
                      "stores": int(s2["stores"]),
                      "cache_dir": cache_dir}
    _fresh({"BIGDL_TPU_AOT_CACHE": None})

    # ---- proxy 7: generative decode (serve/decode.py, ISSUE 18) ------
    # (a) the KV-cache fast-path claim as serving_bench's
    #     kv_cache/full_fwd seconds ratio on a CPU-sized LM: equal
    #     generated tokens, 1-token prompt so no prefill skews it
    import jax
    import numpy as np

    from bigdl_tpu.models import TransformerLM, cached_generate
    from bigdl_tpu.models.transformer_lm import greedy_generate
    lm = TransformerLM(vocab_size=256, max_len=128, d_model=64,
                       num_heads=4, num_layers=2).build(jax.random.key(0))
    prompt1 = np.ones((4, 1), np.int32)

    def _best(fn, n=3):
        fn()  # compile + warm
        times = []
        for _ in range(n):
            t1 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t1)
        return min(times)  # serving_bench convention: best of N

    full_s = _best(lambda: greedy_generate(lm, prompt1, 32, 128))
    kv_s = _best(lambda: cached_generate(lm, prompt1, 32, max_len=128))
    measured["serving.kv_over_full"] = round(kv_s / max(full_s, 1e-9), 4)

    # (b) the continuous-batching engine end to end: tokens/s floor +
    #     the per-slot KV footprint as an exact structural row (slots=4,
    #     page=16 ladder on the same LM — deterministic byte count)
    from bigdl_tpu.serve import DecodeEngine
    drng = np.random.default_rng(3)
    with DecodeEngine(lm, slots=4, page=16) as eng:
        # warm-up request pays the prefill+decode compiles; the timed
        # batch then measures the steady step loop, not the lowering
        eng.generate(drng.integers(1, 256, size=5).astype(np.int32), 8,
                     timeout=120)
        t_dec = time.perf_counter()
        handles = [eng.submit(drng.integers(1, 256, size=5).astype(np.int32),
                              8) for _ in range(8)]
        for h in handles:
            h.result(120)
        decode_wall = time.perf_counter() - t_dec
        dstats = eng.stats()
    measured["decode.tokens_per_s"] = round(8 * 8 / max(decode_wall, 1e-9),
                                            1)
    measured["decode.cache_bytes_per_slot"] = dstats["cache_bytes_per_slot"]
    context["decode"] = {"full_fwd_s": round(full_s, 4),
                         "kv_cache_s": round(kv_s, 4),
                         "tokens_out": dstats["tokens_out"],
                         "cache_len": dstats["cache_len"],
                         "decode_steps": dstats["decode_steps"]}

    # ---- proxies 4+5: pipeline + expert step shapes ------------------
    if jax.device_count() < 2:
        context["pipe_expert"] = {
            "skipped": f"need >= 2 devices, have {jax.device_count()} "
                       "(run with --platform cpu for the forced "
                       "4-virtual-device host)"}
        return measured, context

    # pipe=2: the partitioned step's card carries the schedule's
    # self-description (Optimizer._build_step card_extra) and the
    # compiled program carries the GPipe ring's collective-permutes
    hlostats.reset()
    step, args = _build_layout_step((1, 1, 1, 2, 1), _pipe_model)
    _run_steps(step, args, iters=1)
    card = hlostats.last_card("optim.step")
    extra = card.get("extra", {})
    measured["pipe.microbatches"] = extra.get("pipe_microbatches", 0)
    measured["pipe.bubble_fraction"] = extra.get("pipe_bubble_fraction", 1.0)
    measured["pipe.collective_permutes"] = card.get("ops", {}).get(
        "collective-permute", 0)
    context["pipe"] = {"stages": extra.get("pipe_stages"),
                       "collectives": card.get("collectives"),
                       "total_ops": card.get("total_ops")}

    # expert=2: the GSPMD expert-sharded step's collective count, plus
    # the explicit shard_map dispatch/combine program's all-to-alls
    hlostats.reset()
    step, args = _build_layout_step((1, 1, 1, 1, 2), _moe_model)
    _run_steps(step, args, iters=1)
    card = hlostats.last_card("optim.step")
    measured["moe.step_collectives"] = card.get("collectives", 0)
    context["expert"] = {"ops_sample": {k: v for k, v in
                                        card.get("ops", {}).items()
                                        if "all-" in k or "collective" in k},
                         "total_ops": card.get("total_ops")}

    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.parallel import MoEFFN, expert_parallel_ffn
    from bigdl_tpu.utils.engine import Engine
    mesh = Engine.mesh()  # the (1,1,1,1,2) layout mesh from above
    m = MoEFFN(16, 32, num_experts=4, capacity_factor=4.0)
    m.build(jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(32, 16)),
                    jnp.float32)

    def ep(params, xs):
        return expert_parallel_ffn(mesh, params, xs, k=1,
                                   capacity_factor=4.0)

    lowered = jax.jit(ep).lower(m.params, x)
    compiled = lowered.compile()
    ep_card = hlostats.compile_card(compiled, lowered, label="moe.ep")
    measured["moe.all_to_all"] = ep_card.get("ops", {}).get("all-to-all", 0)
    context["expert"]["ep_collectives"] = ep_card.get("collectives")

    # ---- proxy 6: sharded embedding gather (nn/embedding.LookupTable)
    # the recommender memory story (ISSUE 20): an embedding_row table
    # under fsdp×tp must lower to GATHER ops with the table resident at
    # 1/N per device and ZERO full-table all-gathers on the forward — an
    # all-gather here would silently rebuild the whole table per device
    # and void the 1/N residency the workload shards for
    import bigdl_tpu.nn as nn_mod
    from bigdl_tpu.parallel import LayoutSharding, MeshLayout
    from bigdl_tpu.utils import memstats as _memstats
    Engine.reset()
    emb_layout = MeshLayout(1, 2, 2)
    emb_mesh = emb_layout.install(jax.devices()[:emb_layout.size])
    tbl = nn_mod.Sequential().add(
        nn_mod.LookupTable(4096, 64)).build(jax.random.key(3))
    emb_sh = LayoutSharding(tbl, min_size=0).param_sharding(emb_mesh,
                                                            tbl.params)
    emb_placed = jax.device_put(tbl.params, emb_sh)
    emb_ids = jnp.asarray(np.random.default_rng(2).integers(
        0, 4096, size=(32, 8)), jnp.float32)

    def _emb_fwd(params, xs):
        out, _ = tbl.apply(params, tbl.state, xs)
        return out

    lowered = jax.jit(_emb_fwd).lower(emb_placed, emb_ids)
    compiled = lowered.compile()
    emb_card = hlostats.compile_card(compiled, lowered, label="embed.fwd")
    emb_ops = emb_card.get("ops", {})
    measured["embed.gather_ops"] = sum(
        v for k, v in emb_ops.items()
        if "gather" in k and not k.startswith("all-"))
    measured["embed.table_allgather"] = emb_ops.get("all-gather", 0)
    measured["embed.table_fraction"] = _memstats.embedding_table_bytes(
        tbl, emb_placed)[0]["device_fraction"]
    context["embed"] = {"layout": "1,2,2",
                        "ops_sample": {k: v for k, v in emb_ops.items()
                                       if "gather" in k},
                        "collectives": emb_card.get("collectives"),
                        "total_ops": emb_card.get("total_ops")}

    # ---- proxy 8: router dispatch overhead (serve/router.py) ---------
    # the (bucket, depth) routing decision is pure host work in front of
    # EVERY request — bound its per-call cost over a 4-member pool so a
    # quadratic-scan or lock-contention regression fails the gate before
    # a real deployment measures it as tail latency
    import bigdl_tpu.nn as nn_mod
    from bigdl_tpu.serve import TopologyRouter
    rmodel = nn_mod.Sequential().add(
        nn_mod.Linear(8, 4)).build(jax.random.key(0))
    n_members = min(4, jax.device_count())
    router = TopologyRouter(rmodel, replicas=n_members,
                            example=np.zeros((8,), np.float32))
    # members constructed (queues + health live), never started: _pick
    # reads exactly the state it reads under traffic, with no worker
    # threads adding scheduler noise to the measurement
    for i in range(n_members):
        router._members[i] = router._build_member(i)
    for _ in range(200):
        router._pick()  # warm (allocator, attribute caches)
    n_picks = 5000
    t0_pick = time.perf_counter()
    for _ in range(n_picks):
        router._pick()
    measured["router.dispatch_us"] = round(
        (time.perf_counter() - t0_pick) / n_picks * 1e6, 3)
    context["router"] = {"members": n_members, "picks": n_picks}

    # ---- proxy 8b: fleet front dispatch overhead (serve/fleetfront.py)
    # the cross-process fleet keeps the router's (bucket, depth) decision
    # but computes it off the CACHED registry — bound the per-request
    # host cost so a registry-listing-per-pick regression (cache bypass)
    # or lock contention fails the gate as a number, not as fleet tail
    # latency in a real deployment
    from bigdl_tpu.serve import FleetFront
    from bigdl_tpu.serve import fleet as fleet_mod
    fleet_dir = tempfile.mkdtemp(prefix="perf_gate_fleet_")
    for i in range(4):
        fleet_mod.publish_member(fleet_dir, index=i, generation=1,
                                 pid=1000 + i, port=9000 + i, max_batch=8)
        fleet_mod.beat(fleet_dir, i, 1, 1)
    # refresh/lost thresholds pinned huge: the warm cache is the hot
    # path under traffic; the refresh itself is paid once per interval
    fleet_front = FleetFront(fleet_dir, refresh_s=3600.0,
                             lost_after_s=3600.0)
    for _ in range(200):
        fleet_front._pick()  # warm (registry cache + allocator)
    t0_pick = time.perf_counter()
    for _ in range(n_picks):
        fleet_front._pick()
    measured["fleet.dispatch_us"] = round(
        (time.perf_counter() - t0_pick) / n_picks * 1e6, 3)
    context["fleet"] = {"members": 4, "picks": n_picks}

    # ---- proxy 9: observability tax (ISSUE 19) -----------------------
    # (a) the SAME warm dispatch loop with request tracing armed: every
    # pick mints an id and emits the admit/send/done flow chain — the
    # whole per-request bookkeeping the serving tiers add when
    # BIGDL_TPU_TRACE is set.  Bounded as a ratio over the untraced
    # pick so it tracks machine speed, not absolute microseconds.
    from bigdl_tpu.utils import metrics_export, telemetry
    trace_tmp = tempfile.mkdtemp(prefix="perf_gate_trace_")
    tracer = telemetry.Tracer(trace_tmp, rank=0, flush_every=1 << 30)
    telemetry.set_active(tracer)
    try:
        for _ in range(200):
            fleet_front._pick()  # re-warm under the armed tracer
        t0_pick = time.perf_counter()
        for _ in range(n_picks):
            rid = telemetry.mint_request_id()
            telemetry.flow_start(rid, hop="front.admit")
            fleet_front._pick()
            telemetry.flow_step(rid, hop="front.send", member=0)
            telemetry.flow_finish(rid, hop="front.done", status="ok")
        traced_us = (time.perf_counter() - t0_pick) / n_picks * 1e6
    finally:
        telemetry.set_active(None)
    fleet_front.close()
    measured["fleet.dispatch_traced_ratio"] = round(
        traced_us / max(measured["fleet.dispatch_us"], 1e-9), 4)
    context["fleet"]["traced_us"] = round(traced_us, 3)

    # (b) one GET /metrics render over a representative registry:
    # request-latency histograms, shed causes, and fed counter tracks
    reg = metrics_export.MetricsRegistry()
    for i in range(64):
        reg.observe_request(0.003 + 0.001 * (i % 7),
                            "ok" if i % 9 else "RequestTimeout")
    for cause in ("timeout", "overloaded", "priority", "quota"):
        reg.shed(cause)
    reg.feed_counter("serve", {"depth": 3, "batch_fill": 0.8,
                               "inflight": 2})
    reg.feed_counter("fleet", {"live": 3, "retried": 1, "lost": 1})
    reg.feed_counter("serve.decode", {"slots_busy": 4, "tokens_out": 512})
    reg.render()  # warm
    n_render = 200
    t0_r = time.perf_counter()
    for _ in range(n_render):
        text = reg.render()
    measured["metrics.render_us"] = round(
        (time.perf_counter() - t0_r) / n_render * 1e6, 3)
    context["metrics"] = {"renders": n_render,
                          "exposition_lines": text.count("\n")}

    # ---- proxy 6: 1F1B schedule card + memory ratio (ISSUE 13) -------
    from bigdl_tpu.parallel import build_schedule
    _fresh({"BIGDL_TPU_PIPE_MICROBATCHES": "8",
            "BIGDL_TPU_PIPE_SCHEDULE": "1f1b",
            "BIGDL_TPU_PIPE_VIRTUAL_STAGES": "2"})
    hlostats.reset()
    step, args = _build_layout_step((1, 1, 1, 2, 1), _pipe4_1f1b_model)
    _run_steps(step, args, iters=1)
    card = hlostats.last_card("optim.step")
    extra = card.get("extra", {})
    measured["pipe_1f1b.bubble_fraction"] = extra.get(
        "pipe_bubble_fraction", 1.0)
    measured["pipe_1f1b.collective_permutes"] = card.get("ops", {}).get(
        "collective-permute", 0)
    tbl = build_schedule("1f1b", 2, 8, 2)
    measured["pipe_1f1b.peak_inflight_microbatches"] = tbl.peak_inflight
    measured["pipe.inflight_bytes_ratio"] = round(
        tbl.peak_inflight / (8 * 2), 4)
    # XLA's own memory budget: 1F1B's bounded stash vs GPipe's
    # keep-every-microbatch autodiff backward, batch large enough for
    # activations to dominate the fixed schedule buffers
    mem_batch = 256
    f_temp = _step_temp_bytes((1, 1, 1, 2, 1), _pipe4_1f1b_model, mem_batch)
    _fresh({"BIGDL_TPU_PIPE_SCHEDULE": None,
            "BIGDL_TPU_PIPE_VIRTUAL_STAGES": None})
    g_temp = _step_temp_bytes((1, 1, 1, 2, 1), _pipe4_gpipe_model, mem_batch)
    if f_temp and g_temp:
        measured["pipe_1f1b.temp_bytes_ratio"] = round(f_temp / g_temp, 4)
    context["pipe_1f1b"] = {
        "schedule": extra.get("pipe_schedule"),
        "virtual_stages": extra.get("pipe_virtual_stages"),
        "microbatches": extra.get("pipe_microbatches"),
        "collectives": card.get("collectives"),
        "schedule_ticks": tbl.ticks,
        "temp_bytes": {"1f1b": f_temp, "gpipe": g_temp,
                       "batch": mem_batch},
    }
    _fresh({"BIGDL_TPU_PIPE_MICROBATCHES": None})
    return measured, context


def check(measured, baseline, time_slack=1.0):
    """Diff measured against the baseline metrics.  Returns (rows,
    regressions): one row per metric with a status, regressions the
    subset that failed (baseline metrics with no measurement count)."""
    rows, regressions = [], []
    metrics = baseline.get("metrics", {})
    for name in sorted(set(metrics) | set(measured)):
        spec = metrics.get(name)
        got = measured.get(name)
        if spec is None:
            rows.append((name, None, got, "NEW (not in baseline)"))
            continue
        want, match = spec["value"], spec.get("match", "exact")
        if got is None:
            rows.append((name, want, None, "MISSING (not measured)"))
            regressions.append(name)
            continue
        if match == "exact":
            ok = got == want
            detail = f"exact {want}"
        elif match == "max":
            bound = want * float(spec.get("slack", 1.0)) * time_slack
            ok = got <= bound
            detail = f"<= {round(bound, 4)}"
        elif match == "min":
            ok = got >= want
            detail = f">= {want}"
        else:
            ok, detail = False, f"unknown match kind {match!r}"
        rows.append((name, want, got, "OK" if ok else f"REGRESSED ({detail})"))
        if not ok:
            regressions.append(name)
    return rows, regressions


def update_baseline(measured, path, existing):
    """Write the measured structural values as the new baseline; ratio
    bounds keep their existing (or default) values — an intentional perf
    change is the committed diff of this file."""
    old = existing.get("metrics", {}) if existing else {}
    metrics = {}
    for name in sorted(measured):
        if name in DEFAULT_RATIO_BOUNDS:
            metrics[name] = dict(old.get(name, DEFAULT_RATIO_BOUNDS[name]))
        else:
            entry = dict(old.get(name, {"match": "exact"}))
            entry["value"] = measured[name]
            metrics[name] = entry
    blob = {"format": BASELINE_FORMAT,
            "note": "committed perf baseline for tools/perf_gate.py; "
                    "update ONLY via --update-baseline and review the diff",
            "metrics": metrics}
    with open(path, "w") as f:
        json.dump(blob, f, indent=2, sort_keys=True)
        f.write("\n")
    return blob


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON path (default: repo "
                         "PERF_BASELINE.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the measured values as the new baseline "
                         "(structural counts overwritten, ratio bounds "
                         "preserved) instead of gating")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for smoke runs")
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass
        if args.platform == "cpu":
            # proxies 4/5 (pipe=2 / expert=2 mesh) need a multi-device
            # host: force 4 virtual CPU devices before backend init
            from bigdl_tpu.utils.platform import force_cpu
            force_cpu(4)
    # the regression demo (ISSUE 11 acceptance): an exported
    # BIGDL_TPU_CONV_ROUTE=pad wins over this default and the conv-ops
    # metric names the diff
    os.environ.setdefault("BIGDL_TPU_CONV_ROUTE", "matmul")
    # arm the compile-card ledger (in-memory; no artifacts unless the
    # operator pointed BIGDL_TPU_COMPILE_CARDS at a dir already)
    os.environ.setdefault("BIGDL_TPU_COMPILE_CARDS", "1")
    os.environ.pop("BIGDL_TPU_AOT_CACHE", None)  # proxy 3 owns its dir
    # the gate reads what the compiler makes of each program and times its
    # compiles: nothing may come out of a persistent cache that an earlier
    # run, or an earlier proxy of this run, filled (Engine.init arms it)
    os.environ["BIGDL_TPU_XLA_CACHE"] = "0"

    from bigdl_tpu.utils import config as _config

    t0 = time.perf_counter()
    measured, context = measure(args.batch_size)

    existing = None
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            existing = json.load(f)

    if args.update_baseline:
        blob = update_baseline(measured, args.baseline, existing)
        print(f"perf_gate: baseline updated -> {args.baseline} "
              f"({len(blob['metrics'])} metrics)", file=sys.stderr)
        print(json.dumps({"metric": "perf_gate", "ok": True,
                          "updated_baseline": args.baseline,
                          "measured": measured, "context": context}))
        return 0

    if existing is None:
        print(f"perf_gate: no baseline at {args.baseline} — run "
              "--update-baseline and commit the result", file=sys.stderr)
        print(json.dumps({"metric": "perf_gate", "ok": False,
                          "error": f"missing baseline {args.baseline}",
                          "measured": measured}))
        return 2

    time_slack = _config.get_float("GATE_TIME_SLACK", 1.0)
    rows, regressions = check(measured, existing, time_slack)
    width = max(len(r[0]) for r in rows) + 2
    for name, want, got, status in rows:
        print(f"  {name:<{width}} baseline={want!r:<10} "
              f"measured={got!r:<10} {status}", file=sys.stderr)
    print(json.dumps({"metric": "perf_gate",
                      "ok": not regressions,
                      "regressions": regressions,
                      "measured": measured,
                      "context": context,
                      "baseline": args.baseline,
                      "time_slack": time_slack,
                      "wall_s": round(time.perf_counter() - t0, 1)}))
    if regressions:
        print(f"perf_gate: REGRESSED: {', '.join(regressions)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
