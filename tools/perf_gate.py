#!/usr/bin/env python
"""Perf-regression gate: counts read off compiled programs, diffed against
a committed baseline.

A gate of *counts*, not of speed.  Every row of ``PERF_BASELINE.json`` is
either ``exact`` (an operation, buffer, byte or slot count of a compiled
program or a schedule table: any drift fails) or ``max`` (a ratio of bytes
or of schedule slots, bounded from above).  No row is a time or a ratio of
times: this runs on the CPU, and a statement about speed in this repository
is a run on the chip through ``benchmark/run.py``, recorded in
``PERF_LEDGER.jsonl`` (PERF.md).  What the gate holds is that the program
the next chip run measures is the program we think it is: the matmul conv
route deletes every ``convolution`` from the train step, the bucketed
wire's up-cast count equals the bucket count (not the leaf count), the
fused update runs over N dtype-homogeneous buffers, donation compiles into
input/output aliases.

Proxies (compile cards armed, utils/hlostats.py):

1. **conv route**: the compiled LeNet step under ``BIGDL_TPU_CONV_ROUTE``
   (defaulted to ``matmul``; exporting ``=pad`` is the regression demo)
   must contain 0 convolutions (``lenet_matmul.conv_ops``).
2. **wire + fused card**: with ``BIGDL_TPU_WIRE_BUCKET_MB=4`` and
   ``BIGDL_TPU_FUSED_UPDATE=1``, the card must report the expected
   wire-leaf / wire-bucket counts, a StableHLO up-cast (``f32<-bf16``)
   count bounded by the BUCKET count, the expected fused-buffer count,
   and donation aliases present.
3. **decode cache** (ISSUE 18): the continuous-batching ``DecodeEngine``'s
   per-slot KV-cache footprint (``decode.cache_bytes_per_slot``): a
   cache-layout or page-ladder regression changes the byte count.
4. **pipeline step card** (needs >= 2 devices; the cpu platform runs on
   a forced 4-virtual-device host): a ``partition_pipeline``'d MLP train
   step on a ``(1,1,1,2,1)`` MeshLayout: the card's ``pipe_microbatches``
   count, the GPipe ``pipe_bubble_fraction`` bound (idle schedule slots
   over all slots), and the schedule's ``collective-permute`` ops in the
   compiled program.
5. **expert step card**: a ``MoEFFN`` train step on ``(1,1,1,1,2)``, the
   GSPMD expert-sharded step's collective count, plus the explicit
   ``expert_parallel_ffn`` program's ``all-to-all`` op count.
6. **sharded embedding gather** (ISSUE 20): an ``embedding_row`` table
   under fsdp x tp lowers to gathers, no full-table all-gather, and sits
   at 1/N of its bytes per device (``embed.table_fraction``).
7. **1F1B schedule card** (ISSUE 13): the same pipe=2 mesh running the
   interleaved 1F1B schedule (``BIGDL_TPU_PIPE_SCHEDULE=1f1b``, v=2,
   m=8): the card's bubble fraction must stay under the interleaved
   bound, the compiled program's ``collective-permute`` count is pinned
   (fwd ring + the two bwd-table rings), the schedule table's analytic
   peak in-flight microbatches and their ratio to GPipe's keep-all
   ``m*v`` are pinned, and the XLA temp bytes of the 1F1B step over the
   GPipe step's (batch 256, activations dominating) must stay <= 1.

Intentional changes are a *reviewed diff* to the baseline: run
``--update-baseline`` and commit the result (counts are overwritten with
the measured program; ratio bounds are preserved).

Prints a readable per-metric diff, then ONE JSON line
(``metric=perf_gate``), and exits non-zero on any regression.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "PERF_BASELINE.json")
BASELINE_FORMAT = "bigdl_tpu-perf-baseline-v1"

#: bounds written by --update-baseline for the ratios of bytes and of
#: schedule slots (never overwritten with a measured value: the bound is
#: the claim, the measured ratio only has to stay under it)
DEFAULT_RATIO_BOUNDS = {
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


def _first_call(step, args):
    """One call of the step: it compiles, and the compile writes the card
    the proxies read."""
    import jax
    jax.block_until_ready(step(*args)[3])


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
    from bigdl_tpu.utils import hlostats

    measured, context = {}, {}
    set_policy(DTypePolicy())  # default policy: bf16 wire

    # ---- proxy 1: conv route ------------------------------------------
    route = os.environ["BIGDL_TPU_CONV_ROUTE"]  # defaulted in main()
    _fresh({"BIGDL_TPU_CONV_ROUTE": route,
            "BIGDL_TPU_FUSED_UPDATE": None,
            "BIGDL_TPU_WIRE_BUCKET_MB": None})
    hlostats.reset()
    step, args = _build_step(batch_size)
    _first_call(step, args)
    card = hlostats.last_card("optim.step")
    measured["lenet_matmul.conv_ops"] = card["convolutions"]
    context["route"] = {"route": route, "conv_ops": card["convolutions"],
                        "total_ops": card["total_ops"]}

    # ---- proxy 2: wire + fused card ----------------------------------
    _fresh({"BIGDL_TPU_WIRE_BUCKET_MB": "4",
            "BIGDL_TPU_FUSED_UPDATE": "1"})
    hlostats.reset()
    step, args = _build_step(batch_size)
    _first_call(step, args)
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

    # ---- proxy 3: the decode engine's per-slot cache bytes (slots=4,
    #     page=16 ladder on a CPU-sized LM: a deterministic byte count)
    import jax
    import numpy as np

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.serve import DecodeEngine
    lm = TransformerLM(vocab_size=256, max_len=128, d_model=64,
                       num_heads=4, num_layers=2).build(jax.random.key(0))
    drng = np.random.default_rng(3)
    with DecodeEngine(lm, slots=4, page=16) as eng:
        eng.generate(drng.integers(1, 256, size=5).astype(np.int32), 8,
                     timeout=120)
        dstats = eng.stats()
    measured["decode.cache_bytes_per_slot"] = dstats["cache_bytes_per_slot"]
    context["decode"] = {"tokens_out": dstats["tokens_out"],
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
    _first_call(step, args)
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
    _first_call(step, args)
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

    # ---- proxy 7: 1F1B schedule card + memory ratio (ISSUE 13) -------
    from bigdl_tpu.parallel import build_schedule
    _fresh({"BIGDL_TPU_PIPE_MICROBATCHES": "8",
            "BIGDL_TPU_PIPE_SCHEDULE": "1f1b",
            "BIGDL_TPU_PIPE_VIRTUAL_STAGES": "2"})
    hlostats.reset()
    step, args = _build_layout_step((1, 1, 1, 2, 1), _pipe4_1f1b_model)
    _first_call(step, args)
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


def check(measured, baseline):
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
            ok = got <= want
            detail = f"<= {want}"
        else:
            ok, detail = False, f"unknown match kind {match!r}"
        rows.append((name, want, got, "OK" if ok else f"REGRESSED ({detail})"))
        if not ok:
            regressions.append(name)
    return rows, regressions


def update_baseline(measured, path, existing):
    """Write the measured counts as the new baseline; ratio bounds keep
    their existing (or default) values: an intentional change is the
    committed diff of this file."""
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
                         "(counts overwritten, ratio bounds "
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
    os.environ.pop("BIGDL_TPU_AOT_CACHE", None)
    # the gate reads what the compiler makes of each program: nothing may
    # come out of a persistent cache that an earlier run, or an earlier
    # proxy of this run, filled (Engine.init arms it)
    os.environ["BIGDL_TPU_XLA_CACHE"] = "0"

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

    rows, regressions = check(measured, existing)
    width = max(len(r[0]) for r in rows) + 2
    for name, want, got, status in rows:
        print(f"  {name:<{width}} baseline={want!r:<10} "
              f"measured={got!r:<10} {status}", file=sys.stderr)
    print(json.dumps({"metric": "perf_gate",
                      "ok": not regressions,
                      "regressions": regressions,
                      "measured": measured,
                      "context": context,
                      "baseline": args.baseline}))
    if regressions:
        print(f"perf_gate: REGRESSED: {', '.join(regressions)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
