"""Benchmark harness: prints ONE JSON line
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N, ...}

North-star (BASELINE.md): ResNet-50 ImageNet images/sec/chip at >=45% MFU on
TPU v5e.  All five BASELINE.md configs are benched (resnet50, lenet,
inception_v1, textcnn, lstm); the primary JSON line is the ResNet-50 result
with the others embedded under "configs".

The reference's throughput metric is records/second logged per iteration
(DistriOptimizer.scala:293-297); we report the same unit for the compiled
train step (forward + loss + backward + update) on one chip.  The step is
built by Optimizer._build_step — the exact program real training runs.

Timing methodology (utils/timing.py)
------------------------------------
JAX returns from a call when the work is enqueued, so a loop timed without
waiting for the device reports the enqueue (an early round of this harness
did, and printed an MFU above 1).  Every timing here therefore:
  1. drains the dispatch queue with a host fetch,
  2. enqueues n chained steps (step i consumes step i-1's params, so nothing
     can be elided or reordered), fetches a scalar from the last output, and
  3. DIFFERENCES two chain lengths: dt = (T(n2) - T(n1)) / (n2 - n1),
     cancelling the constant cost of the fetch and the first dispatch.
A per-step fully-synced timing is also reported (`step_seconds_sync`) as a
cross-check; it upper-bounds dt by one dispatch plus one fetch.

MFU accounting: model FLOPs/step counted analytically from the jaxpr of the
*actual train step* (fwd + bwd + update; `bigdl_tpu.utils.flops`), with XLA's
`compiled.cost_analysis()` as a cross-check.  The peak-FLOP/s denominator is
max(device-kind table, measured bf16-matmul roofline) — a harness whose
denominator yields MFU > 1 refuses to report that MFU (emits `mfu_error`
diagnostics instead).

vs_baseline: the reference publishes no numbers (BASELINE.md "published: {}").
vs_baseline = MFU / 0.45 (the BASELINE.md target) when ResNet-50 MFU is
measurable, else null — never an invented constant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

MFU_TARGET = 0.45  # BASELINE.md: ResNet-50 >= 45% MFU on v5e
_SCALING_TIMEOUT = 420  # seconds for the CPU scaling subprocess

# bf16 peak FLOP/s per jax device now lives in utils/flops.py
# (device_peak_flops) — shared with the Optimizer's per-step mfu counter.


# Stall watchdog: a compile or a device call that never returns would eat the
# caller's whole bench budget and land NO json line.  The
# watchdog is the shared supervision subsystem (bigdl_tpu.utils.supervisor
# — the same Supervisor the Optimizer uses, so there is ONE liveness
# mechanism, not two) with a bench-specific on_stall callback that emits
# partial results (or a bench_error) and exits.  Stage transitions are
# phase-tagged heartbeats; utils/timing's measure loops notify the active
# supervisor per rep for free.
_STALL_STATE = {"results": {}, "errors": {}, "skipped": [], "meta": None}
# --out artifact state: when armed, every completed config incrementally
# flushes to `<out>.partial.json` and every exit path (success, stall,
# backend-init death) leaves SOMETHING on disk — a run that dies at
# jax.devices() with zero artifacts is the one outcome this forbids
_OUT_STATE = {"path": None, "t_start": None}
# stages that legitimately hold ONE long silent device/subprocess call and
# get the --compile-stall-seconds allowance: backend init, XLA compiles,
# jaxpr tracing, the roofline's compile+timed 8192^3 matmul chains, the
# scaling subprocess (own timeout _SCALING_TIMEOUT=420s > the short limit),
# and timing ("time:*"): per-rep heartbeats bound most silences to one rep,
# but the fetch of one n2=16 chain is a single blocking call that can pass
# 300s on slow backends (resnet50 under --platform cpu); "e2e" holds the
# final sync fetch of the end-to-end input-pipeline loop
_LONG_STAGES = ("init", "compile", "trace", "roofline", "scaling", "time",
                "e2e")
_EMIT_LOCK = threading.Lock()
_EMITTED = [None]  # thread ident of the claimant
_EMIT_DONE = threading.Event()  # set once the final line is on stdout


def _claim_emit() -> bool:
    """Exactly one THREAD may write the final JSON line (the watchdog can
    race a main thread whose hung RPC resolves right after the idle check).
    Re-entrant for the claimant so its nested _fail/print paths still work."""
    me = threading.get_ident()
    with _EMIT_LOCK:
        if _EMITTED[0] is None:
            _EMITTED[0] = me
            return True
        return _EMITTED[0] == me


def _on_bench_stall(stall):
    """Supervisor on_stall callback: one thread claims the final JSON line
    and the process exits; a lost claim stops the watchdog (the main
    thread's late-resolving RPC owns the line).  Returns True to stop
    monitoring."""
    if not _claim_emit():
        return True
    # from here this thread OWNS the process exit: any uncaught raise
    # (e.g. stderr pipe gone mid-log) must still _exit, or the parked
    # loser threads would leave a zombie bench process holding the TPU
    try:
        _watchdog_emit(stall["phase"], stall["idle_seconds"],
                       stall["deadline_seconds"])
    except Exception:  # noqa: BLE001
        pass
    os._exit(1)


_SUP = None  # the shared Supervisor, built lazily (keeps `import bench` light)


def _get_sup():
    global _SUP
    if _SUP is None:
        from bigdl_tpu.utils import supervisor as _supervision
        _SUP = _supervision.Supervisor(name="bench-watchdog",
                                       on_stall=_on_bench_stall,
                                       poll_interval=10.0)
    return _SUP


def _beat(stage=None):
    _get_sup().beat(stage)


def _log(msg):
    _beat()
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _flush_trace():
    """Best-effort final flush of the run tracer (--trace): every bench
    exit path calls this so a partial trace is still loadable."""
    try:
        from bigdl_tpu.utils import telemetry
        tr = telemetry.get_active()
        if tr is not None:
            tr.flush()
    except Exception:  # noqa: BLE001 — telemetry must never fail the bench
        pass


def _env_snapshot():
    """The environment knobs a failed-round post-mortem needs: every
    BIGDL_TPU_* plus the jax/XLA/libtpu selectors."""
    keep_prefixes = ("BIGDL_TPU_", "JAX_", "TPU_")
    keep_exact = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "XLA_PYTHON_CLIENT_MEM_FRACTION")
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith(keep_prefixes) or k in keep_exact}


def _write_json_atomic(path, obj):
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _flush_partial(stage, error=None, tb=None):
    """Rewrite `<out>.partial.json` with everything concluded so far.
    Armed by --out; a broken artifact write must never fail the bench."""
    out = _OUT_STATE.get("path")
    if not out:
        return
    rec = {"metric": "bench_partial", "partial": True, "stage": stage,
           "platform": sys.platform,
           "results": dict(_STALL_STATE["results"]),
           "config_errors": dict(_STALL_STATE["errors"]),
           "configs_skipped_budget": list(_STALL_STATE["skipped"]),
           "env": _env_snapshot()}
    if _OUT_STATE.get("t_start") is not None:
        rec["elapsed_s"] = round(time.perf_counter() -
                                 _OUT_STATE["t_start"], 1)
    if error is not None:
        rec["error"] = str(error)
        rec["error_type"] = type(error).__name__ \
            if isinstance(error, BaseException) else "str"
    if tb:
        rec["traceback"] = tb
    try:
        _write_json_atomic(f"{out}.partial.json", rec)
    except Exception as e:  # noqa: BLE001 — artifacts are best-effort
        print(f"[bench] partial flush failed: {e}", file=sys.stderr)


def _write_out(obj):
    """Write the final JSON record to the --out path (stdout still gets
    the one-line contract either way)."""
    out = _OUT_STATE.get("path")
    if not out:
        return
    try:
        _write_json_atomic(out, obj)
    except Exception as e:  # noqa: BLE001
        print(f"[bench] --out write failed: {e}", file=sys.stderr)


def _fail(err, stage):
    _flush_trace()
    # leave evidence BEFORE racing for the stdout line: a backend-init
    # death (`jax.devices()` hang/raise) must still produce an artifact
    # holding the platform, the env knobs, and the traceback
    import traceback as _tb
    tb = None
    if isinstance(err, BaseException) and err.__traceback__ is not None:
        tb = "".join(_tb.format_exception(type(err), err, err.__traceback__))
    _flush_partial(stage, error=err, tb=tb)
    if not _claim_emit():
        # another thread claimed the final line (possibly the watchdog
        # emitting a VALID partial-results record with exit 0) — give it a
        # long grace instead of os._exit(1)-ing immediately: racing the
        # claimant's exit could stamp a failed status onto a usable
        # artifact.  The grace is bounded (not park-forever) so a claimant
        # that died between claiming and exiting cannot leave a zombie
        # bench process holding the TPU.
        _EMIT_DONE.wait(timeout=120)
        time.sleep(600)
        os._exit(1)
    err_rec = {"metric": "bench_error", "value": 0.0, "unit": "error",
               "vs_baseline": None, "stage": stage, "error": str(err),
               "traceback": tb, "platform": sys.platform,
               "env": _env_snapshot(),
               "results": dict(_STALL_STATE["results"])}
    _write_out(err_rec)
    print(json.dumps({"metric": "bench_error", "value": 0.0, "unit": "error",
                      "vs_baseline": None, "stage": stage, "error": str(err)}))
    sys.stdout.flush()
    _EMIT_DONE.set()
    os._exit(1)


def _init_backend(timeout=None, retries=3, backoff=15):
    """Bring up the jax backend with a time limit: jax.devices() blocks for
    as long as another process holds the chip, and can raise transient
    UNAVAILABLE during chip handoff.  The limit is tunable
    (`BIGDL_TPU_BENCH_INIT_TIMEOUT` seconds) so a caller with a tight window
    can choose fast-fail-with-artifacts over patience."""
    import jax

    if timeout is None:
        try:
            timeout = float(os.environ.get("BIGDL_TPU_BENCH_INIT_TIMEOUT",
                                           240))
        except ValueError:
            timeout = 240

    last_err = None
    for attempt in range(retries):
        box = {}

        def probe():
            try:
                box["devices"] = jax.devices()
            except Exception as e:  # noqa: BLE001 — recorded, retried
                box["error"] = e

        t = threading.Thread(target=probe, daemon=True)
        t.start()
        t.join(timeout)
        if "devices" in box:
            return jax, box["devices"]
        if t.is_alive():
            # stuck inside native backend init; in-process retry can't help
            _fail(TimeoutError(
                f"jax.devices() did not return within {timeout}s"), "init")
        last_err = box.get("error")
        if attempt < retries - 1:
            time.sleep(backoff * (attempt + 1))
    _fail(last_err, "init")


def _table_peak_flops(device):
    from bigdl_tpu.utils.flops import device_peak_flops
    val, source = device_peak_flops(device)
    # bench refuses to report MFU against the made-up CPU denominator
    # (the trace counter uses it as a relative signal; a bench JSON line
    # must not) — table and explicit BIGDL_TPU_PEAK_FLOPS both count
    return val if source in ("table", "env") else None


def _aot_delta(before):
    """Per-config AOT-cache ledger for the bench record: counter deltas
    since `before` (utils/aot.stats snapshot), or a disabled marker."""
    from bigdl_tpu.utils import aot as aot_mod
    if not aot_mod.enabled():
        return {"enabled": False}
    after = aot_mod.stats()
    return {"enabled": True,
            **{k: int(after[k] - before[k])
               for k in ("hits", "misses", "stores", "compiles")}}


def _step_flops(jitted, compiled, example_args):
    """Model FLOPs for ONE train step: analytic jaxpr count (primary) with
    XLA cost_analysis as cross-check.  Failures are logged, never swallowed
    (round-2 verdict: resnet50 mfu=null from a silently-dead probe)."""
    import jax
    from bigdl_tpu.utils.flops import jaxpr_flops

    analytic = xla = None
    try:
        # trace with the tiny-channel conv pad disabled: MFU must count the
        # NOMINAL model FLOPs, not the zero channels _pad_tiny_cin adds for
        # compile speed (LeNet's conv FLOPs would otherwise inflate ~3x);
        # xla cost_analysis below still sees the padded compiled program,
        # which can legitimately trip the disagreement log for tiny models.
        # Trace the UNJITTED function (`.raw`, set by _build_step): tracing
        # the jitted wrapper would hit pjit's cached (padded) trace and
        # ignore the env toggle entirely.
        fn = getattr(jitted, "raw", jitted)
        prior = os.environ.get("BIGDL_TPU_CONV_PAD_MIN_CIN")
        os.environ["BIGDL_TPU_CONV_PAD_MIN_CIN"] = "0"
        try:
            # fresh lambda: make_jaxpr caches by function identity, and a
            # prior trace of fn under different env settings must not leak
            analytic = jaxpr_flops(
                jax.make_jaxpr(lambda *a: fn(*a))(*example_args))
        finally:
            if prior is None:
                del os.environ["BIGDL_TPU_CONV_PAD_MIN_CIN"]
            else:
                os.environ["BIGDL_TPU_CONV_PAD_MIN_CIN"] = prior
    except Exception as e:  # noqa: BLE001
        _log(f"analytic flops failed: {type(e).__name__}: {e}")
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca:
            xla = float(ca.get("flops", 0.0)) or None
    except Exception as e:  # noqa: BLE001
        _log(f"xla cost_analysis failed: {type(e).__name__}: {e}")
    if analytic and xla and not (0.3 < xla / analytic < 3.0):
        _log(f"flops disagreement: analytic={analytic:.3e} xla={xla:.3e}")
    return analytic or xla, {"flops_analytic": analytic, "flops_xla": xla}


def _make_record(name, batch, dt, timing, compile_s, flops_step,
                 flops_detail, peak_flops, compute_dtype, **extra):
    """Shared MFU gate + result-record assembly for train and inference
    benches: refuses any MFU outside (0,1] with full diagnostics."""
    mfu = mfu_raw = mfu_error = None
    if flops_step and peak_flops:
        mfu_raw = flops_step / dt / peak_flops
        if 0.0 < mfu_raw <= 1.0:
            mfu = round(mfu_raw, 4)
        else:
            mfu_error = (
                f"raw MFU {mfu_raw:.3f} outside (0,1]: flops/step="
                f"{flops_step:.3e}, dt={dt:.6f}s, peak={peak_flops:.3e} — "
                "timing and FLOPs disagree; refusing to report")
            _log(f"{name}: {mfu_error}")
    rec = {"name": name, "images_per_sec": round(batch / dt, 2),
           "step_seconds": round(dt, 6),
           "step_seconds_sync": round(timing["step_seconds_sync"], 6),
           "batch_size": batch,
           "compute_dtype": compute_dtype,
           "compile_seconds": round(compile_s, 2),
           "model_flops_per_step": flops_step,
           "mfu": mfu, "timing": timing, **flops_detail, **extra}
    if name.startswith("resnet50") and extra.get("mode") != "inference" \
            and peak_flops:  # peak is only set on real accelerator runs
        # measured decomposition, docs/benchmarking.md "BN bandwidth
        # ceiling": exact batch-stat BN adds ~4 activation-sized HBM
        # passes (~22ms at batch 256), capping train MFU near 0.35 on one
        # v5e chip; eval-mode grad = 0.452, inference fwd = 0.61
        rec["mfu_note"] = ("train-mode BN batch statistics are "
                           "HBM-bound; see docs/benchmarking.md for the "
                           "measured ceiling decomposition")
    if mfu_error:
        rec["mfu_raw"] = round(mfu_raw, 4)
        rec["mfu_error"] = mfu_error
    return rec


def _bench_e2e(name, compiled, box, inp, tgt, data_sh, lr_arr, rng,
               iters=6):
    """End-to-end records/s INCLUDING the input pipeline: a host-side
    source re-collates numpy copies of the batch each iteration (the
    per-batch memcpy cost a real pipeline pays), the shared background
    prefetcher (dataset/prefetch.PrefetchIterator) stages each batch onto
    the device while the previous step runs, and the loop is synced by a
    final host fetch.  `data_wait_fraction` = consumer time spent waiting
    on the prefetch queue / total wall — the input-bound vs compute-bound
    diagnosis the prefetch win is measured by."""
    import numpy as np

    from bigdl_tpu.dataset.prefetch import PrefetchIterator
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.optim.optimizer import _put_batch
    from bigdl_tpu.utils import telemetry

    inp_np, tgt_np = np.asarray(inp), np.asarray(tgt)
    batch = int(inp_np.shape[0])

    def source():
        for _ in range(iters):
            yield MiniBatch(np.ascontiguousarray(inp_np),
                            np.ascontiguousarray(tgt_np))

    def stage(b):
        return _put_batch((b.get_input(), b.get_target()), data_sh)

    pipe = PrefetchIterator(source(), depth=2, transform=stage)
    # the SAME Metrics counter shape the train loop keeps (one source for
    # the epoch log, the bench record, and telemetry — Metrics.snapshot)
    metrics = Metrics()
    loss = None
    t0 = time.perf_counter()
    try:
        while True:
            _beat()
            g0 = time.perf_counter()
            item = next(pipe, None)
            dw = time.perf_counter() - g0
            metrics.add("get batch time average", dw)
            telemetry.complete("data", dw)
            if item is None:
                break
            di, dt_ = item
            s0 = time.perf_counter()
            box["params"], box["net_state"], box["opt_state"], loss = \
                compiled(box["params"], box["net_state"], box["opt_state"],
                         di, dt_, lr_arr, rng)
            step_s = time.perf_counter() - s0
            metrics.add("computing time average", step_s)
            telemetry.complete("step", step_s)
            telemetry.counter("bench_e2e", data_wait_s=dw, step_s=step_s)
        if loss is not None:
            float(loss)  # host fetch: waits for the device
    finally:
        pipe.close()
    wall = time.perf_counter() - t0
    data_wait = metrics.get("get batch time average")[0]
    frac = data_wait / wall if wall > 0 else 0.0
    return {
        "records_per_sec_e2e": round(iters * batch / wall, 2),
        "data_wait_fraction": round(frac, 4),
        "pipeline_diagnosis": (
            f"input-bound (data_wait_fraction {frac:.2f} > 0.5: the host "
            "pipeline gates the chip — raise prefetch depth/threads)"
            if frac > 0.5 else
            f"compute-bound (data_wait_fraction {frac:.2f} <= 0.5: the "
            "device step sets the pace)"),
        "metrics": metrics.snapshot(),
        "input_pipeline": {"depth": 2, "staged": True,
                           "iterations": iters},
    }


def _bench_config(name, build, peak_flops):
    """Time the REAL compiled train step (Optimizer._build_step) on a 1-chip
    mesh; returns images/sec + flops/step + mfu."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils.engine import Engine

    from bigdl_tpu.common import DTypePolicy, get_policy, set_policy

    set_policy(DTypePolicy())  # each config owns its policy; reset first
    model, criterion, inp, tgt, lr = build()
    policy = get_policy()
    Engine.reset()
    # per-CHIP numbers: bench on device 0 only, so flops/dt is divided by a
    # single device's peak (a mesh over N devices would inflate MFU by N).
    # BIGDL_TPU_BENCH_LAYOUT="data,fsdp,tp" (or the 5-axis
    # "data,fsdp,tp,pipe,expert") instead benches the config on a
    # MeshLayout mesh with role-resolved FSDP/TP/pipeline/expert
    # shardings (parallel/layout) — the per-device memory block below is
    # where the
    # 1/N footprint shows up in the trajectory.
    layout_env = os.environ.get("BIGDL_TPU_BENCH_LAYOUT")
    strategy = None
    if layout_env:
        from bigdl_tpu.parallel import LayoutSharding, MeshLayout
        layout = MeshLayout.parse(layout_env)
        Engine.set_mesh(layout.build_mesh())
        strategy = LayoutSharding(model)
    else:
        Engine.init(devices=[jax.devices()[0]])
    mesh = Engine.mesh()

    model.build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=criterion,
                    end_trigger=Trigger.max_iteration(1),
                    strategy=strategy)
    opt.set_optim_method(SGD(learning_rate=lr, momentum=0.9))
    # perf knobs measured by bigdl_tpu.tools.bn_experiment: remat policy for
    # the timed step (BIGDL_TPU_BENCH_REMAT=conv_out|full) composes with the
    # BIGDL_TPU_BN_FUSED_VJP config-tier flag read inside BatchNormalization
    bench_remat = os.environ.get("BIGDL_TPU_BENCH_REMAT")
    if bench_remat:
        opt.set_remat(bench_remat)
    step, param_sh, data_sh = opt._build_step(mesh)

    params = jax.device_put(model.params, param_sh)
    net_state = model.state
    opt_state = opt.optim_method.init_state(params)
    lr_arr, rng = jnp.float32(lr), jax.random.key(1)

    _beat(f"compile:{name}")
    from bigdl_tpu.utils import aot as aot_mod
    aot0 = aot_mod.stats()
    t0 = time.perf_counter()
    lowered = step.lower(params, net_state, opt_state, inp, tgt, lr_arr, rng)
    # tracing just ran any pipeline microbatch clamp: fold the effective
    # count into the card/knobs before either is recorded
    opt._refresh_pipe_effective()
    # AOT executable cache (BIGDL_TPU_AOT_CACHE): a warm config's
    # compile_seconds collapses to one cache read; disabled -> identical
    # to the old lowered.compile()
    compiled = aot_mod.cached_compile(
        lowered, label=f"bench.{name}", mesh=mesh,
        example_args=(params, net_state, opt_state, inp, tgt, lr_arr, rng),
        extra=opt._aot_extra,
        card_extra=dict(opt._card_extra))
    compile_s = time.perf_counter() - t0
    aot_rec = _aot_delta(aot0)
    # compiled-program self-description (utils/hlostats): the headline op
    # counts of this config's compile card, embedded in the record so a
    # bench JSON alone can answer "did the step really have 0 convs /
    # bucketed wire / donated buffers" without re-running anything
    card_rec = None
    from bigdl_tpu.utils import hlostats as _hlostats
    card = _hlostats.last_card(f"bench.{name}")
    if card is not None:
        card_rec = {k: card.get(k) for k in
                    ("convolutions", "dots", "converts", "collectives",
                     "custom_calls", "total_ops", "input_output_aliases",
                     "donation", "source")}

    _beat(f"trace:{name}")
    flops_step, flops_detail = _step_flops(
        step, compiled, (params, net_state, opt_state, inp, tgt, lr_arr, rng))
    _beat(f"time:{name}")

    box = {"params": params, "net_state": net_state, "opt_state": opt_state}

    def run():
        box["params"], box["net_state"], box["opt_state"], loss = compiled(
            box["params"], box["net_state"], box["opt_state"],
            inp, tgt, lr_arr, rng)
        return loss

    from bigdl_tpu.utils.timing import measure_step_seconds
    dt, timing = measure_step_seconds(
        run, log=lambda m: _log(f"{name}: {m}"), progress=_beat)
    # per-device memory block (utils/memstats): runtime ledger (peak HBM)
    # when the backend has one, live-buffer sum fallback on CPU — plus
    # per-device param/slot bytes, where FSDP's 1/N footprint and
    # donation's savings show up in the bench trajectory
    from bigdl_tpu.utils import memstats
    try:
        memory = memstats.memory_record(box["params"], box["opt_state"])
        if layout_env:
            memory["layout"] = layout_env
        # per-stage param bytes for pipelined configs (GPipeSequential):
        # the pipe axis's 1/n-per-device claim, visible in the record
        # per-table bytes for embedding-role params (LookupTable):
        # recommender memory is table-dominated, and `device_fraction`
        # shows the fsdp×tp 1/N row-sharding working per config
        tables = memstats.embedding_table_bytes(model, box["params"])
        if tables:
            memory["embedding_tables"] = tables
        stages = memstats.pipeline_stage_bytes(model, box["params"])
        if stages:
            memory["pipeline_stages"] = stages
            # schedule attribution beside the per-stage memory block
            # (ISSUE 13): which schedule the step baked in, how many
            # interleaved slices, and the measured bubble of the ACTUAL
            # (clamped) microbatch count — one artifact is enough to
            # A/B gpipe vs 1f1b on the next real-TPU round
            if opt._pipe_info is not None:
                _, _pmod = opt._pipe_info
                memory["pipe_schedule"] = opt._step_knobs.get(
                    "pipe_schedule")
                memory["pipe_virtual_stages"] = opt._step_knobs.get(
                    "pipe_virtual_stages")
                memory["pipe_microbatches"] = opt._step_knobs.get(
                    "pipe_microbatches")
                if _pmod._last_bubble is not None:
                    memory["pipe_bubble_fraction"] = round(
                        _pmod._last_bubble, 4)
    except Exception as e:  # noqa: BLE001 — diagnostics, never fatal
        _log(f"{name}: memory stats failed: {type(e).__name__}: {e}")
        memory = {"error": f"{type(e).__name__}: {e}"}
    # step-arithmetic attribution: the fused/bucket knobs the step was
    # traced with, plus the standalone (unoverlapped) gradient-wire
    # collective cost — 0.0 on this 1-chip mesh, measured on pod meshes —
    # so the MFU trajectory can attribute wins to the right knob
    from bigdl_tpu.parallel import wire as _wire
    try:
        collective_s = _wire.measure_collective_seconds(
            mesh, params, policy.wire_dtype, axis=("data", "fsdp"))
    except Exception as e:  # noqa: BLE001 — diagnostics, never fatal
        _log(f"{name}: collective probe failed: {type(e).__name__}: {e}")
        collective_s = None
    step_arith = {
        "step_knobs": dict(opt._step_knobs),
        "collective_s": (None if collective_s is None
                         else round(collective_s, 6)),
        "collective_fraction": (None if collective_s is None
                                else round(min(1.0, collective_s / dt), 4)),
    }
    _beat(f"e2e:{name}")
    try:
        e2e = _bench_e2e(name, compiled, box, inp, tgt, data_sh,
                         lr_arr, rng)
    except Exception as e:  # noqa: BLE001 — e2e must not kill the config
        _log(f"{name}: e2e input-pipeline bench failed: "
             f"{type(e).__name__}: {e}")
        e2e = {"e2e_error": f"{type(e).__name__}: {e}"}
    return _make_record(name, int(inp.shape[0]), dt, timing, compile_s,
                        flops_step, flops_detail, peak_flops,
                        jnp.dtype(policy.compute_dtype).name,
                        aot_cache=aot_rec, memory=memory,
                        compile_card=card_rec, **step_arith,
                        **e2e)


def _bench_resnet50_bf16_autotune(name, build, peak_flops):
    """Race the semantics-identical BN implementations for the HEADLINE
    config and report the fastest, with per-variant provenance.

    Rationale: the BN variant race (bigdl_tpu.tools.bn_experiment) has
    never executed on hardware, so the default BN path is an unmeasured
    guess.  If the only hardware contact is a bench run, this race IS the
    measurement: baseline XLA stats, the hand-written fused VJP
    (BIGDL_TPU_BN_FUSED_VJP), and conv-epilogue stat fusion
    (nn.fuse_conv_bn) — all parity-pinned against torch goldens /
    the unfused model, so whichever wins is numerically identical.
    A variant failure is recorded and skipped, never fatal.  Gated to
    real TPUs (BIGDL_TPU_BENCH_BN_AUTOTUNE=0 disables; =1 forces on CPU,
    where tripling a multi-minute compile is test-only).
    """
    import jax

    on_tpu = jax.default_backend() == "tpu"
    auto = os.environ.get("BIGDL_TPU_BENCH_BN_AUTOTUNE", "")
    if auto == "0" or (not on_tpu and auto != "1"):
        return _bench_config(name, build, peak_flops)

    variants = [
        ("baseline", {}, False),
        ("fused_vjp", {"BIGDL_TPU_BN_FUSED_VJP": "1"}, False),
        # off-TPU (forced-on test mode) ConvBN needs the explicit
        # interpret opt-in or it silently falls back to the unfused
        # children and 'conv_epilogue' would mislabel a baseline run
        ("conv_epilogue",
         {} if on_tpu
         else {"BIGDL_TPU_BN_IMPL": "pallas_interpret"}, True),
    ]
    raced, best = {}, None
    for vname, env, fuse in variants:
        def build_v(fuse=fuse):
            out = build()
            if fuse:
                from bigdl_tpu.nn import fuse_conv_bn
                fuse_conv_bn(out[0])
            return out

        # ambient BN knobs would corrupt the race (an exported
        # BN_FUSED_VJP=1 makes "baseline" measure the fused path) — pop
        # them all first, like bn_experiment does, and restore after
        bn_vars = ("BIGDL_TPU_BN_FUSED_VJP", "BIGDL_TPU_BN_IMPL",
                   "BIGDL_TPU_BN_STAT_ROWS")
        saved = {k: os.environ.get(k) for k in (*bn_vars, *env)}
        for k in bn_vars:
            os.environ.pop(k, None)
        os.environ.update(env)
        try:
            rec = _bench_config(name, build_v, peak_flops)
            rec["bn_variant"] = vname
            raced[vname] = {k: rec[k] for k in
                            ("step_seconds", "images_per_sec", "mfu",
                             "compile_seconds")}
            if best is None or rec["step_seconds"] < best["step_seconds"]:
                best = rec
        except Exception as e:  # noqa: BLE001 — a variant must not kill
            _log(f"{name}: variant {vname} failed: {e}")  # the headline
            raced[vname] = {"error": f"{type(e).__name__}: {e}"}
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    if best is None:
        raise RuntimeError(f"every BN variant failed: {raced}")
    best["bn_variants_raced"] = raced
    return best


def _bench_infer(name, build, peak_flops):
    """Time the compiled INFERENCE forward (the Predictor/Evaluator hot path,
    reference AbstractModule.evaluate -> Evaluator.test, SURVEY.md §3.4) on
    one chip: batched apply(training=False), fwd-only FLOPs."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
    from bigdl_tpu.utils.timing import measure_step_seconds

    set_policy(DTypePolicy())
    model, _criterion, inp, _tgt, _lr = build()
    policy = get_policy()
    model.build(jax.random.key(0))
    params, net_state = model.params, model.state

    # `tok` chains call i to call i-1's output so measure_chain's
    # all-prior-calls dependency contract holds (the broadcast-add
    # materializes one extra copy of x — a small, conservative overcount)
    def forward(p, x, tok):
        out, _ = model.apply(p, net_state, x + tok * 0, training=False,
                             rng=None)
        return out, jnp.mean(out.astype(jnp.float32)) * 0

    tok0 = jnp.float32(0)
    _beat(f"compile:{name}")
    from bigdl_tpu.utils import aot as aot_mod
    aot0 = aot_mod.stats()
    t0 = time.perf_counter()
    lowered = jax.jit(forward).lower(params, inp, tok0)
    compiled = aot_mod.cached_compile(lowered, label=f"bench.{name}.infer",
                                      example_args=(params, inp, tok0))
    compile_s = time.perf_counter() - t0
    aot_rec = _aot_delta(aot0)
    _beat(f"trace:{name}")
    flops_step, flops_detail = _step_flops(forward, compiled,
                                           (params, inp, tok0))
    _beat(f"time:{name}")

    box = {"tok": tok0}

    def run():
        out, box["tok"] = compiled(params, inp, box["tok"])
        return out

    dt, timing = measure_step_seconds(run, log=lambda m: _log(f"{name}: {m}"),
                                      progress=_beat)
    from bigdl_tpu.utils import memstats
    try:
        memory = memstats.memory_record(params)
    except Exception as e:  # noqa: BLE001 — diagnostics, never fatal
        memory = {"error": f"{type(e).__name__}: {e}"}
    return _make_record(name, int(inp.shape[0]), dt, timing, compile_s,
                        flops_step, flops_detail, peak_flops,
                        jnp.dtype(policy.compute_dtype).name,
                        mode="inference", aot_cache=aot_rec, memory=memory)


def _bench_flash(name, build, peak_flops):
    """Flash-attention kernel bench: Pallas vs the jnp reference path,
    fwd+bwd at long sequence.  MFU from the analytic attention FLOPs (jaxpr_flops cannot see
    inside pallas_call): causal fwd 4*B*H*T^2*D/2, bwd ~2.5x fwd (dV, dP,
    dQ, dK plus the blockwise score recompute)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.attention import flash_attention
    from bigdl_tpu.utils.timing import measure_step_seconds

    B, H, T, D = build()
    # off-TPU (--platform cpu smoke) the kernel runs in interpret mode,
    # which is Python-per-block slow — clamp the default long-sequence
    # shape so a CPU run cannot grind for hours / trip the stall watchdog
    interpret = jax.default_backend() != "tpu"
    if interpret and B * H * T > 2 * 256:
        B, H, T = 1, 2, min(T, 256)
        _log(f"{name}: non-TPU backend, clamping interpret-mode shape to "
             f"({B},{H},{T},{D})")
    q, k, v = (jax.random.normal(jax.random.key(i), (B, H, T, D),
                                 jnp.bfloat16) for i in range(3))
    flops = 3.5 * (4.0 * B * H * T * T * D) / 2.0  # causal fwd+bwd

    def timed(use_pallas):
        def loss(q, k, v, tok):
            out = flash_attention(q + tok * 0, k, v, causal=True,
                                  use_pallas=use_pallas,
                                  interpret=interpret and use_pallas)
            return jnp.sum(out.astype(jnp.float32)) * 1e-6

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        _beat(f"compile:{name}")
        t0 = time.perf_counter()
        compiled = g.lower(q, k, v, jnp.bfloat16(0)).compile()
        compile_s = time.perf_counter() - t0
        box = {"tok": jnp.bfloat16(0)}

        def run():
            dq, dk, dv = compiled(q, k, v, box["tok"])
            # chain: next call's inputs depend on this call's output
            box["tok"] = jnp.sum(dq[0, 0, 0, :8]).astype(jnp.bfloat16) * 0
            return dq

        _beat(f"time:{name}")
        dt, timing = measure_step_seconds(
            run, log=lambda m: _log(f"{name}: {m}"), progress=_beat)
        return dt, timing, compile_s

    dt_p, timing_p, comp_p = timed(True)
    dt_r, timing_r, comp_r = timed(False)
    rec = _make_record(name, B, dt_p, timing_p, comp_p, flops,
                       {"flops_analytic": flops, "flops_xla": None},
                       peak_flops, "bfloat16",
                       mode="op", shape=[B, H, T, D],
                       reference_dt_seconds=round(dt_r, 6),
                       speedup_vs_reference=round(dt_r / dt_p, 3))
    if peak_flops:
        # same (0,1] sanity gate _make_record applies to the primary MFU:
        # a differencing glitch must not smuggle an impossible number in
        mfu_ref = flops / dt_r / peak_flops
        if 0 < mfu_ref <= 1:
            rec["mfu_reference_path"] = round(mfu_ref, 4)
        else:
            rec["mfu_reference_path"] = None
            rec["mfu_reference_path_error"] = (
                f"raw MFU {mfu_ref:.3f} outside (0,1]: dt={dt_r:.6f}s")
    return rec


def _cfg_flash():
    """(B, H, T, D): 4k sequence, 16 heads of 64 — the long-context shape
    ring attention shards (parallel/ring_attention.py).
    BIGDL_TPU_BENCH_FLASH_SHAPE=B,H,T,D overrides (CPU smoke tests)."""
    shape = os.environ.get("BIGDL_TPU_BENCH_FLASH_SHAPE")
    if shape:
        return tuple(int(x) for x in shape.split(","))
    return (4, 16, 4096, 64)


# ---------------------------------------------------------------- configs


def _cfg_resnet50():
    import jax.numpy as jnp
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    b = 64
    return (ResNet(50, class_num=1000, dataset="imagenet"),
            CrossEntropyCriterion(),
            jnp.zeros((b, 224, 224, 3), jnp.float32),
            jnp.ones((b,), jnp.int32), 0.1)


def _cfg_resnet50_bf16():
    """The MFU-target configuration: mixed precision (f32 params, bf16
    matmul/conv compute — the MXU's native dtype) at a throughput batch.
    BASELINE.md's >=45%-MFU target on v5e presumes bf16 compute; the plain
    `resnet50` config keeps f32 parity with the reference's training."""
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    b = 256
    return (ResNet(50, class_num=1000, dataset="imagenet"),
            CrossEntropyCriterion(),
            jnp.zeros((b, 224, 224, 3), jnp.float32),
            jnp.ones((b,), jnp.int32), 0.1)


def _cfg_lenet():
    import jax.numpy as jnp
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.nn import ClassNLLCriterion
    b = 512
    return (LeNet5(10), ClassNLLCriterion(),
            jnp.zeros((b, 28, 28, 1), jnp.float32),
            jnp.ones((b,), jnp.int32), 0.05)


def _cfg_inception_v1():
    import jax.numpy as jnp
    from bigdl_tpu.models.inception import Inception_v1_NoAuxClassifier
    from bigdl_tpu.nn import ClassNLLCriterion
    b = 64
    return (Inception_v1_NoAuxClassifier(1000), ClassNLLCriterion(),
            jnp.zeros((b, 224, 224, 3), jnp.float32),
            jnp.ones((b,), jnp.int32), 0.1)


def _cfg_textcnn():
    import jax.numpy as jnp
    from bigdl_tpu.models.textclassifier import TextClassifier
    from bigdl_tpu.nn import ClassNLLCriterion
    b = 128
    return (TextClassifier(20), ClassNLLCriterion(),
            jnp.zeros((b, 500, 200), jnp.float32),
            jnp.ones((b,), jnp.int32), 0.05)


def _cfg_widedeep():
    """Wide-and-deep recommender over the recsys feature layout
    (ISSUE 20): embedding-table-dominated memory, 1/N per device under a
    BIGDL_TPU_BENCH_LAYOUT fsdp×tp layout (the `embedding_tables` block
    in the memory record)."""
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu.dataset import FeatureSpec, synthetic_criteo_records
    from bigdl_tpu.models import WideDeep
    from bigdl_tpu.nn import ClassNLLCriterion
    b = 512
    spec = FeatureSpec()
    recs = list(synthetic_criteo_records(b, seed=1, spec=spec))
    inp = jnp.asarray(np.stack([spec.featurize(r).feature for r in recs]))
    tgt = jnp.asarray(np.array([r["label"] for r in recs], np.int32))
    return (WideDeep.from_spec(spec, embed_dim=64, hidden=(256, 128)),
            ClassNLLCriterion(), inp, tgt, 0.05)


def _cfg_textclassifier():
    """Token-id text classification end-to-end (ISSUE 20): a trained
    LookupTable front (embedding_row, 1/N-sharded) feeding the textcnn
    conv stack — ids in, classes out, the serving-side bucket ladder's
    training counterpart."""
    import jax.numpy as jnp
    from bigdl_tpu.models.textclassifier import TextClassifier
    from bigdl_tpu.nn import ClassNLLCriterion
    b, t, v = 128, 192, 40000
    return (TextClassifier(20, embed_dim=128, seq_len=t, vocab_size=v),
            ClassNLLCriterion(),
            jnp.zeros((b, t), jnp.int32),
            jnp.ones((b,), jnp.int32), 0.05)


def _cfg_transformer_lm():
    """Net-new long-context workload (SURVEY.md §7): decoder-only LM in
    bf16 — flash-attention + matmul path on the MXU."""
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy
    from bigdl_tpu.models.transformer_lm import TransformerLM
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    b, t = 16, 512
    return (TransformerLM(vocab_size=32000, max_len=t, d_model=512,
                          num_heads=8, num_layers=8),
            TimeDistributedCriterion(ClassNLLCriterion(), size_average=True),
            jnp.zeros((b, t), jnp.int32),
            jnp.ones((b, t), jnp.int32), 0.01)


def _cfg_transformer_lm_pipe():
    """Pipelined decoder LM: the repeated-block body partitioned over
    the mesh 'pipe' axis (parallel/pipeline.partition_pipeline) into
    pipe * BIGDL_TPU_PIPE_VIRTUAL_STAGES slices, scheduled per
    BIGDL_TPU_PIPE_SCHEDULE (gpipe default; 1f1b = table-driven
    one-forward-one-backward).  Under BIGDL_TPU_BENCH_LAYOUT=d,f,t,p,e
    with p>1 each pipe-mesh row owns 1/p of the block stack (the
    record's memory.pipeline_stages block shows the per-stage bytes
    beside pipe_schedule/pipe_virtual_stages/pipe_bubble_fraction);
    without a pipe axis the partition degrades to the sequential math
    on one chip."""
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy
    from bigdl_tpu.models.transformer_lm import TransformerLM
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.parallel import (MeshLayout, partition_pipeline,
                                    pipe_virtual_stages)
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    layout_env = os.environ.get("BIGDL_TPU_BENCH_LAYOUT")
    pipe_n = MeshLayout.parse(layout_env).pipe if layout_env else 2
    b, t = 16, 256
    model = TransformerLM(vocab_size=16000, max_len=t, d_model=512,
                          num_heads=8, num_layers=8)
    model = partition_pipeline(model, max(pipe_n, 2) * pipe_virtual_stages())
    return (model,
            TimeDistributedCriterion(ClassNLLCriterion(), size_average=True),
            jnp.zeros((b, t), jnp.int32),
            jnp.ones((b, t), jnp.int32), 0.01)


def _cfg_transformer_moe():
    """Switch-style MoE LM (parallel/expert.MoEFFN): expert tables carry
    the expert_table role, so BIGDL_TPU_BENCH_LAYOUT=d,f,t,p,e with e>1
    shards them 1/e over the 'expert' axis with all-to-all dispatch in
    the compile card's collective counts."""
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy
    from bigdl_tpu.models.transformer_lm import TransformerLM
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    b, t = 16, 256
    return (TransformerLM(vocab_size=16000, max_len=t, d_model=512,
                          num_heads=8, num_layers=4, num_experts=8,
                          expert_axis="expert"),
            TimeDistributedCriterion(ClassNLLCriterion(), size_average=True),
            jnp.zeros((b, t), jnp.int32),
            jnp.ones((b, t), jnp.int32), 0.01)


def _cfg_lstm():
    import jax.numpy as jnp
    from bigdl_tpu.models.rnn import PTBModel
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    b, t = 64, 35
    return (PTBModel(vocab_size=10000, embed_size=200, hidden_size=200),
            TimeDistributedCriterion(ClassNLLCriterion(), size_average=True),
            jnp.zeros((b, t), jnp.int32),
            jnp.ones((b, t), jnp.int32), 0.1)


CONFIGS = {"resnet50_bf16": _cfg_resnet50_bf16, "resnet50": _cfg_resnet50,
           "inception_v1": _cfg_inception_v1,
           "textcnn": _cfg_textcnn, "lstm": _cfg_lstm,
           "widedeep": _cfg_widedeep,
           "textclassifier": _cfg_textclassifier,
           "transformer_lm": _cfg_transformer_lm,
           "transformer_lm_pipe": _cfg_transformer_lm_pipe,
           "transformer_moe": _cfg_transformer_moe,
           # inference (Predictor/Evaluator path, fwd-only MFU); after the
           # fast-compiling train configs so the soft budget prefers them
           "resnet50_infer_bf16": _cfg_resnet50_bf16,
           # op bench: Pallas flash attention vs the jnp path (fwd+bwd)
           "flash_attention": _cfg_flash,
           # LAST: lenet's small-channel conv backward has the one compile
           # time nobody has taken on a v5e (docs/benchmarking.md); running
           # it last means a stall there costs only lenet, never the
           # configs after it
           "lenet": _cfg_lenet}
INFER_CONFIGS = {"resnet50_infer_bf16"}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*", default=list(CONFIGS),
                    choices=list(CONFIGS))
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for local testing "
                         "(same effect as JAX_PLATFORMS in the environment)")
    ap.add_argument("--data", action="store_true",
                    help="input-pipeline micro-mode: bench the host data "
                         "pipeline alone (decode/augment/collate, sync vs "
                         "prefetch vs MT batcher) and exit — touches no "
                         "jax backend, so a parent may run it while "
                         "another process holds the chip")
    ap.add_argument("--serve", action="store_true",
                    help="online-serving mode: closed-loop, open-loop and "
                         "bursty traffic-storm load against the serve/ "
                         "subsystem (dynamic batcher + replica pool) on "
                         "the LeNet forward — reports requests/s, latency "
                         "p50/p95/p99, batch fill, shed rate and per-"
                         "priority-class storm shed rates as ONE JSON "
                         "line")
    ap.add_argument("--fused", action="store_true",
                    help="arm the fused train-step arithmetic for this "
                         "run: multi-tensor optimizer update "
                         "(BIGDL_TPU_FUSED_UPDATE=1) and the bucketed "
                         "bf16 gradient wire (BIGDL_TPU_WIRE_BUCKET_MB=4 "
                         "unless already set) — per-config records carry "
                         "the knobs in step_knobs either way")
    ap.add_argument("--serve-clients", type=int, default=8,
                    help="--serve closed-loop concurrent clients")
    ap.add_argument("--serve-requests", type=int, default=200,
                    help="--serve total closed-loop requests")
    ap.add_argument("--replay", default=None, metavar="TRACE",
                    help="with --serve: replay a RECORDED request trace "
                         "(serve/tracefile.py recordio format — arrival "
                         "deltas, payloads, tenants, priorities, "
                         "deadlines) with open-loop pacing instead of "
                         "synthetic load, reporting per-tenant/per-"
                         "priority SLO attainment beside p50/p95/p99 "
                         "and shed-by-cause")
    ap.add_argument("--speed", type=float, default=10.0,
                    help="--replay time compression: offer the trace at "
                         "K x its recorded rate (the 10-100x regime the "
                         "scale-out layer is sized for)")
    ap.add_argument("--replay-compare", action="store_true",
                    help="with --replay: ALSO replay against a fixed "
                         "1-replica pool and report both attainments "
                         "(the autoscaled-vs-static measurement "
                         "tools/scale_smoke.py gates on)")
    ap.add_argument("--autoscale-max", type=int, default=4,
                    help="--replay pool ceiling: > 1 arms the queue-"
                         "driven autoscaler (serve/autoscale.py) for "
                         "the replayed pool; 1 = fixed pool")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="with --serve (synthetic modes): record the "
                         "offered open-loop + storm traffic into PATH "
                         "as a replayable trace")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the final JSON record to PATH and "
                         "flush every completed config incrementally to "
                         "PATH.partial.json — on a backend-init failure "
                         "the partial file still holds an error record "
                         "(platform, env knobs, traceback), so a flaky-"
                         "backend round always leaves evidence")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="emit a run trace (Chrome trace-event JSON, "
                         "bigdl_tpu.utils.telemetry) into DIR for ANY "
                         "bench mode; inspect with tools/trace_report.py "
                         "or load trace.<rank>.json in Perfetto")
    ap.add_argument("--roofline-n", type=int, default=8192)
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the virtual-mesh scaling table")
    ap.add_argument("--budget-seconds", type=float, default=1500.0,
                    help="soft wall-clock budget: remaining configs are "
                         "skipped (recorded, not failed) once exceeded so "
                         "one JSON line is always produced")
    ap.add_argument("--stall-seconds", type=float, default=300.0,
                    help="watchdog: max silent seconds between progress "
                         "marks before the run is declared hung")
    ap.add_argument("--compile-stall-seconds", type=float, default=900.0,
                    help="watchdog allowance for stages holding one long "
                         "legitimate silent call: init, compile, trace, "
                         "roofline, scaling, and timing-chain fetches "
                         "(--stall-seconds covers the remaining, "
                         "quick-transition stages)")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec (bigdl_tpu.utils.chaos), "
                         "e.g. 'fs.remote=fail*2@1;data.batch=fail@6', "
                         "'step.stall=stall*30@5' (deterministic hang the "
                         "supervisor must catch), or "
                         "'data.record=truncate@3' (corrupt-record "
                         "quarantine) — measure throughput WITH the "
                         "robustness machinery exercised; deterministic "
                         "count-based schedules")
    args = ap.parse_args(argv)
    if args.trace:
        # arm run telemetry for this process (and, via the env knob, any
        # subprocess stages): every bench mode emits trace.<rank>.json
        os.environ["BIGDL_TPU_TRACE"] = args.trace
        from bigdl_tpu.utils import telemetry
        telemetry.maybe_start()
    if args.data:
        return _data_micro_bench()
    if args.serve:
        if args.replay:
            return _serve_replay_bench(platform=args.platform,
                                       trace_path=args.replay,
                                       speed=args.speed,
                                       compare=args.replay_compare,
                                       autoscale_max=args.autoscale_max)
        return _serve_bench(platform=args.platform,
                            clients=args.serve_clients,
                            requests=args.serve_requests,
                            record_trace=args.record_trace)
    t_start = time.perf_counter()
    if args.out:
        _OUT_STATE["path"] = args.out
        _OUT_STATE["t_start"] = t_start
        _flush_partial("init")  # evidence exists before the backend is touched
    _beat("init")
    _start_watchdog(args.stall_seconds, args.compile_stall_seconds)

    if args.platform:
        import jax as _jax
        try:
            _jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass
    if args.chaos:
        from bigdl_tpu.utils import chaos as _chaos
        _chaos.install(args.chaos)
        _log(f"chaos schedules installed: {args.chaos}")
    if args.fused:
        os.environ["BIGDL_TPU_FUSED_UPDATE"] = "1"
        os.environ.setdefault("BIGDL_TPU_WIRE_BUCKET_MB", "4")
        _log("fused step arithmetic armed: FUSED_UPDATE=1, "
             f"WIRE_BUCKET_MB={os.environ['BIGDL_TPU_WIRE_BUCKET_MB']}")
    # collective-overlap XLA flags (latency-hiding scheduler + async
    # collectives): must be in LIBTPU_INIT_ARGS before backend init; inert
    # on CPU (utils/platform.py; BIGDL_TPU_OVERLAP_FLAGS=0 disables)
    from bigdl_tpu.utils.platform import enable_overlap_flags
    overlap = enable_overlap_flags()
    if overlap:
        _log(f"LIBTPU_INIT_ARGS: {overlap}")
    # (the persistent XLA cache is armed by Engine.init — utils/engine.py)
    from bigdl_tpu.utils import aot as _aot
    if _aot.cache_dir():
        _log(f"AOT executable cache: {_aot.cache_dir()} "
             "(warm configs skip XLA entirely; per-config hit/miss in "
             "each record's aot_cache)")

    jax, devices = _init_backend()

    from bigdl_tpu.utils.timing import measure_roofline

    table_peak = _table_peak_flops(devices[0])
    measured_peak = None
    _beat("roofline")
    if devices[0].platform == "tpu":
        try:
            # measure_roofline self-checks reproducibility (reps must agree)
            measured_peak = measure_roofline(args.roofline_n)
        except Exception as e:  # noqa: BLE001
            _log(f"roofline measurement failed: {type(e).__name__}: {e}")
        if measured_peak is None:
            _log("roofline measurement inconclusive (irreproducible or "
                 "non-positive differenced time)")
        elif table_peak and measured_peak > 1.25 * table_peak:
            # a glitch that survives the reps-agreement check but contradicts
            # the hardware table would silently deflate every MFU — refuse it
            _log(f"measured roofline {measured_peak/1e12:.1f} TFLOP/s "
                 f"exceeds 1.25x table peak {table_peak/1e12:.1f}; "
                 "discarding as a timing glitch")
            measured_peak = None
        else:
            _log(f"measured bf16 roofline: {measured_peak/1e12:.1f} TFLOP/s "
                 f"(table: {table_peak and table_peak/1e12} TFLOP/s)")
    peak = max(filter(None, (table_peak, measured_peak)), default=None)

    results = _STALL_STATE["results"]
    errors = _STALL_STATE["errors"]
    skipped = _STALL_STATE["skipped"]
    _STALL_STATE["meta"] = dict(args=args, table_peak=table_peak,
                                measured_peak=measured_peak, peak=peak,
                                devices=devices, t_start=t_start)
    for name in args.configs:
        elapsed = time.perf_counter() - t_start
        if (results or errors) and elapsed > args.budget_seconds:
            # something already concluded (success OR error): prefer a
            # partial-but-valid JSON line over being killed by the driver's
            # timeout mid-config
            skipped.append(name)
            _log(f"budget exceeded ({elapsed:.0f}s): skipping {name}")
            continue
        try:
            _beat(f"build:{name}")
            bench_fn = (_bench_infer if name in INFER_CONFIGS
                        else _bench_flash if name == "flash_attention"
                        else _bench_resnet50_bf16_autotune
                        if name == "resnet50_bf16"
                        else _bench_config)
            from bigdl_tpu.utils import telemetry
            with telemetry.span(f"bench:{name}", cat="bench"):
                results[name] = bench_fn(name, CONFIGS[name], peak)
        except Exception as e:  # noqa: BLE001 — recorded per config
            errors[name] = f"{type(e).__name__}: {e}"
            _log(f"config {name} failed: {errors[name]}")
        # incremental artifact: each config's record (or error) lands on
        # disk the moment it concludes — a mid-run backend loss costs the
        # remaining configs, never the completed ones
        _flush_partial(f"config:{name}")

    if not _claim_emit():
        # the watchdog declared a stall and claimed the final line (our
        # hung RPC must have resolved late); returning now would tear down
        # the interpreter and freeze the daemon thread mid-print — wait
        # for its line to land, then say nothing
        _EMIT_DONE.wait(timeout=60)
        return
    _assemble_and_print(args, results, errors, skipped, table_peak,
                        measured_peak, peak, devices, t_start)
    # the line has landed; a requested config that errored still fails the
    # run (config_errors names it)
    return 1 if errors else 0


def _assemble_and_print(args, results, errors, skipped, table_peak,
                        measured_peak, peak, devices, t_start, stall=None):
    primary = (results.get("resnet50_bf16") or results.get("resnet50") or
               # prefer any TRAIN config as the headline; infer/op-bench last
               next((r for k, r in results.items()
                     if k not in INFER_CONFIGS
                     and r.get("mode") != "op"), None) or
               next(iter(results.values()), None))
    if primary is None:
        _fail("; ".join(f"{k}: {v}" for k, v in errors.items()) or
              (stall and f"stalled in {stall['stage']}") or
              "no configs ran", "bench")

    primary_is_train = primary.get("mode") != "inference"
    mfu = primary.get("mfu")
    if mfu is not None and primary_is_train and \
            primary["name"].startswith("resnet50"):
        # the >=45%-MFU target is the ResNet-50 TRAIN north star (BASELINE.md)
        vs_baseline = round(mfu / MFU_TARGET, 3)
    else:
        vs_baseline = None  # no real published baseline exists (BASELINE.md)
    mode = ("op" if primary.get("mode") == "op"
            else "train" if primary_is_train else "infer")
    # config names may already carry the mode token (resnet50_infer_bf16)
    metric_base = primary["name"].replace("_infer", "")
    out = {"metric": f"{metric_base}_{mode}_images_per_sec_per_chip",
           "value": primary["images_per_sec"], "unit": "images/sec",
           "vs_baseline": vs_baseline,
           "mfu": mfu, "mfu_target": MFU_TARGET,
           "model_flops_per_step": primary["model_flops_per_step"],
           "peak_flops_table": table_peak,
           "peak_flops_measured_roofline": measured_peak,
           "peak_flops_used": peak,
           "records_per_sec_e2e": primary.get("records_per_sec_e2e"),
           "data_wait_fraction": primary.get("data_wait_fraction"),
           "device": str(devices[0]),
           "device_kind": getattr(devices[0], "device_kind", "unknown"),
           "configs": results}
    if errors:
        out["config_errors"] = errors
    if skipped:
        out["configs_skipped_budget"] = skipped
    if stall:
        out["stall"] = stall
    if not args.no_scaling and not stall:
        # headroom for the scaling subprocess's own timeout so the total
        # stays inside the budget the driver is assumed to allow
        if time.perf_counter() - t_start < args.budget_seconds - \
                _SCALING_TIMEOUT:
            _beat("scaling")
            out["scaling_virtual_cpu"] = _scaling_table()
        else:
            out["scaling_skipped_budget"] = True
            _log("budget: skipping virtual-mesh scaling table")
    _flush_trace()
    _write_out(out)
    print(json.dumps(out))
    sys.stdout.flush()
    _EMIT_DONE.set()


def _data_micro_bench(n_images=512, batch=64, hw=48):
    """`--data`: the input pipeline alone, on the host CPU — no jax import,
    no backend.  A synthetic image corpus runs the canonical
    augment chain (crop/flip/normalize/to-sample/batch) three ways: the
    sequential chain, the chain behind the background prefetcher (the
    train-loop default), and the MT batcher (parallel augment feeding
    collation).  Prints ONE JSON line."""
    import numpy as np

    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.image import (HFlip, ImgNormalizer, ImgRdmCropper,
                                         ImgToSample, LabeledImage,
                                         MTImageToBatch)
    from bigdl_tpu.dataset.prefetch import PrefetchIterator, prefetch_depth

    rng = np.random.default_rng(0)
    records = [LabeledImage(
        rng.standard_normal((hw, hw, 3)).astype(np.float32),
        float(i % 10)) for i in range(n_images)]
    aug = (ImgRdmCropper(hw - 8, hw - 8) >> HFlip() >>
           ImgNormalizer([0.5, 0.5, 0.5], [0.25, 0.25, 0.25]))
    chain = aug >> ImgToSample() >> SampleToMiniBatch(batch, drop_last=True)

    from bigdl_tpu.utils import telemetry

    def timed(run, label):
        run()  # warmup (allocator, pools)
        with telemetry.span(f"bench:data:{label}", cat="bench"):
            t0 = time.perf_counter()
            count = run()
            return round(count / (time.perf_counter() - t0), 1)

    def run_sync():
        return sum(b.size() for b in chain(iter(records)))

    def run_prefetch():
        with PrefetchIterator(chain(iter(records)), depth=2) as pipe:
            return sum(b.size() for b in pipe)

    mt = MTImageToBatch(batch, transformer=aug, drop_last=True)

    def run_mt():
        return sum(b.size() for b in mt(iter(records)))

    sync_rps = timed(run_sync, "sync")
    prefetch_rps = timed(run_prefetch, "prefetch")
    mt_rps = timed(run_mt, "mt_batcher")
    print(json.dumps({
        "metric": "input_pipeline_records_per_sec", "value": mt_rps,
        "unit": "records/s", "vs_baseline": round(mt_rps / sync_rps, 3),
        "mode": "data-micro",
        "sync_records_per_sec": sync_rps,
        "prefetch_records_per_sec": prefetch_rps,
        "mt_batcher_records_per_sec": mt_rps,
        "prefetch_depth": prefetch_depth(),
        "images": n_images, "batch_size": batch,
        "image_hw": hw, "num_threads": mt.num_threads}))
    sys.stdout.flush()
    _flush_trace()
    _EMIT_DONE.set()


def _percentiles(latencies):
    """p50/p95/p99 (ms) from a list of per-request latency seconds."""
    if not latencies:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    xs = sorted(latencies)
    pick = lambda q: xs[min(int(q * (len(xs) - 1) + 0.5), len(xs) - 1)]
    return {"p50_ms": round(pick(0.50) * 1e3, 2),
            "p95_ms": round(pick(0.95) * 1e3, 2),
            "p99_ms": round(pick(0.99) * 1e3, 2)}


def _replay_model_for(header, model_builder=None):
    """A servable model matching the trace's recorded sample shape: the
    caller's builder, LeNet for image-shaped traces, a small Linear head
    for flat feature rows — a trace whose shape matches nothing is a
    typed error, not a garbage benchmark."""
    import jax
    import numpy as np

    if model_builder is not None:
        return model_builder()
    shape = tuple(header.get("sample_shape") or ())
    dtype = header.get("sample_dtype", "float32")
    if shape == (28, 28, 1):
        from bigdl_tpu.models.lenet import LeNet5
        return (LeNet5(10).build(jax.random.key(0)),
                np.zeros(shape, np.float32))
    if len(shape) == 1 and shape[0] >= 1:
        import bigdl_tpu.nn as nn
        d = int(shape[0])
        model = nn.Sequential().add(
            nn.Linear(d, max(2, min(d, 8)))).build(jax.random.key(0))
        return model, np.zeros(shape, np.dtype(dtype))
    raise SystemExit(
        f"bench --replay: no builtin model serves sample shape {shape} "
        "(record traces against lenet-shaped or flat-feature models, or "
        "extend _replay_model_for)")


def _serve_replay_bench(platform=None, trace_path=None, speed=10.0,
                        compare=False, autoscale_max=4,
                        model_builder=None):
    """`--serve --replay TRACE --speed K`: recorded-traffic replay.

    Replays a recorded request stream (serve/tracefile.py — arrival
    deltas, payloads, tenants, priorities, deadlines) with OPEN-LOOP
    pacing at K x the recorded rate against the serving stack, and
    reports **per-tenant / per-priority SLO attainment** (fraction of
    offered requests answered within their own deadline) beside
    p50/p95/p99, shed-by-cause (overload / timeout / a separate real-
    `errors` bucket), the autoscaler's decisions, and the AOT ledger
    delta across the scale-up window (the zero-fresh-lowers receipt).
    `--replay-compare` additionally replays the same trace against a
    FIXED 1-replica pool — the elasticity win as one JSON record."""
    import numpy as np

    if platform:
        import jax as _jax
        try:
            _jax.config.update("jax_platforms", platform)
        except RuntimeError:
            pass
    import jax

    from bigdl_tpu.serve import (InferenceServer, read_trace, replay,
                                 resolve_outcomes, slo_report)
    from bigdl_tpu.utils import aot as aot_mod
    from bigdl_tpu.utils.engine import Engine

    _beat("init")
    Engine.reset()
    Engine.init()
    header, events = read_trace(trace_path)
    if not events:
        _fail(ValueError(f"trace {trace_path} holds zero events"),
              "serve-replay")
    model, sample = _replay_model_for(header, model_builder)

    def run_pool(tag, ceiling):
        _beat(f"serve:replay:{tag}")
        server = InferenceServer(
            model, example=sample, replicas=1,
            autoscale_min=1, autoscale_max=ceiling)
        with server:
            aot0 = aot_mod.stats() if aot_mod.enabled() else None

            def submit(e):
                return server.submit(e.payload, deadline_ms=e.deadline_ms,
                                     tenant=e.tenant, priority=e.priority)

            outcomes = replay(events, submit, speed=speed, progress=_beat)
            resolve_outcomes(outcomes)
            rec = slo_report(outcomes)
            stats = server.stats()
        rec["pool"] = {"autoscale_max": ceiling,
                       "replicas_final": stats["replicas"]}
        if "autoscale" in stats:
            rec["autoscale"] = stats["autoscale"]
        if aot0 is not None:
            rec["aot_delta"] = _aot_delta(aot0)
        return rec

    autoscaled = autoscale_max and autoscale_max > 1
    primary = run_pool("autoscaled" if autoscaled else "fixed",
                       autoscale_max if autoscaled else 0)
    out = {"metric": "serve_replay_slo_attainment",
           "value": primary["attainment"], "unit": "fraction",
           "vs_baseline": None, "mode": "serve-replay",
           "trace": trace_path, "speed": speed,
           "events": len(events),
           "recorded_duration_s": header.get("duration_s"),
           "model": type(model).__name__,
           "replay": primary,
           "device": str(jax.devices()[0])}
    if compare:
        fixed = run_pool("fixed-1", 0)
        out["fixed"] = fixed
        if primary["attainment"] is not None and \
                fixed["attainment"] is not None:
            out["attainment_gain"] = round(
                primary["attainment"] - fixed["attainment"], 4)
    _flush_trace()
    print(json.dumps(out))
    sys.stdout.flush()
    _EMIT_DONE.set()
    return out


def _serve_bench(platform=None, clients=8, requests=200, model_builder=None,
                 record_trace=None):
    """`--serve`: online-serving load bench (bigdl_tpu.serve).

    Two load shapes against the LeNet forward, ONE JSON line:
      closed loop — `clients` threads issue back-to-back requests (the
        batcher's coalescing sets throughput; nothing is shed), reporting
        requests/s + latency p50/p95/p99 + realized batch fill;
      open loop — requests arrive at a fixed rate ~2x the closed-loop
        throughput against a deliberately small queue + tight deadline,
        so admission (ServerOverloaded) and deadline (RequestTimeout)
        shedding actually engage — the shed rate and served-tail latency
        are the report;
      traffic storm — bursty arrivals (back-to-back bursts, idle gaps)
        across three priority classes against a tiny queue, reporting
        shed rate BY CLASS: the priority-aware-admission measurement
        (higher classes evict lower ones from a full queue,
        serve/batcher.py).  The record lands alongside the e2e training
        records in the bench JSON family."""
    import numpy as np

    if platform:
        import jax as _jax
        try:
            _jax.config.update("jax_platforms", platform)
        except RuntimeError:
            pass
    import jax

    from bigdl_tpu.serve import (InferenceServer, RequestTimeout,
                                 ServerOverloaded)
    from bigdl_tpu.utils.engine import Engine

    _beat("init")
    Engine.reset()
    Engine.init()
    if model_builder is None:
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(10).build(jax.random.key(0))
        sample = np.zeros((28, 28, 1), np.float32)
    else:
        model, sample = model_builder()
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=sample.shape).astype(np.float32)
          for _ in range(16)]

    # -- closed loop ----------------------------------------------------
    latencies, errors = [], []
    lock = threading.Lock()
    per_client = max(requests // max(clients, 1), 1)
    with InferenceServer(model, example=sample) as server:
        _beat("serve:closed")

        def client(cid):
            for i in range(per_client):
                t0 = time.perf_counter()
                try:
                    server.predict(xs[(cid + i) % len(xs)], timeout=120)
                    with lock:
                        latencies.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — recorded
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        closed_wall = time.perf_counter() - t0
        closed_stats = server.stats()
    served = len(latencies)
    closed_rps = round(served / closed_wall, 1) if closed_wall > 0 else 0.0
    closed = {"clients": clients, "requests": served,
              "requests_per_sec": closed_rps,
              **_percentiles(latencies),
              "batches": closed_stats["batches"],
              "batch_fill": closed_stats["batch_fill"],
              "errors": errors[:5]}

    # -- open loop ------------------------------------------------------
    _beat("serve:open")
    target_rps = max(closed_rps * 2.0, 10.0)
    interval = 1.0 / target_rps
    n_open = min(max(served, 20), int(target_rps * 2) or 20)
    open_lat, handles = [], []
    shed_overload = 0
    deadline_ms = max(_percentiles(latencies)["p95_ms"] or 50.0, 5.0)
    with InferenceServer(model, queue_limit=16,
                         deadline_ms=deadline_ms,
                         example=sample) as server:
        next_t = time.perf_counter()
        for i in range(n_open):
            now = time.perf_counter()
            if now < next_t:
                time.sleep(next_t - now)
            next_t += interval
            try:
                handles.append((time.perf_counter(),
                                server.submit(xs[i % len(xs)])))
            except ServerOverloaded:
                shed_overload += 1
        shed_timeout = 0
        open_errors, open_error_samples = 0, []
        for t0, h in handles:
            try:
                h.result(120)
                open_lat.append(time.perf_counter() - t0)
            except RequestTimeout:
                shed_timeout += 1
            except ServerOverloaded:  # evicted from the queue post-admit
                shed_overload += 1
            except Exception as e:  # noqa: BLE001 — a REAL failure, not
                # intentional shedding: reported in its own bucket so a
                # broken replica can never masquerade as load shedding
                open_errors += 1
                if len(open_error_samples) < 5:
                    open_error_samples.append(
                        f"{type(e).__name__}: {e}")
        open_stats = server.stats()
    shed = shed_overload + shed_timeout
    open_loop = {"offered_rps": round(target_rps, 1),
                 "offered": n_open, "served": len(open_lat),
                 "deadline_ms": round(deadline_ms, 1),
                 "shed_overload": shed_overload,
                 "shed_timeout": shed_timeout,
                 "errors": open_errors,
                 "shed_rate": round(shed / n_open, 4) if n_open else 0.0,
                 **_percentiles(open_lat),
                 "batch_fill": open_stats["batch_fill"]}
    if open_error_samples:
        open_loop["error_samples"] = open_error_samples

    # -- traffic storm --------------------------------------------------
    # bursty open loop against a deliberately tiny queue, requests spread
    # over three priority classes (2 = interactive, 1 = standard, 0 =
    # batch/best-effort): each burst slams `burst_n` back-to-back
    # arrivals (no pacing) then goes idle — the diurnal-peak shape at
    # 10-100x replay speed.  Under pressure the batcher sheds the
    # LOWEST-priority queued request first (priority eviction,
    # serve/batcher.py), so the report is shed rate BY CLASS: the
    # priority-awareness measurement, not just a scalar shed rate.
    _beat("serve:storm")
    bursts = 4
    burst_n = min(max(requests // 4, 12), 96)
    by_prio = {p: {"offered": 0, "served": 0, "shed_overload": 0,
                   "shed_timeout": 0, "errors": 0} for p in (0, 1, 2)}
    storm_lat = []
    with InferenceServer(model, queue_limit=8,
                         deadline_ms=max(deadline_ms, 20.0),
                         example=sample) as server:
        if record_trace:
            # capture the storm's offered stream (the bursty diurnal
            # shape worth replaying) as a serve/tracefile.py trace —
            # written when the server stops
            server.record_trace(record_trace)
        pending = []
        for b in range(bursts):
            for i in range(burst_n):
                p = (0, 1, 2)[i % 3]
                by_prio[p]["offered"] += 1
                try:
                    pending.append(
                        (p, time.perf_counter(),
                         server.submit(xs[i % len(xs)], priority=p,
                                       tenant=f"class{p}")))
                except ServerOverloaded:
                    by_prio[p]["shed_overload"] += 1
            time.sleep(0.05)  # inter-burst idle gap (the diurnal trough)
        for p, t0, h in pending:
            try:
                h.result(120)
                by_prio[p]["served"] += 1
                storm_lat.append(time.perf_counter() - t0)
            except ServerOverloaded:   # evicted for a higher class
                by_prio[p]["shed_overload"] += 1
            except RequestTimeout:     # deadline passed while queued
                by_prio[p]["shed_timeout"] += 1
            except Exception:  # noqa: BLE001 — real failures get their
                # own bucket, never reported as intentional shedding
                by_prio[p]["errors"] += 1
        storm_stats = server.stats()
    for p, rec in by_prio.items():
        sheds = rec["shed_overload"] + rec["shed_timeout"]
        rec["shed_rate"] = round(sheds / rec["offered"], 4) \
            if rec["offered"] else 0.0
    offered = sum(r["offered"] for r in by_prio.values())
    served = sum(r["served"] for r in by_prio.values())
    storm = {"bursts": bursts, "burst_n": burst_n,
             "offered": offered, "served": served,
             "errors": sum(r["errors"] for r in by_prio.values()),
             "shed_rate": round(1.0 - served / offered, 4) if offered
             else 0.0,
             "by_priority": {str(p): by_prio[p] for p in sorted(by_prio)},
             "shed_priority_evictions": storm_stats["shed_priority"],
             **_percentiles(storm_lat)}

    out = {"metric": "serve_requests_per_sec", "value": closed_rps,
           "unit": "req/s", "vs_baseline": None, "mode": "serve",
           "model": type(model).__name__,
           "max_batch": server.max_batch,
           "buckets": list(server.batcher.buckets),
           "replicas": server.replicas,
           "closed_loop": closed, "open_loop": open_loop,
           "storm": storm,
           "device": str(jax.devices()[0])}
    if record_trace:
        out["recorded_trace"] = record_trace
    _flush_trace()
    print(json.dumps(out))
    sys.stdout.flush()
    _EMIT_DONE.set()
    return out


def _start_watchdog(stall_seconds, compile_stall_seconds):
    """Arm the shared supervision subsystem (bigdl_tpu.utils.supervisor)
    as bench's stall watchdog: stages known to hold long silent device
    calls (_LONG_STAGES) get the larger allowance, everything else
    `stall_seconds`; a missed deadline runs _on_bench_stall, which prints
    whatever is complete and exits.  Partial results are a valid JSON
    line; an empty run becomes a bench_error naming the stage.  The
    supervisor is also installed as the process default, so
    utils/timing's measure loops heartbeat it per rep."""
    from bigdl_tpu.utils import supervisor as _supervision
    sup = _get_sup()
    sup.set_deadlines(default=stall_seconds,
                      phases={s: compile_stall_seconds
                              for s in _LONG_STAGES})
    _supervision.set_active(sup)
    sup.start()


def _watchdog_emit(stage, idle, limit):
    """Emit partial results (or a bench_error) after a declared stall; the
    caller owns the final os._exit on any raise that escapes this."""
    _log(f"WATCHDOG: no progress for {idle:.0f}s in stage "
         f"'{stage}' (limit {limit:.0f}s) — lost-RPC hang; "
         "emitting partial results")
    st = _STALL_STATE
    if st["meta"] is None or not st["results"]:
        prior = "; ".join(f"{k}: {v}" for k, v in st["errors"].items())
        _fail(TimeoutError(
            f"no progress for {idle:.0f}s in {stage}" +
            (f" (earlier config errors: {prior})" if prior
             else "")), f"stall:{stage}")
    # snapshot the live dicts (atomic C-level copies under the
    # GIL): the main thread's hung RPC can resolve late and
    # keep inserting while json.dumps iterates
    results = dict(st["results"])
    errors = dict(st["errors"])
    skipped = list(st["skipped"])
    stall = {"stage": stage, "idle_seconds": round(idle, 1)}
    try:
        attempted = set(results) | set(errors) | set(skipped)
        cur = stage.split(":", 1)[-1]
        stall["configs_not_attempted"] = [
            c for c in st["meta"]["args"].configs
            if c not in attempted and c != cur]
        _assemble_and_print(results=results, errors=errors,
                            skipped=skipped, stall=stall,
                            **st["meta"])
    except Exception as e:  # noqa: BLE001 — line must land
        _fail(f"stall in {stage}; emit of partial results "
              f"failed: {type(e).__name__}: {e}",
              f"stall:{stage}")
    # partial results are a valid, self-describing JSON line
    # (the "stall" field names the hung stage) — exit 0 like
    # the budget-skip path so the driver records it
    os._exit(0)


def _scaling_table():
    """BASELINE.md's 'linear 8->64' target, simulated: run the scaling tool
    (collective introspection + 1-vs-8-device virtual throughput) in a CPU
    subprocess so it cannot disturb this process's TPU backend."""
    import subprocess
    # --no-strategies: the per-strategy collective signatures add minutes
    # of compiles and are pinned by tests/test_scaling.py anyway — the
    # bench's scaling table stays within _SCALING_TIMEOUT
    cmd = [sys.executable, "-m", "bigdl_tpu.tools.scaling", "--devices", "8",
           "--no-strategies"]
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               filter(None, [repo_dir, os.environ.get("PYTHONPATH")]))}
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=_SCALING_TIMEOUT, env=env)
        line = [l for l in res.stdout.splitlines() if l.startswith("{")]
        if res.returncode == 0 and line:
            return json.loads(line[-1])
        return {"error": (res.stderr or "no output")[-500:]}
    except Exception as e:  # noqa: BLE001 — scaling is best-effort metadata
        return {"error": f"{type(e).__name__}: {e}"}


if __name__ == "__main__":
    # --data/--serve return their record (tests read it); the config path
    # returns its exit code
    _rc = main()
    sys.exit(_rc if isinstance(_rc, int) else 0)
