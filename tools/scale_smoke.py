#!/usr/bin/env python
"""Serving scale-out smoke: record traffic, replay it 10x, prove elasticity.

The end-to-end drill for the scale-out layer (serve/autoscale.py,
serve/router.py, serve/tracefile.py — docs/serving.md "Scale-out"), on
the 8-virtual-CPU-device mesh, exit-coded, ONE JSON line:

  1. **record** — a real request stream (3 tenants x 3 priority
     classes, per-request deadlines, real arrival pacing) is captured
     through ``InferenceServer.record_trace`` into the recordio trace
     format and read back (CRC-verified).
  2. **route + bit-match** — a ``TopologyRouter`` places replicas on
     disjoint device subsets; routed answers must BIT-match bulk
     ``Predictor.predict``.
  3. **replay fixed** — the trace replays at ``--speed`` (>= 10x) with
     open-loop pacing against a FIXED 1-replica pool while a
     deterministic chaos stall (``serve.batch=stall*S@...``) pins the
     per-batch service time; per-tenant SLO attainment is measured.
  4. **replay autoscaled** — same trace, same stall, against an
     autoscaled router pool (min 1, max 4).  The controller must GROW
     the pool (scale_ups >= 1), attainment must be STRICTLY higher
     than the fixed pool's, the scale-up window must perform ZERO
     fresh lowers (``aot`` ledger — spawn is cache reads), and after
     the traffic drains the pool must SHRINK back to min.

A CPU drill; safe anywhere
(tiny model, seconds of wall clock, no accelerator needed).
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

#: deterministic per-batch service time injected by the chaos stall —
#: the capacity lever that makes fixed-vs-autoscaled attainment a
#: schedule property instead of a CPU-load coin flip
SERVICE_STALL_S = 0.03
STALL_COUNTS = ",".join(str(i) for i in range(1, 2001))


def _model(jax):
    import bigdl_tpu.nn as nn
    return nn.Sequential().add(nn.Linear(8, 8)).add(nn.ReLU()) \
        .add(nn.Linear(8, 4)).build(jax.random.key(0))


def _record_trace(model, xs, path, n_events, gap_s, deadline_ms):
    """Capture a real offered stream (tenants x priorities, real
    pacing) through the server's admission-path recorder."""
    from bigdl_tpu.serve import InferenceServer
    server = InferenceServer(model, example=xs[0], max_batch=4,
                             queue_limit=512).start()
    server.record_trace(path)
    handles = []
    for i in range(n_events):
        p = (2, 1, 0)[i % 3]
        handles.append(server.submit(
            xs[i % len(xs)], tenant=f"tenant{i % 3}", priority=p,
            deadline_ms=deadline_ms))
        time.sleep(gap_s)
    for h in handles:
        h.result(30)
    n = len(server.stop_trace())
    server.stop()
    return n


def _bit_match(model, xs):
    """Routed answers vs bulk Predictor.predict — byte-for-byte."""
    import numpy as np

    from bigdl_tpu.optim import Predictor
    from bigdl_tpu.serve import TopologyRouter
    with TopologyRouter(model, replicas=2, example=xs[0],
                        max_batch=4) as router:
        handles = [router.submit(x) for x in xs]
        got = np.stack([h.result(30) for h in handles])
    ref = np.asarray(Predictor(model).predict(np.stack(xs)))
    return bool(np.array_equal(got, ref))


def _replay(pool, events, speed):
    from bigdl_tpu.serve import replay, resolve_outcomes, slo_report

    def submit(e):
        return pool.submit(e.payload, deadline_ms=e.deadline_ms,
                           tenant=e.tenant, priority=e.priority)

    outcomes = replay(events, submit, speed=speed)
    resolve_outcomes(outcomes, timeout=60)
    return slo_report(outcomes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--speed", type=float, default=10.0)
    ap.add_argument("--events", type=int, default=150)
    args = ap.parse_args(argv)

    os.environ.setdefault("BIGDL_TPU_AOT_CACHE",
                          tempfile.mkdtemp(prefix="scale_smoke_aot_"))
    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass
        from bigdl_tpu.utils.platform import force_cpu
        force_cpu(8)
    import jax
    import numpy as np

    from bigdl_tpu import Engine
    from bigdl_tpu.serve import InferenceServer, TopologyRouter, read_trace
    from bigdl_tpu.utils import aot, chaos

    Engine.reset()
    Engine.init()
    model = _model(jax)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(8,)).astype(np.float32) for _ in range(16)]
    trace_path = os.path.join(tempfile.mkdtemp(prefix="scale_smoke_"),
                              "mini_trace.rec")

    rec = {"metric": "scale_smoke", "speed": args.speed}
    t0 = time.perf_counter()

    # 1. record + read back (CRC-framed recordio)
    rec["recorded"] = _record_trace(model, xs, trace_path,
                                    n_events=args.events, gap_s=0.015,
                                    deadline_ms=300.0)
    header, events = read_trace(trace_path)
    rec["trace"] = {"path": trace_path, "events": len(events),
                    "recorded_duration_s": header["duration_s"]}

    # 2. topology routing bit-match
    rec["bit_match"] = _bit_match(model, xs)

    # 3. fixed 1-replica pool under the pinned service time
    with chaos.scoped(f"serve.batch=stall*{SERVICE_STALL_S}"
                      f"@{STALL_COUNTS}"):
        with InferenceServer(model, example=xs[0], max_batch=4,
                             queue_limit=512) as fixed:
            fixed_rep = _replay(fixed, events, args.speed)
    rec["fixed"] = {"attainment": fixed_rep["attainment"],
                    "served": fixed_rep["served"],
                    "shed": fixed_rep["shed"],
                    "p99_ms": fixed_rep["p99_ms"]}

    # 4. autoscaled router pool, same trace, same service time
    with chaos.scoped(f"serve.batch=stall*{SERVICE_STALL_S}"
                      f"@{STALL_COUNTS}"):
        router = TopologyRouter(
            model, replicas=1, example=xs[0], max_batch=4,
            queue_limit=512, prewarm=True,
            autoscale_min=1, autoscale_max=4,
            autoscale_target_wait_ms=40.0, autoscale_up_polls=1,
            autoscale_cooldown_s=0.03, autoscale_idle_s=0.3,
            autoscale_poll_s=0.01).start()
        aot0 = aot.stats()   # after start + prewarm: the scale-up window
        auto_rep = _replay(router, events, args.speed)
        aot1 = aot.stats()
        scale_stats = router.stats()["autoscale"]
        replicas_peak = max([scale_stats["replicas"]] +
                            [e["to"] for e in scale_stats["events"]])
        # drain + idle: the controller must hand the capacity back
        deadline = time.monotonic() + 10.0
        while router.replicas > 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        final_stats = router.stats()
        router.stop()
    aot_delta = {k: int(aot1[k] - aot0[k])
                 for k in ("hits", "misses", "lowers", "compiles")}
    rec["autoscaled"] = {
        "attainment": auto_rep["attainment"],
        "served": auto_rep["served"], "shed": auto_rep["shed"],
        "p99_ms": auto_rep["p99_ms"],
        "per_tenant": {t: b["attainment"]
                       for t, b in auto_rep["per_tenant"].items()},
        "per_priority": {p: b["attainment"]
                         for p, b in auto_rep["per_priority"].items()},
        "scale_ups": final_stats["autoscale"]["scale_ups"],
        "scale_downs": final_stats["autoscale"]["scale_downs"],
        "replicas_peak": replicas_peak,
        "replicas_final": final_stats["replicas"],
        "aot_scaleup_delta": aot_delta}

    checks = {
        "recorded_trace_roundtrips": rec["recorded"] == len(events) > 0,
        "routed_answers_bit_match": rec["bit_match"],
        "autoscaler_grew": rec["autoscaled"]["scale_ups"] >= 1
        and replicas_peak > 1,
        "autoscaler_shrank_back": rec["autoscaled"]["replicas_final"] == 1,
        "attainment_strictly_higher":
            auto_rep["attainment"] > fixed_rep["attainment"],
        "zero_fresh_lowers_on_scaleup": aot_delta["lowers"] == 0
        and aot_delta["misses"] == 0,
    }
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
