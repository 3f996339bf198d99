#!/usr/bin/env python
"""Serving control-plane smoke: prove the self-healing loop end-to-end
(bigdl_tpu/serve/control.py — docs/serving.md "Self-healing &
resilience").

Two chaos drills, exit-coded, ONE JSON line:

  drill 1 — restart under traffic.  ``serve.replica@0=wedge*W@2`` wedges
    replica 0 uninterruptibly on its 2nd batch while closed-loop clients
    keep submitting.  The replica monitor must detect the heartbeat
    silence (``replica_lost``), condemn the wedged thread, respawn a
    replacement (bucket ladder re-warmed), and — the contract — ZERO
    accepted requests may be dropped or answered incorrectly: every
    response is bit-compared against per-sample bulk
    ``Predictor.predict``.  The restart must be counted in ``stats()``
    and the server must stay healthy.

  drill 2 — bad canary never promotes.  ``swap(canary_fraction=f)``
    installs fresh weights as a canary while ``serve.canary=stall*S@...``
    inflates exactly the canary's batch latency.  The rolling p99
    comparator must auto-roll it back with a typed ``CanaryRejected``
    reason in ``stats()``, the canary must never have served more than
    its fraction of batches (+1 rounding), and the incumbent version
    must still be live.

Prints ONE JSON line::

    {"metric": "resilience_smoke", "ok": true,
     "restart": {...}, "canary": {...}}

A CPU drill; safe anywhere
(tiny model, seconds of wall clock, 8 virtual CPU devices).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _drill_restart(model, x, ref, wedge_s, replica_lost):
    """Wedge replica 0 under closed-loop traffic; assert zero loss,
    bit-match, restart counted."""
    import numpy as np

    from bigdl_tpu.serve import InferenceServer
    from bigdl_tpu.utils import chaos

    results, errors = {}, []
    lock = threading.Lock()
    with chaos.scoped(f"serve.replica@0=wedge*{wedge_s}@2"):
        server = InferenceServer(model, max_batch=4, max_wait_ms=5,
                                 queue_limit=len(x) * 2, example=x[0],
                                 replica_lost=replica_lost,
                                 restart_backoff=0.02).start()

        def client(i):
            try:
                h = server.submit(x[i])
                out = h.result(60)
                with lock:
                    results[i] = out
            except Exception as e:  # noqa: BLE001 — recorded, fails smoke
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(x))]
        for t in threads:
            t.start()
            time.sleep(0.015)  # sustained trickle spanning the wedge
        for t in threads:
            t.join()
        # give the monitor a beat to finish any in-flight respawn
        deadline = time.monotonic() + 5.0
        while server.stats()["restarts"] < 1 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        stats = server.stats()
        server.stop()
    mismatches = sum(
        1 for i in results if not np.array_equal(results[i], ref[i]))
    rec = {"requests": len(x), "served": len(results),
           "errors": errors[:5], "mismatched": mismatches,
           "restarts": stats["restarts"], "healthy": stats["healthy"],
           "monitor": stats.get("replica_monitor", {}).get("lost", 0)}
    rec["ok"] = bool(len(results) == len(x) and not errors
                     and mismatches == 0 and stats["restarts"] >= 1
                     and stats["healthy"])
    return rec


def _drill_canary(model, model_b, x, stall_s, fraction):
    """Latency-inflate the canary; assert auto-rollback, typed reason,
    fraction bound, incumbent still live."""
    from bigdl_tpu.serve import InferenceServer
    from bigdl_tpu.utils import chaos

    counts = ",".join(str(i) for i in range(1, 17))
    with chaos.scoped(f"serve.canary=stall*{stall_s}@{counts}"):
        server = InferenceServer(model, max_batch=2, max_wait_ms=1,
                                 queue_limit=len(x) * 2, example=x[0],
                                 canary_min_batches=4).start()
        base_version = server.stats()["version"]
        server.swap(model_b, canary_fraction=fraction)
        for i in range(60):
            server.predict(x[i % len(x)], timeout=60)
            if (server.stats().get("canary") or {}).get("state") \
                    != "running":
                break
        stats = server.stats()
        server.stop()
    c = stats.get("canary") or {}
    rec = {"state": c.get("state"), "reason_type": c.get("reason_type"),
           "reason": c.get("reason"), "routed": c.get("routed"),
           "total": c.get("total"), "fraction": fraction,
           "live_version": stats["version"],
           "rollbacks": stats["canary_rollbacks"]}
    rec["ok"] = bool(
        c.get("state") == "rolled_back"
        and c.get("reason_type") == "CanaryRejected"
        and c.get("routed", 1e9) <= fraction * c.get("total", 0) + 1
        and stats["version"] == base_version
        and stats["canary_rollbacks"] == 1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--requests", type=int, default=24,
                    help="closed-loop requests in the restart drill")
    ap.add_argument("--wedge-seconds", type=float, default=1.0)
    ap.add_argument("--replica-lost", type=float, default=0.25,
                    help="replica heartbeat-silence deadline, seconds")
    ap.add_argument("--canary-stall", type=float, default=0.3,
                    help="injected canary latency per batch, seconds")
    ap.add_argument("--canary-fraction", type=float, default=0.25)
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    out = {"metric": "resilience_smoke", "ok": False}
    try:
        from bigdl_tpu.utils.platform import force_cpu
        # 8 virtual devices = the test mesh: every forward pads to the
        # same row multiple, so serve answers bit-match the bulk oracle
        force_cpu(8)
        import jax
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu.optim import Predictor
        from bigdl_tpu.utils.engine import Engine

        Engine.init()
        model = nn.Sequential().add(nn.Linear(4, 3)).build(
            jax.random.key(0))
        model_b = nn.Sequential().add(nn.Linear(4, 3)).build(
            jax.random.key(9))
        x = np.random.default_rng(0).normal(
            size=(args.requests, 4)).astype(np.float32)
        ref = np.stack([Predictor(model).predict(x[i:i + 1])[0]
                        for i in range(len(x))])

        out["restart"] = _drill_restart(model, x, ref,
                                        args.wedge_seconds,
                                        args.replica_lost)
        out["canary"] = _drill_canary(model, model_b, x,
                                      args.canary_stall,
                                      args.canary_fraction)
        out["ok"] = bool(out["restart"]["ok"] and out["canary"]["ok"])
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        out["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))
    sys.stdout.flush()
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
