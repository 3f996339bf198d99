#!/usr/bin/env python
"""Serving smoke: prove the online-serving subsystem end-to-end on any
backend (bigdl_tpu.serve — docs/serving.md).

Spins up an InferenceServer on LeNet, fires concurrent single-sample
requests from many client threads, and asserts the serving contract:

  - real coalescing: the requests were answered in strictly fewer device
    batches than requests (non-zero batch fill beyond singletons);
  - a latency bound: p95 under --p95-bound seconds (post-warmup steady
    state — startup warmup pre-compiles every bucket shape);
  - a mid-traffic hot swap completes with zero dropped requests;
  - clean shutdown (no leaked replica threads).

Prints ONE JSON line:

    {"metric": "serve_smoke", "ok": true, "requests": N, "batches": B,
     "batch_fill": f, "p95_ms": x, "swap_version": 2, ...}

A CPU drill of the serving machinery; safe anywhere (tiny model,
seconds of wall clock).
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests-per-client", type=int, default=8)
    ap.add_argument("--p95-bound", type=float, default=2.0,
                    help="steady-state p95 latency bound, seconds "
                         "(generous: CPU smoke, not a perf target)")
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    import jax
    import numpy as np

    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.serve import InferenceServer
    from bigdl_tpu.utils.engine import Engine

    out = {"metric": "serve_smoke", "ok": False}
    try:
        Engine.init()
        model = LeNet5(10).build(jax.random.key(0))
        sample = np.zeros((28, 28, 1), np.float32)
        rng = np.random.default_rng(0)
        xs = [rng.normal(size=(28, 28, 1)).astype(np.float32)
              for _ in range(8)]
        total = args.clients * args.requests_per_client
        latencies, errors = [], []
        lock = threading.Lock()
        base_threads = threading.active_count()

        server = InferenceServer(model, max_wait_ms=args.max_wait_ms,
                                 example=sample).start()

        def client(cid):
            for i in range(args.requests_per_client):
                t0 = time.perf_counter()
                try:
                    server.predict(xs[(cid + i) % len(xs)], timeout=60)
                    with lock:
                        latencies.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — recorded
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        # hot swap mid-traffic: fresh weights, zero dropped requests
        time.sleep(0.02)
        swap_version = server.swap(LeNet5(10).build(jax.random.key(7)))
        for t in threads:
            t.join()
        stats = server.stats()
        server.stop()
        lat = sorted(latencies)
        p95 = lat[int(0.95 * (len(lat) - 1))] if lat else None
        out.update({
            "requests": total, "served": len(latencies),
            "batches": stats["batches"],
            "batch_fill": stats["batch_fill"],
            "p95_ms": round(p95 * 1e3, 2) if p95 is not None else None,
            "p95_bound_ms": args.p95_bound * 1e3,
            "swap_version": swap_version,
            "swaps": stats["swaps"],
            "errors": errors[:5],
            "leaked_threads": max(
                threading.active_count() - base_threads, 0)})
        out["ok"] = bool(
            len(latencies) == total                # zero dropped
            and stats["batches"] < total           # real coalescing
            and stats["batch_fill"] > 0            # non-zero fill
            and p95 is not None and p95 <= args.p95_bound
            and out["leaked_threads"] == 0
            and not errors)
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        out["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))
    sys.stdout.flush()
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
