#!/usr/bin/env python
"""Continuous train->serve drill: the optimizer->canary loop end-to-end
with trainer and server as SEPARATE processes sharing only a lineage
directory (a CPU drill; the tier-1 acceptance test in
tests/test_continuous.py drives the same artifact).

Orchestration:

1. Two subprocess trainer ranks (the simulated multi-host harness,
   ``BIGDL_TPU_ELASTIC_WORLD=2``) train a Linear model, checkpoint every
   iteration, and PUBLISH a release entry every ``--publish-every``-th
   snapshot (``set_checkpoint(..., publish=True)``).  Rank 0 carries
   chaos ``deploy.publish=corrupt@2`` — its 2nd release entry lands
   corrupt on storage.  Rank 1 carries ``host.lost@1=exit@1:3`` — it
   dies mid-epoch-1 and rank 0 must run the elastic recovery and KEEP
   PUBLISHING from the shrunken world.

2. This process is the serving side: a live ``InferenceServer`` (fresh
   random weights) + a ``DeployController`` watching the shared lineage
   dir with ``canary_fraction`` routing, while a closed-loop traffic
   thread keeps submitting.  Chaos ``serve.canary=stall*S@4,5`` inflates
   exactly the SECOND deployed release's canary latency — the comparator
   must auto-roll it back.

3. The three failure legs asserted in ONE run: the corrupt entry is
   quarantined + skipped with a typed ``ReleaseRejected`` (and the next
   good entry deploys), the host loss never interrupts the release feed
   (a release with ``neval`` past the recovery point promotes), and the
   canary regression rolls back exactly once without degrading serving.
   End state: the LAST release is promoted, the served model answers
   bit-for-bit what bulk ``Predictor.predict`` computes from that
   release's snapshot, and ZERO submitted requests were dropped or
   errored.  The merged trainer+server trace must carry the ``deploy``
   counter track (publishes + deploy outcomes on one timeline).

Prints ONE JSON line; exit 0 iff every leg closed::

    {"metric": "continuous_smoke", "ok": true, "published": 8,
     "promoted": 6, "rolled_back": 1, "rejected": 1, "recovered": true,
     "traffic": {"submitted": N, "served": N, "errors": []},
     "bit_match": true, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

# runnable as `python tools/continuous_smoke.py` from the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

LOST_EXIT = 117  # chaos.ExitAt.EXIT_CODE


# ---------------------------------------------------------------------------
# trainer worker (one logical rank, subprocess)
# ---------------------------------------------------------------------------

def _trainer(args) -> int:
    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.dataset.transformer import Transformer
    from bigdl_tpu.optim import Adam, Optimizer, Trigger

    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(128)]

    class Pace(Transformer):
        """Per-minibatch pacing so the run outlives the peer-lost
        detection window (the drill's clock, not the model's)."""

        def __init__(self, seconds):
            self.seconds = seconds

        def __call__(self, it):
            for x in it:
                if self.seconds:
                    time.sleep(self.seconds)
                yield x

    ds = (DataSet.rdd(samples)
          .transform(SampleToMiniBatch(args.batch, drop_last=True))
          .transform(Pace(args.pace)))
    ds.shuffle = lambda: None  # deterministic epoch order

    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                     nn.CrossEntropyCriterion())
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(args.epochs)))
    opt.set_checkpoint(args.ckpt_dir, Trigger.several_iteration(1),
                       publish=True, publish_every=args.publish_every)
    opt.optimize()
    plan = getattr(opt, "_elastic_plan", None)
    out = {"rank": args.rank,
           "recovered": plan is not None,
           "neval_resumed": plan.neval if plan is not None else None,
           "published": (opt._publisher.published
                         if opt._publisher is not None else 0),
           "loss": float(opt.optim_method.hyper.get("loss", 0.0))}
    print(json.dumps(out), flush=True)
    return 0


def _spawn(args, rank: int, extra_env: dict):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("BIGDL_TPU_ELASTIC", "BIGDL_TPU_CHAOS",
                                "BIGDL_TPU_TRACE", "BIGDL_TPU_SUPERVISE",
                                "BIGDL_TPU_DEPLOY"))}
    env.update({"PYTHONPATH": _REPO_ROOT,
                "JAX_PLATFORMS": args.platform,
                "BIGDL_TPU_PREFETCH_DEPTH": "0",
                **extra_env})
    wargs = ["--worker", "--rank", str(rank),
             "--ckpt-dir", args.ckpt_dir,
             "--epochs", str(args.epochs), "--batch", str(args.batch),
             "--pace", str(args.pace),
             "--publish-every", str(args.publish_every)]
    if args.platform:
        wargs += ["--platform", args.platform]
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), *wargs],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _last_json(out: str):
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


# ---------------------------------------------------------------------------
# the serving side (this process)
# ---------------------------------------------------------------------------

class _Traffic:
    """Closed-loop traffic: one request at a time, every answer counted.
    Zero-drop is the contract — any error or unanswered submit fails
    the smoke."""

    def __init__(self, server, queries):
        self.server = server
        self.queries = queries
        self.submitted = 0
        self.served = 0
        self.errors = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="continuous-smoke-traffic")

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=120.0)

    def _run(self):
        i = 0
        while not self._stop.is_set():
            x = self.queries[i % len(self.queries)]
            i += 1
            try:
                self.submitted += 1
                self.server.submit(x).result(120)
                self.served += 1
            except Exception as e:  # noqa: BLE001 — recorded, fails smoke
                self.errors.append(f"{type(e).__name__}: {e}")
                if len(self.errors) > 8:
                    return
            time.sleep(0.002)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu",
                    help="a CPU drill with several ranks: a chip belongs "
                         "to one process, so this never defaults to it")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--pace", type=float, default=0.05)
    ap.add_argument("--publish-every", type=int, default=5)
    ap.add_argument("--lost-iter", type=int, default=3)
    ap.add_argument("--peer-lost", type=float, default=0.8)
    ap.add_argument("--canary-fraction", type=float, default=0.3)
    ap.add_argument("--canary-stall", type=float, default=0.4)
    ap.add_argument("--timeout", type=int, default=240)
    args = ap.parse_args(argv)

    if args.worker:
        return _trainer(args)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    base = args.ckpt_dir or tempfile.mkdtemp(prefix="continuous_smoke_")
    cleanup = args.ckpt_dir is None
    ckpt = os.path.join(base, "ckpt")
    trace = os.path.join(base, "trace")
    os.makedirs(ckpt, exist_ok=True)
    args.ckpt_dir = ckpt
    out = {"metric": "continuous_smoke", "ok": False}
    p0 = p1 = None
    try:
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu.optim import Predictor
        from bigdl_tpu.serve import InferenceServer
        from bigdl_tpu.serve.continuous import DeployController
        from bigdl_tpu.utils import chaos, file_io, telemetry
        from bigdl_tpu.utils.engine import Engine

        Engine.init()
        import jax
        arch = nn.Sequential().add(nn.Linear(6, 2)).build(
            jax.random.key(7))
        queries = np.random.default_rng(1).normal(
            size=(32, 6)).astype(np.float32)

        # the server side writes its own rank-2 trace beside the trainer
        # ranks' so trace_report merges train + deploy on one timeline
        tracer = telemetry.Tracer(trace, rank=2)
        telemetry.set_active(tracer)

        # trainer chaos: rank 0 corrupts its 2nd release entry mid-
        # publish; rank 1 dies mid-epoch-1 (the host-loss leg)
        common = {"BIGDL_TPU_ELASTIC_WORLD": "2",
                  "BIGDL_TPU_ELASTIC_PEER_LOST": str(args.peer_lost),
                  "BIGDL_TPU_SUPERVISE_PEER_STALE": str(args.peer_lost / 2),
                  "BIGDL_TPU_SUPERVISE_STEP": "20"}
        p0 = _spawn(args, 0, {**common, "BIGDL_TPU_ELASTIC_RANK": "0",
                              "BIGDL_TPU_TRACE": trace,
                              "BIGDL_TPU_CHAOS":
                                  "deploy.publish=corrupt@2"})
        p1 = _spawn(args, 1, {**common, "BIGDL_TPU_ELASTIC_RANK": "1",
                              "BIGDL_TPU_CHAOS":
                                  f"host.lost@1=exit@1:{args.lost_iter}"})

        # serving-side chaos: canary batches 4-5 are exactly the SECOND
        # deployed release's canary episode (3 clean batches promote the
        # first) — its latency inflates and the comparator must roll it
        # back, while stalled requests are still answered (zero drop)
        with chaos.scoped(f"serve.canary=stall*{args.canary_stall}@4,5"):
            # latency_ratio 20: the injected 0.4s stall is a >100x
            # regression, while natural CPU scheduler jitter (2-5x on a
            # 2-sample window under load) must not flake the drill
            server = InferenceServer(
                arch, max_batch=4, max_wait_ms=2, queue_limit=4096,
                example=queries[0], canary_min_batches=3,
                canary_window=16, canary_latency_ratio=20.0).start()
            controller = DeployController(
                server, ckpt, canary_fraction=args.canary_fraction,
                rollback_budget=3, poll_s=0.05,
                decision_timeout=60.0).start()
            traffic = _Traffic(server, queries).start()

            out1, err1 = p1.communicate(timeout=args.timeout)
            out0, err0 = p0.communicate(timeout=args.timeout)
            out["rank0_rc"], out["rank1_rc"] = p0.returncode, p1.returncode
            if p1.returncode != LOST_EXIT:
                out["error"] = (f"rank 1 exited {p1.returncode}, expected "
                                f"the host-lost drill exit {LOST_EXIT}: "
                                f"{err1[-1500:]}")
                return 1
            if p0.returncode != 0:
                out["error"] = f"rank 0 failed: {err0[-2000:]}"
                return 1
            r0 = _last_json(out0)
            if not r0 or not r0.get("recovered") or \
                    not r0.get("published"):
                out["error"] = ("rank 0 never recovered/published: "
                                f"{r0}")
                return 1
            published = int(r0["published"])
            out.update(published=published, recovered=True,
                       neval_resumed=r0["neval_resumed"])

            # every published release must reach a terminal outcome:
            # promoted, rolled_back, or rejected
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                st = controller.stats()
                terminal = (st["promoted"] + st["rolled_back"]
                            + st["rejected"])
                if terminal >= published and st["seen"] >= published:
                    break
                time.sleep(0.1)
            traffic.stop()
            st = controller.stats()
            timeline = controller.versions()["timeline"]
            out.update({k: st[k] for k in
                        ("seen", "deployed", "promoted", "rolled_back",
                         "rejected", "consecutive_rollbacks")},
                       healthy=st["healthy"], frozen=st["frozen"])
            out["traffic"] = {"submitted": traffic.submitted,
                              "served": traffic.served,
                              "errors": traffic.errors[:5]}
            terminal = st["promoted"] + st["rolled_back"] + st["rejected"]
            if terminal < published:
                out["error"] = (f"controller consumed {terminal} of "
                                f"{published} releases in time; stats "
                                f"{st}")
                return 1

            # leg 1 — corrupt publish: skipped typed + quarantined, and
            # good entries still deployed in order
            rejected = [e for e in timeline if e["action"] == "rejected"]
            corrupt = [e for e in rejected
                       if "unreadable entry" in e.get("reason", "")]
            if not corrupt or not os.path.exists(
                    os.path.join(ckpt, "release.2.corrupt")):
                out["error"] = ("corrupt release was not skipped typed + "
                                f"quarantined: rejected={rejected}")
                return 1
            deployed_ids = [e["release"] for e in timeline
                            if e["action"] == "deployed"]
            if deployed_ids != sorted(deployed_ids) or 2 in deployed_ids:
                out["error"] = f"bad deploy order: {deployed_ids}"
                return 1

            # leg 2 — host loss: the feed survived recovery (a release
            # with neval past the resume point was promoted)
            promoted = [e for e in timeline if e["action"] == "promoted"]
            if not any(e.get("neval", -1) > (r0["neval_resumed"] or 0)
                       for e in promoted):
                out["error"] = ("no release promoted past the elastic "
                                f"recovery point: {promoted}")
                return 1

            # leg 3 — canary regression: exactly one auto-rollback, the
            # controller still healthy (budget not exhausted)
            if st["rolled_back"] != 1 or not st["healthy"]:
                out["error"] = ("expected exactly 1 canary rollback on a "
                                f"healthy controller: {st}")
                return 1

            # end state — the LAST release promoted, and the live server
            # answers bit-for-bit what that release's snapshot computes
            last = max(e["release"] for e in timeline)
            last_terminal = [e for e in timeline if e["release"] == last
                             and e["action"] in ("promoted", "rolled_back",
                                                 "rejected")]
            if not last_terminal or \
                    last_terminal[-1]["action"] != "promoted":
                out["error"] = (f"last release {last} did not promote: "
                                f"{last_terminal}")
                return 1
            out["final_release"] = last
            neval = last_terminal[-1]["neval"]
            out["final_neval"] = neval
            blob = file_io.load(os.path.join(ckpt, f"model.{neval}"))
            oracle = nn.Sequential().add(nn.Linear(6, 2)).build(
                jax.random.key(0))
            oracle.attach(blob["params"], blob["state"])
            ref = Predictor(oracle)
            mismatches = 0
            for i in range(8):
                got = server.predict(queries[i], timeout=60)
                want = ref.predict(queries[i:i + 1])[0]
                if not np.array_equal(got, want):
                    mismatches += 1
            out["bit_match"] = mismatches == 0
            if mismatches:
                out["error"] = (f"{mismatches}/8 served answers differ "
                                "from the promoted snapshot's oracle")
                return 1
            if traffic.errors or traffic.served != traffic.submitted:
                out["error"] = ("dropped/errored requests: "
                                f"{out['traffic']}")
                return 1

            controller.stop()
            server.stop()
        tracer.close()

        # the merged trainer+server trace must carry the deploy track
        breakdown = telemetry.phase_breakdown(telemetry.merge_traces(trace))
        out["deploy_report"] = breakdown.get("deploy", {})
        if breakdown.get("deploy", {}).get("published") != published or \
                "promoted" not in breakdown.get("deploy", {}):
            out["error"] = ("merged trace is missing the deploy track: "
                            f"{out['deploy_report']}")
            return 1
        out["ok"] = True
        return 0
    except subprocess.TimeoutExpired as e:
        out["error"] = f"drill timed out: {e}"
        for proc in (p0, p1):
            if proc is not None and proc.poll() is None:
                proc.kill()
        return 1
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        import traceback
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
        return 1
    finally:
        print(json.dumps(out))
        sys.stdout.flush()
        if cleanup:
            shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
