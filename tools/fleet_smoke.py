#!/usr/bin/env python
"""Fleet drill: supervised worker PROCESSES under kill -9, a wedged
zombie, and a stale registry entry — in ONE run (a CPU drill;
tests/test_fleet.py drives the same modules in-process).

Orchestration:

1. A :class:`FleetSupervisor` (this process) spawns three
   ``tools/serve_worker.py`` members into a shared fleet dir, every one
   warming its bucket ladder through ONE shared AOT cache dir.  A
   :class:`FleetFront` routes over the registry.  A bogus
   ``member.7.1`` record with no heartbeat is planted — the stale
   registry entry that must NEVER attract traffic.

2. A synthetic request trace replays through the front while the fleet
   is hurt mid-traffic: member 0 takes a real ``kill -9`` (process
   gone: connections refused, the front's bounded retry-on-next-member
   absorbs in-flight rows), and member 1 carries chaos
   ``fleet.member@1=wedge`` — its beat loop blocks uninterruptibly so
   the heartbeat goes silent while its HTTP threads still answer: the
   ZOMBIE.  The supervisor must promote both into typed losses, condemn
   the lost generations (the bump the zombie exits on), and respawn
   both at generation 2 — WARM: the respawned members' AOT ledgers must
   show zero fresh lowers and zero cache misses.

3. A release (new weights) publishes into a lineage dir and a
   :class:`DeployController` in fleet mode rolls it out: canary on the
   lowest live member decided by that member's OWN comparator under
   routed traffic, then a rolling swap over the rest with at most
   ``--max-unavailable`` members in-swap at once.

4. Asserted in one run: ZERO accepted-request loss across both faults
   (every admitted row answered; sheds would be typed, and there must
   be none), the stale entry never routed, warm respawn (no fresh
   lowers), the rolling deploy promoted with bounded blast radius, the
   whole fleet serving the release BIT-FOR-BIT equal to bulk
   ``Predictor.predict``, and the merged trace carrying the ``fleet``
   counter track beside the ``deploy`` timeline.

Prints ONE JSON line; exit 0 iff every leg closed::

    {"metric": "fleet_smoke", "ok": true, "replay": {...},
     "respawned": {"0": 2, "1": 2}, "warm_respawn": true,
     "deploy": {...}, "bit_match": true, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

# runnable as `python tools/fleet_smoke.py` from the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _wait(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"fleet_smoke: timed out waiting for {what}")


class _Traffic:
    """Closed-loop traffic through the front (feeds the canary member's
    comparator during the deploy).  Zero-drop contract: any error fails
    the smoke."""

    def __init__(self, front, queries):
        self.front = front
        self.queries = queries
        self.submitted = 0
        self.served = 0
        self.errors = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="fleet-smoke-traffic")

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
                self.front.submit(x).result(60)
                self.served += 1
            except Exception as e:  # noqa: BLE001 — recorded, fails smoke
                self.errors.append(f"{type(e).__name__}: {e}")
                if len(self.errors) > 8:
                    return
            time.sleep(0.005)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu",
                    help="a CPU drill: parent and workers each start jax, "
                         "and a chip belongs to one process")
    ap.add_argument("--members", type=int, default=3)
    ap.add_argument("--requests", type=int, default=150)
    ap.add_argument("--heartbeat-s", type=float, default=0.1)
    ap.add_argument("--lost-after-s", type=float, default=1.0)
    ap.add_argument("--wedge-beat", type=int, default=50,
                    help="beat count at which member 1's first life "
                         "wedges (publication silence, HTTP alive)")
    ap.add_argument("--canary-fraction", type=float, default=0.3)
    ap.add_argument("--max-unavailable", type=int, default=1)
    ap.add_argument("--timeout", type=int, default=420)
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    base = tempfile.mkdtemp(prefix="fleet_smoke_")
    fleet_dir = os.path.join(base, "fleet")
    aot_dir = os.path.join(base, "aot")
    trace_dir = os.path.join(base, "trace")
    lineage = os.path.join(base, "lineage")
    logs = os.path.join(base, "logs")
    for d in (fleet_dir, aot_dir, trace_dir, lineage, logs):
        os.makedirs(d, exist_ok=True)
    # the ORACLE must share the workers' AOT cache: an AOT executable's
    # numerics are shape-exact but can differ from the jit path by 1 ULP,
    # so bit-match only holds when both sides run the same executables
    os.environ["BIGDL_TPU_AOT_CACHE"] = aot_dir

    out = {"metric": "fleet_smoke", "ok": False}
    sup = front = controller = traffic = tracer = None
    try:
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu.optim import Predictor
        from bigdl_tpu.serve import (DeployController, FleetFront,
                                     FleetSupervisor, ReleasePublisher,
                                     TraceEvent, fleet, replay,
                                     resolve_outcomes)
        from bigdl_tpu.utils import file_io, telemetry
        from bigdl_tpu.utils.engine import Engine

        Engine.init()
        import jax

        # the front/supervisor process writes the rank-0 trace; each
        # worker writes rank 10+idx beside it -> ONE merged timeline
        tracer = telemetry.Tracer(trace_dir, rank=0)
        telemetry.set_active(tracer)
        telemetry.thread_name("fleet smoke")

        # -- 1. spawn the fleet -----------------------------------------
        def spawn(index, generation):
            env = {k: v for k, v in os.environ.items()
                   if not k.startswith(("BIGDL_TPU_ELASTIC",
                                        "BIGDL_TPU_CHAOS",
                                        "BIGDL_TPU_TRACE",
                                        "BIGDL_TPU_SUPERVISE",
                                        "BIGDL_TPU_DEPLOY",
                                        "BIGDL_TPU_FLEET"))}
            env.update({"PYTHONPATH": _REPO_ROOT,
                        "JAX_PLATFORMS": args.platform,
                        "BIGDL_TPU_PREFETCH_DEPTH": "0",
                        "BIGDL_TPU_AOT_CACHE": aot_dir,
                        "BIGDL_TPU_TRACE": trace_dir,
                        "BIGDL_TPU_SERVE_CANARY_MIN_BATCHES": "2"})
            if index == 1 and generation == 1:
                # the ZOMBIE leg: this life's beat loop wedges mid-
                # traffic while its HTTP threads keep answering.  Only
                # the FIRST life — the respawn must come back clean.
                env["BIGDL_TPU_CHAOS"] = \
                    f"fleet.member@1=wedge@{args.wedge_beat}"
            log = open(os.path.join(
                logs, f"member.{index}.{generation}.log"), "w")
            cmd = [sys.executable,
                   os.path.join(_REPO_ROOT, "tools", "serve_worker.py"),
                   "--fleet-dir", fleet_dir,
                   "--index", str(index),
                   "--generation", str(generation),
                   "--model", "linear",
                   "--heartbeat-s", str(args.heartbeat_s)]
            if args.platform:
                cmd += ["--platform", args.platform]
            return subprocess.Popen(cmd, env=env, stdout=log, stderr=log)

        sup = FleetSupervisor(fleet_dir, spawn, members=args.members,
                              lost_after_s=args.lost_after_s, poll_s=0.2,
                              backoff_s=0.2, grace_s=180.0,
                              restart_budget=3).start()
        front = FleetFront(fleet_dir, refresh_s=0.1,
                           lost_after_s=args.lost_after_s, retries=2,
                           timeout_s=30.0, decision_timeout=120.0,
                           max_unavailable=args.max_unavailable)

        # the stale registry entry: a record with NO heartbeat behind it
        # (a member that registered and vanished before ever beating) —
        # must never attract a single request
        fleet.publish_member(fleet_dir, index=7, generation=1, pid=999999,
                             port=1)

        _wait(lambda: sup.live_count() >= args.members, args.timeout / 2,
              f"{args.members} live members")
        members1 = front.members()
        if sorted(members1) != list(range(args.members)):
            out["error"] = f"bad initial registry: {sorted(members1)}"
            return 1
        out["spawned"] = {str(i): members1[i]["generation"]
                          for i in members1}

        # -- baseline: the whole fleet serves the seed weights bit-for-
        # bit (every worker builds the same deterministic linear model)
        model1 = nn.Sequential().add(nn.Linear(4, 3)).build(
            jax.random.key(0))
        rng = np.random.default_rng(11)
        queries = rng.standard_normal((32, 4)).astype(np.float32)
        oracle1 = Predictor(model1)
        # per-row oracle: sequential front predicts run the bucket-1
        # executable, so the reference must run the same (1, din) shape
        # (loaded from the SAME shared cache -> byte-identical numerics)
        want1 = np.stack([oracle1.predict(queries[i:i + 1])[0]
                          for i in range(4)])
        got1 = np.stack([front.predict(q, timeout=60)
                         for q in queries[:4]])
        out["bit_match_seed"] = bool(np.array_equal(got1, want1))
        if not out["bit_match_seed"]:
            out["error"] = "seed weights do not bit-match bulk Predictor"
            return 1

        # -- 2. replay a trace while the fleet is hurt -------------------
        events = [TraceEvent(0.04, queries[i % len(queries)])
                  for i in range(args.requests)]
        want_rows = oracle1.predict(queries)
        replayed = {}

        def run_replay():
            replayed["outcomes"] = replay(
                events, lambda e: front.submit(e.payload), speed=1.0)

        rt = threading.Thread(target=run_replay, daemon=True,
                              name="fleet-smoke-replay")
        rt.start()

        # kill -9 member 0 mid-replay: the real SIGKILL, not a stop —
        # its socket refuses, in-flight rows fail over to survivors
        time.sleep(1.5)
        pid0 = members1[0]["pid"]
        os.kill(pid0, signal.SIGKILL)
        out["killed_pid"] = pid0
        # member 1 wedges on its own beat counter (chaos env above)

        rt.join(timeout=args.timeout / 2)
        if rt.is_alive():
            out["error"] = "replay never finished"
            return 1
        outcomes = replayed["outcomes"]
        resolve_outcomes(outcomes, timeout=120.0)
        errors = [f"{type(o.error).__name__}: {o.error}"
                  for o in outcomes if o.error is not None]
        served = sum(1 for o in outcomes
                     if o.handle is not None and o.error is None)
        out["replay"] = {"offered": len(outcomes), "served": served,
                         "errors": errors[:5],
                         "retried": front.stats()["fleet"]["retried"]}
        if errors or served != len(outcomes):
            out["error"] = f"accepted-request loss: {out['replay']}"
            return 1
        # every replayed answer is the right model's answer for its row —
        # allclose here (not bit-equal) because replay rows coalesce into
        # whatever bucket is filling, and each bucket shape is its own
        # AOT executable (shape-exact numerics, 1 ULP apart across
        # shapes); wrong weights or a misrouted row would be off by
        # orders of magnitude, not 1 ULP
        mismatch = sum(
            1 for i, o in enumerate(outcomes)
            if not np.allclose(o.handle.result(1),
                               want_rows[i % len(queries)], rtol=1e-5))
        if mismatch:
            out["error"] = f"{mismatch} replayed rows differ from oracle"
            return 1

        # -- both hurt members replaced at generation 2 ------------------
        def replaced():
            m = front.members()
            return (0 in m and m[0]["generation"] >= 2 and
                    1 in m and m[1]["generation"] >= 2 and
                    sup.live_count() >= args.members)

        _wait(replaced, args.timeout / 2, "generation-2 respawns")
        members2 = front.members()
        out["respawned"] = {str(i): members2[i]["generation"]
                            for i in sorted(members2)}
        out["condemned"] = {
            "0": fleet.condemned_generation(fleet_dir, 0),
            "1": fleet.condemned_generation(fleet_dir, 1)}
        if out["condemned"]["0"] < 1 or out["condemned"]["1"] < 1:
            out["error"] = f"lost generations not condemned: {out}"
            return 1

        # -- warm respawn: the generation-2 members warmed their bucket
        # ladders ENTIRELY from the shared AOT cache (zero fresh lowers,
        # zero misses — the generation-1 fleet paid the compile once)
        warm = {}
        for i in (0, 1):
            st = front.member_stats(i) or {}
            aot = st.get("aot") or {}
            warm[str(i)] = {"lowers": aot.get("lowers"),
                            "misses": aot.get("misses"),
                            "hits": aot.get("hits")}
        out["warm_respawn_aot"] = warm
        cold = [i for i, w in warm.items()
                if w["lowers"] != 0 or w["misses"] != 0]
        if cold:
            out["error"] = f"respawn was not warm for members {cold}: {warm}"
            return 1
        out["warm_respawn"] = True

        # -- stale entry never attracted traffic -------------------------
        routed = front.stats()["fleet"]["members"]
        out["stale_entry_routed"] = "7" in routed
        if out["stale_entry_routed"]:
            out["error"] = "stale registry entry (member 7) was routed"
            return 1

        # -- 3. rolling deploy through the DeployController --------------
        model2 = nn.Sequential().add(nn.Linear(4, 3)).build(
            jax.random.key(1))
        snap = os.path.join(lineage, "model.1")
        file_io.save({"params": model2.params, "state": model2.state},
                     snap)
        ReleasePublisher(lineage).publish(snap, neval=1)

        traffic = _Traffic(front, queries).start()
        controller = DeployController(
            front, lineage, canary_fraction=args.canary_fraction,
            poll_s=0.1, decision_timeout=120.0,
            max_unavailable=args.max_unavailable).start()
        _wait(lambda: controller.stats()["promoted"] >= 1,
              args.timeout / 2, "the release to promote fleet-wide")
        traffic.stop()
        cst = controller.stats()
        fst = front.stats()
        out["deploy"] = {
            "promoted": cst["promoted"],
            "rolled_back": cst["rolled_back"],
            "canary": fst.get("canary"),
            "rolled": fst["fleet"]["deploy"]["rolled"],
            "max_concurrent": fst["fleet"]["deploy"]["max_concurrent"]}
        out["traffic"] = {"submitted": traffic.submitted,
                          "served": traffic.served,
                          "errors": traffic.errors[:5]}
        if traffic.errors or traffic.served != traffic.submitted:
            out["error"] = f"deploy-window traffic loss: {out['traffic']}"
            return 1
        if fst["fleet"]["deploy"]["max_concurrent"] > args.max_unavailable:
            out["error"] = ("rolling deploy exceeded max-unavailable: "
                            f"{out['deploy']}")
            return 1
        if (fst.get("canary") or {}).get("state") != "promoted":
            out["error"] = f"canary verdict not promoted: {out['deploy']}"
            return 1

        # -- end state: EVERY member serves the release bit-for-bit
        # (single-row POST = bucket-1 executable = the oracle's shape)
        want2 = Predictor(model2).predict(queries[:1])[0]
        per_member = {}
        for i, rec in front.members().items():
            req = urllib.request.Request(
                f"http://{rec.get('host', '127.0.0.1')}:{rec['port']}"
                "/v1/predict",
                data=json.dumps({"inputs":
                                 queries[0].tolist()}).encode(),
                method="POST")
            with urllib.request.urlopen(req, timeout=60) as r:
                got = np.asarray(json.loads(r.read())["outputs"],
                                 np.float32)
            per_member[str(i)] = bool(np.array_equal(got, want2))
        out["bit_match_members"] = per_member
        out["bit_match"] = all(per_member.values()) and \
            len(per_member) == args.members
        if not out["bit_match"]:
            out["error"] = ("fleet members disagree with the promoted "
                            f"release: {per_member}")
            return 1

        # -- live metrics plane: every member answers GET /metrics with
        # Prometheus text exposition, and the front's rollup re-exports
        # the fleet under fleet_-prefixed, member-labelled series
        mrec = next(iter(front.members().values()))
        mreq = urllib.request.Request(
            f"http://{mrec.get('host', '127.0.0.1')}:{mrec['port']}"
            "/metrics")
        with urllib.request.urlopen(mreq, timeout=30) as r:
            mtext = r.read().decode()
        rollup = front.metrics_text()
        out["metrics"] = {
            "member_ok": "bigdl_serve_requests_total" in mtext,
            "rollup_ok": "fleet_bigdl_serve_requests_total" in rollup}
        if not all(out["metrics"].values()):
            out["error"] = f"metrics plane incomplete: {out['metrics']}"
            return 1

        # degradation never tripped: every loss stayed within budget
        sst = sup.stats()
        out["supervisor"] = {"restarts": sst["restarts"],
                             "degraded": sst["degraded"]}
        if sst["degraded"]:
            out["error"] = f"a slot degraded during the drill: {sst}"
            return 1

        # -- teardown, then the merged timeline ---------------------------
        controller.stop()
        controller = None
        front.close()
        sup.stop()          # condemn + terminate -> workers drain, close
        sup = None          # their tracers, flush rank-10.. trace files
        tracer.close()
        tracer = None

        merged = telemetry.merge_traces(trace_dir)
        breakdown = telemetry.phase_breakdown(merged)
        out["fleet_report"] = breakdown.get("fleet", {})
        out["deploy_report"] = breakdown.get("deploy", {})
        if not breakdown.get("fleet") or not breakdown.get("deploy"):
            out["error"] = ("merged trace is missing the fleet/deploy "
                            f"tracks: fleet={out['fleet_report']} "
                            f"deploy={out['deploy_report']}")
            return 1

        # -- request flows: every traced request is one Perfetto arrow
        # chain across front + worker ranks, and the kill -9 leg left at
        # least one flow that touched TWO members (the failover story)
        rb = telemetry.request_breakdown(merged)
        multi = [rid for rid, r in rb["requests"].items()
                 if len(r.get("members", [])) >= 2]
        cross = [rid for rid, r in rb["requests"].items()
                 if len(r.get("ranks", [])) >= 2]
        out["request_flows"] = {"count": rb["count"],
                                "cross_process": len(cross),
                                "failover_flows": len(multi)}
        if rb["count"] == 0:
            out["error"] = "merged trace holds no request flows"
            return 1
        if not cross:
            out["error"] = ("no request flow spans front AND a worker "
                            f"process: {out['request_flows']}")
            return 1
        if not multi:
            out["error"] = ("kill -9 failover left no two-member "
                            f"request flow: {out['request_flows']}")
            return 1
        out["ok"] = True
        return 0
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        import traceback
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
        return 1
    finally:
        for closer in (traffic, controller):
            try:
                if closer is not None:
                    closer.stop()
            except Exception:  # noqa: BLE001
                pass
        try:
            if front is not None:
                front.close()
        except Exception:  # noqa: BLE001
            pass
        try:
            if sup is not None:
                sup.stop()
        except Exception:  # noqa: BLE001
            pass
        try:
            if tracer is not None:
                tracer.close()
        except Exception:  # noqa: BLE001
            pass
        print(json.dumps(out))
        sys.stdout.flush()
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
