#!/usr/bin/env python
"""Elastic host-loss drill: prove detect->negotiate->re-form->resume
end-to-end with REAL processes (a CPU drill; the tier-1 acceptance test
drives this too).

Orchestration (default mode):

1. Spawn 2 subprocess ranks — single-process jax runtimes coordinated
   ONLY through file_io (the simulated multi-host harness: logical
   topology from ``BIGDL_TPU_ELASTIC_WORLD``/``_ELASTIC_RANK``, shared
   checkpoint + heartbeat dirs).  Rank 1 carries chaos
   ``host.lost@1=exit@1:<iter>`` — at epoch 1 iteration <iter> it stops
   publishing and dies (exit 117, the expected outcome).
2. Rank 0 must DETECT the publication silence (PeerLostError), negotiate
   the newest common lineage entry, shrink to world=1 with the per-host
   batch rescaled 16 -> 32 (global batch preserved), resume, and finish
   training — its trace must carry the ``elastic.*`` events.
3. A third, CLEAN world-1 process resumes from the SAME negotiated
   lineage entry at batch 32 and trains to the same end trigger: its
   final loss must match rank 0's bit-for-bit (shuffle disabled and the
   snapshot's RNG state restored in both, so the post-resume iteration
   sequences are identical).

``--grow`` runs the full preemption-AND-reclamation drill instead
(parallel/elastic step 4):

1. Same kill: rank 1 dies at epoch 1 (exit 117), rank 0 shrinks to
   world=1 / batch 32 — but rank 0 also PUBLISHES a release entry per
   checkpoint (``set_checkpoint(..., publish=True)``), so a deployment
   feed crosses both resizes.
2. Rank 1 is re-spawned as a JOINER (``BIGDL_TPU_ELASTIC_JOIN=1``,
   chaos ``host.return@1=join@2:2``): it gates on the survivor's
   checkpoint stream reaching epoch 2, announces itself
   (``elastic/join.1`` + generation-bumped heartbeat), waits for the
   admission offer rank 0 writes at its next checkpoint boundary, and
   both negotiate the join snapshot — rank 0 widens back to world=2 and
   rescales batch 32 -> 16, the joiner adopts the agreed lineage entry.
3. Asserted: world 2 -> 1 -> 2 and per-host batch 16 -> 32 -> 16 (from
   ``Optimizer._elastic_history``), ``elastic.join`` / ``.agree`` /
   ``.reform`` / ``.resume`` in BOTH ranks' traces, release ids
   gap-free across both resizes, a stub-served DeployController
   promotes a release published AFTER the grow, and clean world-2
   runs resumed from the join snapshot bit-match both ranks' final
   losses.

Prints ONE JSON line; exit 0 iff the whole drill closed:

    {"metric": "elastic_smoke", "recovered": true, "neval_resumed": 7,
     "world_after": 1, "batch_after": 32, "loss": ..., "clean_loss": ...,
     "loss_match": true, "elastic_events": [...], ...}
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

# runnable as `python tools/elastic_smoke.py` from the repo root (the
# usual invocation): sys.path[0] is tools/, so add the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

LOST_EXIT = 117  # chaos.ExitAt.EXIT_CODE


def _worker(args) -> int:
    """One logical rank (or the clean comparison run)."""
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
    from bigdl_tpu.utils.engine import Engine

    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(128)]

    class Pace(Transformer):
        """Per-minibatch pacing so the run outlives the detection window
        (the drill's clock is the peer-lost threshold, not the model)."""

        def __init__(self, seconds):
            self.seconds = seconds

        def __call__(self, it):
            import time
            for x in it:
                if self.seconds:
                    time.sleep(self.seconds)
                yield x

    ds = (DataSet.rdd(samples)
          .transform(SampleToMiniBatch(args.batch, drop_last=True))
          .transform(Pace(args.pace)))
    # identical epoch order for the faulted and clean runs: post-resume
    # bit-identity is the acceptance bound, and dataset shuffle RNGs are
    # per-instance (not in the snapshot) — so the drill pins the order
    ds.shuffle = lambda: None

    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                     nn.CrossEntropyCriterion())
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(args.epochs)))
    out = {"rank": args.rank, "recovered": False}
    if args.resume_neval:
        # clean world-1 comparison: resume from the negotiated entry, no
        # new checkpoints (the lineage under test must stay untouched)
        opt.resume_from(os.path.join(args.ckpt_dir,
                                     f"model.{args.resume_neval}"),
                        os.path.join(args.ckpt_dir,
                                     f"optimMethod.{args.resume_neval}"))
    else:
        opt.set_checkpoint(args.ckpt_dir, Trigger.several_iteration(1),
                           publish=True if args.publish else None)
    trained = opt.optimize()
    plan = getattr(opt, "_elastic_plan", None)
    if plan is not None:
        out.update(recovered=True, neval_resumed=plan.neval,
                   world_after=Engine.world(),
                   batch_after=opt._find_batchers(opt.dataset)[0].batch_size)
    out["history"] = getattr(opt, "_elastic_history", [])
    out["loss"] = float(opt.optim_method.hyper["loss"])
    out["finite"] = bool(all(np.all(np.isfinite(np.asarray(leaf)))
                             for leaf in
                             __import__("jax").tree.leaves(trained.params)))
    print(json.dumps(out), flush=True)
    return 0


def _spawn(args, rank: int, extra_env: dict, worker_args: list):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("BIGDL_TPU_ELASTIC", "BIGDL_TPU_CHAOS",
                                "BIGDL_TPU_TRACE", "BIGDL_TPU_SUPERVISE"))}
    env.update({"PYTHONPATH": _REPO_ROOT,
                "JAX_PLATFORMS": args.platform,
                "BIGDL_TPU_PREFETCH_DEPTH": "0",  # sync data path: the
                # faulted and clean runs must be bit-comparable
                **extra_env})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--rank", str(rank), *worker_args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _last_json(out: str):
    lines = [l for l in out.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def _trace_events(trace_dir: str) -> dict:
    """Per trace file: sorted list of elastic.* event names."""
    by_file = {}
    for tf in glob.glob(os.path.join(trace_dir, "trace.*.json")):
        names = set()
        try:
            for ev in json.load(open(tf)).get("traceEvents", []):
                if str(ev.get("name", "")).startswith("elastic."):
                    names.add(ev["name"])
        except ValueError:
            pass
        by_file[os.path.basename(tf)] = sorted(names)
    return by_file


def _grow_drill(args, ckpt: str, trace: str) -> int:
    """Kill-then-return: shrink 2->1, joiner re-admitted, grow 1->2,
    release feed gap-free across both resizes, clean world-2 bit-match."""
    import re

    out = {"metric": "elastic_grow_smoke", "recovered": False,
           "joined": False, "loss_match": False, "elastic_events": {}}
    procs = []
    try:
        wargs = ["--ckpt-dir", ckpt, "--epochs", str(args.epochs),
                 "--batch", str(args.batch), "--pace", str(args.pace)]
        if args.platform:
            wargs += ["--platform", args.platform]
        common = {"BIGDL_TPU_ELASTIC_WORLD": "2",
                  "BIGDL_TPU_ELASTIC_PEER_LOST": str(args.peer_lost),
                  "BIGDL_TPU_SUPERVISE_PEER_STALE":
                      str(args.peer_lost / 2),
                  "BIGDL_TPU_SUPERVISE_STEP": "20"}
        # rank 0: the survivor — traces AND publishes (the deployment
        # feed whose continuity across both resizes is under test)
        p0 = _spawn(args, 0, {**common, "BIGDL_TPU_ELASTIC_RANK": "0",
                              "BIGDL_TPU_TRACE": trace},
                    wargs + ["--publish"])
        procs.append(p0)
        p1 = _spawn(args, 1, {**common, "BIGDL_TPU_ELASTIC_RANK": "1",
                              "BIGDL_TPU_CHAOS":
                                  f"host.lost@1=exit@1:{args.lost_iter}"},
                    wargs)
        procs.append(p1)
        def _keep(tag, stdout, stderr):
            # worker logs beside the lineage: a caller captures only
            # the orchestrator's one JSON line, so a failing stage needs
            # these for the post-mortem
            try:
                with open(os.path.join(ckpt, f"{tag}.log"), "w") as f:
                    f.write(stdout + "\n--- stderr ---\n" + stderr)
            except OSError:
                pass

        out1, err1 = p1.communicate(timeout=args.timeout)
        _keep("rank1", out1, err1)
        out["rank1_rc"] = p1.returncode
        if p1.returncode != LOST_EXIT:
            out["error"] = (f"rank 1 exited {p1.returncode}, expected the "
                            f"host-lost drill exit {LOST_EXIT}: "
                            f"{err1[-1500:]}")
            return 1
        # rank 1 returns: same logical rank, join-armed, gated on the
        # survivor's checkpoint stream reaching --return-at (at-or-after)
        pj = _spawn(args, 1, {**common, "BIGDL_TPU_ELASTIC_RANK": "1",
                              "BIGDL_TPU_ELASTIC_JOIN": "1",
                              "BIGDL_TPU_ELASTIC_JOIN_POLL": "0.05",
                              "BIGDL_TPU_ELASTIC_JOIN_TIMEOUT": "60",
                              "BIGDL_TPU_TRACE": trace,
                              "BIGDL_TPU_CHAOS":
                                  f"host.return@1=join@{args.return_at}"},
                    wargs)
        procs.append(pj)
        outj, errj = pj.communicate(timeout=args.timeout)
        _keep("joiner", outj, errj)
        out0, err0 = p0.communicate(timeout=args.timeout)
        _keep("rank0", out0, err0)
        out["rank0_rc"] = p0.returncode
        out["joiner_rc"] = pj.returncode
        if pj.returncode != 0:
            out["error"] = f"joiner failed: {errj[-2000:]}"
            return 1
        if p0.returncode != 0:
            out["error"] = f"rank 0 failed: {err0[-2000:]}"
            return 1
        r0, rj = _last_json(out0), _last_json(outj)
        if not r0 or not r0.get("recovered") or not r0.get("finite"):
            out["error"] = f"rank 0 never ran elastic recovery: {r0}"
            return 1
        if not rj or not rj.get("recovered") or not rj.get("finite"):
            out["error"] = f"joiner never joined: {rj}"
            return 1
        out["recovered"] = True
        # world 2 -> 1 -> 2 and per-host batch B -> 2B -> B, from the
        # survivor's audit trail; the joiner records exactly one join
        kinds0 = [h["kind"] for h in r0.get("history", [])]
        out["history_rank0"] = r0.get("history", [])
        out["history_joiner"] = rj.get("history", [])
        if kinds0 != ["shrink", "grow"]:
            out["error"] = f"rank 0 episode kinds {kinds0} != " \
                           "['shrink', 'grow']"
            return 1
        shrink, grow = r0["history"]
        if [shrink["world"], grow["world"]] != [1, 2] or \
                [shrink["batch"], grow["batch"]] != \
                [2 * args.batch, args.batch]:
            out["error"] = ("resize trajectory wrong (want world 2->1->2, "
                            f"batch {args.batch}->{2 * args.batch}->"
                            f"{args.batch}): {r0['history']}")
            return 1
        if [h["kind"] for h in rj.get("history", [])] != ["join"] or \
                rj["history"][0]["world"] != 2 or \
                rj["history"][0]["batch"] != args.batch:
            out["error"] = f"joiner episode wrong: {rj.get('history')}"
            return 1
        out["joined"] = True
        grow_neval = int(grow["neval"])
        out["grow_neval"] = grow_neval
        if int(rj["history"][0]["neval"]) != grow_neval:
            out["error"] = ("survivor and joiner adopted different "
                            f"snapshots: {grow_neval} != "
                            f"{rj['history'][0]['neval']}")
            return 1
        # BOTH ranks' traces must carry the grow episode
        out["elastic_events"] = _trace_events(trace)
        need = {"elastic.join", "elastic.agree", "elastic.reform",
                "elastic.resume"}
        for rk in (0, 1):
            have = set(out["elastic_events"].get(f"trace.{rk}.json", []))
            if not need <= have:
                out["error"] = (f"rank {rk} trace missing elastic grow "
                                f"events: {sorted(need - have)}")
                return 1
        # release feed: ids must be gap-free across BOTH resizes, and a
        # stub-served DeployController must promote a release published
        # AFTER the grow (the train->serve loop survived the resize)
        from bigdl_tpu.serve.continuous import (DeployController,
                                                RELEASE_PATTERN)
        ids = sorted(int(m.group(1)) for n in os.listdir(ckpt)
                     for m in [re.fullmatch(RELEASE_PATTERN, n)] if m)
        out["releases"] = len(ids)
        out["release_gap_free"] = bool(
            ids and ids == list(range(ids[0], ids[0] + len(ids))))
        if not out["release_gap_free"]:
            out["error"] = f"release feed has gaps: {ids}"
            return 1

        class _Server:
            def __init__(self):
                self.versions = 0

            def swap(self, source, canary_fraction=None):
                self.versions += 1
                return self.versions

            def stats(self):
                return {}

        # canary_fraction=0 -> full swaps, each deploy promotes at once
        ctrl = DeployController(_Server(), ckpt, canary_fraction=0.0,
                                since=0)
        for rid in ids:
            ctrl._handle(rid, os.path.join(ckpt, f"release.{rid}"))
        out["promoted"] = ctrl.counts["promoted"]
        out["rejected"] = ctrl.counts["rejected"]
        promoted_after = [t for t in ctrl.timeline
                          if t.get("action") == "promoted" and
                          (t.get("neval") or -1) > grow_neval]
        out["promoted_after_grow"] = len(promoted_after)
        if ctrl.counts["rejected"] or not promoted_after:
            out["error"] = ("deployment did not survive the resize: "
                            f"rejected={ctrl.counts['rejected']} "
                            f"promoted_after_grow={len(promoted_after)}")
            return 1
        # clean world-2 runs resumed from the join snapshot: each rank's
        # final loss must match the drilled run bit-for-bit
        cargs = ["--ckpt-dir", ckpt, "--epochs", str(args.epochs),
                 "--batch", str(args.batch), "--pace", "0",
                 "--resume-neval", str(grow_neval)]
        if args.platform:
            cargs += ["--platform", args.platform]
        cleans = []
        for rk in (0, 1):
            pc = _spawn(args, rk,
                        {"BIGDL_TPU_ELASTIC_WORLD": "2",
                         "BIGDL_TPU_ELASTIC_RANK": str(rk)}, cargs)
            procs.append(pc)
            cleans.append(pc)
        losses = {0: r0["loss"], 1: rj["loss"]}
        for rk, pc in zip((0, 1), cleans):
            outc, errc = pc.communicate(timeout=args.timeout)
            if pc.returncode != 0:
                out["error"] = f"clean rank {rk} failed: {errc[-2000:]}"
                return 1
            rc_ = _last_json(outc)
            out[f"clean_loss_rank{rk}"] = rc_["loss"]
            if abs(rc_["loss"] - losses[rk]) >= 1e-9:
                out["error"] = (f"rank {rk}: drilled loss "
                                f"{losses[rk]!r} != clean world-2 loss "
                                f"{rc_['loss']!r}")
                return 1
        out["loss"] = r0["loss"]
        out["join_loss"] = rj["loss"]
        out["loss_match"] = True
        return 0
    except subprocess.TimeoutExpired as e:
        out["error"] = f"grow drill timed out: {e}"
        return 1
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        out["error"] = f"{type(e).__name__}: {e}"
        return 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        print(json.dumps(out))
        sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu",
                    help="a CPU drill with several ranks: a chip belongs "
                         "to one process, so this never defaults to it")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--pace", type=float, default=None)
    ap.add_argument("--resume-neval", type=int, default=0)
    ap.add_argument("--lost-iter", type=int, default=3,
                    help="epoch-1 iteration at which rank 1 dies "
                         "(chaos host.lost@1=exit@1:N)")
    ap.add_argument("--grow", action="store_true",
                    help="kill-then-RETURN drill: rank 1 rejoins at "
                         "epoch 2 and the cluster widens back to "
                         "world=2")
    ap.add_argument("--return-at", default="2:2",
                    help="epoch:iteration join gate for the re-spawned "
                         "rank 1 (chaos host.return@1=join@E:I, fires "
                         "at-or-after)")
    ap.add_argument("--publish", action="store_true",
                    help="worker flag: publish a release entry per "
                         "checkpoint (the --grow deployment feed)")
    ap.add_argument("--peer-lost", type=float, default=0.8)
    ap.add_argument("--timeout", type=int, default=240)
    args = ap.parse_args(argv)
    if args.pace is None:
        # the grow drill paces slower: the survivor must still be
        # training when the re-spawned joiner (a fresh jax runtime)
        # finishes importing, gates on epoch 2, and negotiates
        args.pace = 0.35 if args.grow else 0.05
    if args.epochs is None:
        args.epochs = 12 if args.grow else 10

    if args.worker:
        return _worker(args)

    base = args.ckpt_dir or tempfile.mkdtemp(prefix="elastic_smoke_")
    cleanup = args.ckpt_dir is None
    ckpt = os.path.join(base, "ckpt")
    trace = os.path.join(base, "trace")
    os.makedirs(ckpt, exist_ok=True)
    if args.grow:
        try:
            return _grow_drill(args, ckpt, trace)
        finally:
            if cleanup:
                shutil.rmtree(base, ignore_errors=True)
    out = {"metric": "elastic_smoke", "recovered": False,
           "loss_match": False, "elastic_events": []}
    try:
        wargs = ["--ckpt-dir", ckpt, "--epochs", str(args.epochs),
                 "--batch", str(args.batch), "--pace", str(args.pace)]
        if args.platform:
            wargs += ["--platform", args.platform]
        common = {"BIGDL_TPU_ELASTIC_WORLD": "2",
                  "BIGDL_TPU_ELASTIC_PEER_LOST": str(args.peer_lost),
                  "BIGDL_TPU_SUPERVISE_PEER_STALE":
                      str(args.peer_lost / 2),
                  # a live phase deadline beside elasticity: recovery must
                  # run under the 'checkpoint' phase, not trip this
                  "BIGDL_TPU_SUPERVISE_STEP": "20"}
        p0 = _spawn(args, 0, {**common, "BIGDL_TPU_ELASTIC_RANK": "0",
                              "BIGDL_TPU_TRACE": trace}, wargs)
        p1 = _spawn(args, 1, {**common, "BIGDL_TPU_ELASTIC_RANK": "1",
                              "BIGDL_TPU_CHAOS":
                                  f"host.lost@1=exit@1:{args.lost_iter}"},
                    wargs)
        out1, err1 = p1.communicate(timeout=args.timeout)
        out0, err0 = p0.communicate(timeout=args.timeout)
        out["rank1_rc"] = p1.returncode
        out["rank0_rc"] = p0.returncode
        if p1.returncode != LOST_EXIT:
            out["error"] = (f"rank 1 exited {p1.returncode}, expected the "
                            f"host-lost drill exit {LOST_EXIT}: "
                            f"{err1[-1500:]}")
            return 1
        if p0.returncode != 0:
            out["error"] = f"rank 0 failed: {err0[-2000:]}"
            return 1
        r0 = _last_json(out0)
        if not r0 or not r0.get("recovered") or not r0.get("finite"):
            out["error"] = f"rank 0 never ran elastic recovery: {r0}"
            return 1
        out.update(recovered=True, neval_resumed=r0["neval_resumed"],
                   world_after=r0["world_after"],
                   batch_after=r0["batch_after"], loss=r0["loss"])
        if r0["world_after"] != 1 or \
                r0["batch_after"] != 2 * args.batch:
            out["error"] = ("shrink did not preserve the global batch: "
                            f"{r0}")
            return 1
        # the survivor's trace must show the recovery next to the fault
        events = set()
        for tf in glob.glob(os.path.join(trace, "trace.*.json")):
            try:
                for ev in json.load(open(tf)).get("traceEvents", []):
                    if str(ev.get("name", "")).startswith("elastic."):
                        events.add(ev["name"])
            except ValueError:
                pass
        out["elastic_events"] = sorted(events)
        need = {"elastic.detect", "elastic.negotiate", "elastic.reform",
                "elastic.resume"}
        if not need <= events:
            out["error"] = f"missing elastic trace events: {need - events}"
            return 1
        # clean world-1 run from the SAME lineage entry at the rescaled
        # batch: final loss must match the recovered run bit-for-bit
        cargs = ["--ckpt-dir", ckpt, "--epochs", str(args.epochs),
                 "--batch", str(2 * args.batch), "--pace", "0",
                 "--resume-neval", str(r0["neval_resumed"])]
        if args.platform:
            cargs += ["--platform", args.platform]
        pc = _spawn(args, 0, {}, cargs)
        outc, errc = pc.communicate(timeout=args.timeout)
        if pc.returncode != 0:
            out["error"] = f"clean run failed: {errc[-2000:]}"
            return 1
        rc = _last_json(outc)
        out["clean_loss"] = rc["loss"]
        out["loss_match"] = bool(abs(rc["loss"] - r0["loss"]) < 1e-9)
        if not out["loss_match"]:
            out["error"] = (f"recovered loss {r0['loss']!r} != clean "
                            f"world-1 loss {rc['loss']!r}")
            return 1
        return 0
    except subprocess.TimeoutExpired as e:
        out["error"] = f"drill timed out: {e}"
        for p in ("p0", "p1", "pc"):
            proc = locals().get(p)
            if proc is not None and proc.poll() is None:
                proc.kill()
        return 1
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        out["error"] = f"{type(e).__name__}: {e}"
        return 1
    finally:
        print(json.dumps(out))
        sys.stdout.flush()
        if cleanup:
            shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
