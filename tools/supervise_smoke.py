#!/usr/bin/env python
"""Supervision smoke: prove the stall-watchdog end-to-end on any backend.

Runs a short Linear-model fit with a deterministic chaos ``step.stall``
injected mid-run and supervision armed (step deadline << stall length).
PASS means the whole loop closed: the supervisor detected the hang,
wrote a crash report (all-thread stacks + heartbeat timeline) next to
the checkpoint dir, raised the typed StallError into the optimizer's
retry machinery, and the run recovered from the checkpoint lineage and
completed.  Prints ONE JSON line:

    {"metric": "supervise_smoke", "recovered": true, "stalls": 1,
     "report": "<path>", "report_threads": N, ...}

A CPU drill of the supervision machinery; safe anywhere (tiny model,
seconds of wall clock).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

# runnable as `python tools/supervise_smoke.py` from the repo root (the
# usual invocation): sys.path[0] is tools/, so add the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--step-deadline", type=float, default=0.5)
    ap.add_argument("--stall-seconds", type=float, default=30.0)
    ap.add_argument("--stall-at", type=int, default=5,
                    help="1-based minibatch count to hang at")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint/report dir (default: a temp dir)")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Adam, Optimizer, Trigger
    from bigdl_tpu.utils import chaos

    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="supervise_smoke_")
    cleanup = args.ckpt_dir is None
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(64)]
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(16, drop_last=True))

    out = {"metric": "supervise_smoke", "recovered": False, "stalls": 0,
           "report": None, "step_deadline": args.step_deadline}
    try:
        with chaos.scoped(
                f"step.stall=stall*{args.stall_seconds}@{args.stall_at}"):
            opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                             nn.CrossEntropyCriterion())
                   .set_optim_method(Adam(1e-2))
                   .set_end_when(Trigger.max_epoch(2))
                   .set_checkpoint(ckpt, Trigger.several_iteration(1))
                   .set_supervision(step=args.step_deadline))
            trained = opt.optimize()
        import jax
        finite = all(np.all(np.isfinite(np.asarray(leaf)))
                     for leaf in jax.tree.leaves(trained.params))
        reports = sorted(glob.glob(os.path.join(ckpt, "crash_report*.json")))
        out["stalls"] = len(reports)
        out["recovered"] = bool(finite and reports)
        if reports:
            out["report"] = reports[0]
            with open(reports[0]) as f:
                rep = json.load(f)
            out["report_threads"] = len(rep.get("threads", {}))
            out["report_timeline"] = len(rep.get("timeline", []))
            out["report_phase"] = rep.get("phase")
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        if cleanup:
            shutil.rmtree(ckpt, ignore_errors=True)
    print(json.dumps(out))
    sys.stdout.flush()
    return 0 if out["recovered"] else 1


if __name__ == "__main__":
    sys.exit(main())
