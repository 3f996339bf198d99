#!/usr/bin/env python
"""Two-workload train->publish->canary->serve drill: the ISSUE-20
zero-workload-specific-pipeline claim, end-to-end (a CPU drill).

ONE invocation runs BOTH production workloads through the IDENTICAL
generic chain — same Optimizer checkpoint/publish path, same
DeployController, same InferenceServer — with zero recommendation- or
text-specific branches anywhere in that chain:

1. Recommendation (wide-and-deep): two subprocess trainer ranks
   (``BIGDL_TPU_ELASTIC_WORLD=2``) stream synthetic Criteo shards
   through ``TabularToSample`` and train ``models/widedeep.WideDeep``.
   Rank 0 carries ``data.record=corrupt`` chaos on its reader (bounded
   quarantine under ``BIGDL_TPU_DATA_SKIP_BUDGET``); rank 1 carries
   ``host.lost@1=exit@1:3`` and dies mid-train — rank 0 must recover
   elastically and keep publishing.  The parent serves the lineage live
   (canary per release) under closed-loop traffic.

2. Text (token-id classifier): one trainer rank feeds the
   ``dataset/text.py`` chain (SentenceTokenizer -> Dictionary ->
   encoded ids) into a ``TextClassifier(vocab_size=...)`` and publishes
   the same way; the Dictionary ships beside the checkpoints.  The
   parent serves VARIABLE-LENGTH token requests over a
   (batch, seq)-bucket ladder, padded per request — no text-specific
   serving code, just ``seq_buckets``.

Asserted in one run, per workload: every published release reaches a
terminal outcome and the LAST one is promoted; every embedding table on
the SERVED version is resident at exactly 1/N per device under the
(1,2,2) fsdp×tp layout; served answers bit-match a bulk ``Predictor``
oracle loaded from the promoted snapshot (text: at the same padded
sequence bucket); ZERO requests dropped or errored.  Across workloads:
the serve-side span/counter track sets of the two traces are IDENTICAL
(same generic code paths), and a literal grep proves the optimizer /
publisher / DeployController / InferenceServer sources contain no
workload-specific branch.

Prints ONE JSON line; exit 0 iff every leg closed::

    {"metric": "workload_smoke", "ok": true,
     "recsys": {"published": ..., "promoted": ..., "table_fractions":
                [0.25, 0.25], "bit_match": true, ...},
     "text": {...}, "spans_equal": true, "generic_chain_clean": true}
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
import threading
import time

# runnable as `python tools/workload_smoke.py` from the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

LOST_EXIT = 117      # chaos.ExitAt.EXIT_CODE
SERVE_RANK = 7       # the parent's trace rank in both workload traces
SEQ_LADDER = (192, 256, 384)
TEXT_SEQ = 192       # training length (textclassifier conv needs >= 149)

# the generic chain: files that must contain ZERO workload-specific
# branches (checked by literal grep below)
GENERIC_FILES = ("bigdl_tpu/optim/optimizer.py",
                 "bigdl_tpu/serve/continuous.py",
                 "bigdl_tpu/serve/server.py")
WORKLOAD_WORDS = ("widedeep", "wide_deep", "recsys", "criteo",
                  "textclassifier", "text_classifier")


def _spec():
    """The drill's tabular schema — small tables, everything else the
    production default shape."""
    from bigdl_tpu.dataset import FeatureSpec
    return FeatureSpec(n_cat=4, n_dense=2, multihot_slots=2,
                       deep_buckets=512, wide_buckets=256)


def _widedeep(spec):
    from bigdl_tpu.models import WideDeep
    return WideDeep.from_spec(spec, embed_dim=8, hidden=(16,))


def _text_corpus(n=96, seed=0):
    """Deterministic 3-class corpus: class k docs carry the marker word
    ``markk`` often — learnable through the Dictionary chain."""
    import numpy as np
    rng = np.random.default_rng(seed)
    filler = [f"w{i}" for i in range(60)]
    docs, labels = [], []
    for i in range(n):
        k = i % 3
        body = [filler[int(j)] for j in rng.integers(0, 60, 60)]
        body += [f"mark{k}"] * 12
        order = rng.permutation(len(body))
        docs.append(" ".join(body[int(j)] for j in order))
        labels.append(k)
    return docs, labels


class _Pace:
    """Per-minibatch pacing so the elastic run outlives the peer-lost
    detection window (the drill's clock, not the model's)."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __call__(self, it):
        for x in it:
            if self.seconds:
                time.sleep(self.seconds)
            yield x


# ---------------------------------------------------------------------------
# trainer workers (subprocesses)
# ---------------------------------------------------------------------------

def _recsys_trainer(args) -> int:
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import (DataSet, SampleToMiniBatch,
                                   TabularToSample)
    from bigdl_tpu.optim import Adam, Optimizer, Trigger
    from bigdl_tpu.utils import recordio

    spec = _spec()
    paths = sorted(glob.glob(os.path.join(args.data_dir, "criteo.bd-*")))
    stream = DataSet.record_stream(paths)
    ds = (stream
          .transform(TabularToSample(spec)
                     >> SampleToMiniBatch(args.batch, drop_last=True))
          .transform(_Pace(args.pace)))

    opt = (Optimizer(_widedeep(spec), ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(args.epochs)))
    opt.set_checkpoint(args.ckpt_dir, Trigger.several_iteration(1),
                       publish=True, publish_every=args.publish_every)
    opt.optimize()
    plan = getattr(opt, "_elastic_plan", None)
    out = {"rank": args.rank, "workload": "recsys",
           "recovered": plan is not None,
           "neval_resumed": plan.neval if plan is not None else None,
           "published": (opt._publisher.published
                         if opt._publisher is not None else 0),
           "quarantined": recordio.quarantine_stats()["records"],
           "loss": float(opt.optim_method.hyper.get("loss", 0.0))}
    print(json.dumps(out), flush=True)
    return 0


def _text_trainer(args) -> int:
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import (DataSet, Dictionary, Sample,
                                   SampleToMiniBatch, SentenceTokenizer)
    from bigdl_tpu.models import TextClassifier
    from bigdl_tpu.optim import Adam, Optimizer, Trigger

    docs, labels = _text_corpus()
    tokenized = list(SentenceTokenizer()(iter(docs)))
    d = Dictionary(tokenized)
    d.save(args.ckpt_dir)  # the vocabulary ships beside the lineage
    samples = []
    for toks, k in zip(tokenized, labels):
        ids = d.encode(toks)[:TEXT_SEQ]
        ids = np.pad(ids, (0, TEXT_SEQ - len(ids)))
        samples.append(Sample(ids.astype(np.int32), np.int32(k)))
    ds = (DataSet.array(samples)
          .transform(SampleToMiniBatch(args.batch, drop_last=True))
          .transform(_Pace(args.pace)))

    model = TextClassifier(3, embed_dim=16, seq_len=TEXT_SEQ,
                           vocab_size=d.vocab_size())
    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_epoch(args.epochs)))
    opt.set_checkpoint(args.ckpt_dir, Trigger.several_iteration(1),
                       publish=True, publish_every=args.publish_every)
    opt.optimize()
    out = {"rank": args.rank, "workload": "text",
           "vocab": d.vocab_size(),
           "published": (opt._publisher.published
                         if opt._publisher is not None else 0),
           "loss": float(opt.optim_method.hyper.get("loss", 0.0))}
    print(json.dumps(out), flush=True)
    return 0


def _spawn(args, workload: str, rank: int, ckpt_dir: str, epochs: int,
           publish_every: int, extra_env: dict):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("BIGDL_TPU_ELASTIC", "BIGDL_TPU_CHAOS",
                                "BIGDL_TPU_TRACE", "BIGDL_TPU_SUPERVISE",
                                "BIGDL_TPU_DEPLOY", "BIGDL_TPU_DATA"))}
    env.update({"PYTHONPATH": _REPO_ROOT,
                "JAX_PLATFORMS": args.platform,
                "BIGDL_TPU_PREFETCH_DEPTH": "0",
                **extra_env})
    wargs = ["--worker", workload, "--rank", str(rank),
             "--ckpt-dir", ckpt_dir, "--data-dir", args.data_dir or "",
             "--epochs", str(epochs), "--batch", str(args.batch),
             "--pace", str(args.pace),
             "--publish-every", str(publish_every)]
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
                                        name="workload-smoke-traffic")

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


def _drain_controller(controller, published: int, timeout_s=150.0):
    """Wait until every published release reached a terminal outcome."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = controller.stats()
        terminal = st["promoted"] + st["rolled_back"] + st["rejected"]
        if terminal >= published and st["seen"] >= published:
            return st
        time.sleep(0.1)
    return controller.stats()


def _table_fractions(module, engine) -> list:
    """device_fraction per embedding table on the SERVED (placed)
    params — the 1/N-sharded-serving assertion."""
    from bigdl_tpu.utils import memstats
    placed = getattr(engine, "_placed", None)
    if placed is None:
        return []
    tables = memstats.embedding_table_bytes(module, placed[1]) or []
    return [t["device_fraction"] for t in tables]


def _serve_tracks(trace_dir: str):
    """(span names, counter tracks) emitted by the serving rank."""
    from bigdl_tpu.utils import telemetry
    merged = telemetry.merge_traces(trace_dir)
    spans, counters = set(), set()
    for e in merged["traceEvents"]:
        if int(e.get("pid", -1)) != SERVE_RANK:
            continue
        if e.get("ph") == "X":
            spans.add(e["name"])
        elif e.get("ph") == "C":
            counters.add(e["name"])
    return spans, counters


def _check_last_promoted(timeline) -> tuple:
    """-> (last_release, neval) or raises AssertionError."""
    last = max(e["release"] for e in timeline)
    terminal = [e for e in timeline if e["release"] == last and
                e["action"] in ("promoted", "rolled_back", "rejected")]
    if not terminal or terminal[-1]["action"] != "promoted":
        raise AssertionError(f"last release {last} did not promote: "
                             f"{terminal}")
    return last, terminal[-1]["neval"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu",
                    help="a CPU drill with several ranks: a chip belongs "
                         "to one process, so this never defaults to it")
    ap.add_argument("--worker", default=None,
                    choices=(None, "recsys", "text"))
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--text-epochs", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--pace", type=float, default=0.05)
    ap.add_argument("--publish-every", type=int, default=5)
    ap.add_argument("--text-publish-every", type=int, default=6)
    ap.add_argument("--lost-iter", type=int, default=3)
    ap.add_argument("--peer-lost", type=float, default=0.8)
    ap.add_argument("--canary-fraction", type=float, default=0.3)
    ap.add_argument("--timeout", type=int, default=300)
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        # the (1,2,2) layout needs >= 4 devices
        from bigdl_tpu.utils.platform import force_cpu
        force_cpu(8)
    elif args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    if args.worker == "recsys":
        return _recsys_trainer(args)
    if args.worker == "text":
        return _text_trainer(args)

    base = args.ckpt_dir or tempfile.mkdtemp(prefix="workload_smoke_")
    cleanup = args.ckpt_dir is None
    ckpt_rec = os.path.join(base, "ckpt_recsys")
    ckpt_txt = os.path.join(base, "ckpt_text")
    trace_rec = os.path.join(base, "trace_recsys")
    trace_txt = os.path.join(base, "trace_text")
    args.data_dir = os.path.join(base, "data")
    for d in (ckpt_rec, ckpt_txt, args.data_dir):
        os.makedirs(d, exist_ok=True)
    out = {"metric": "workload_smoke", "ok": False}
    procs = []
    try:
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu.dataset import (Dictionary,
                                       synthetic_criteo_records,
                                       write_criteo_shards)
        from bigdl_tpu.models import TextClassifier
        from bigdl_tpu.optim import Predictor
        from bigdl_tpu.parallel import LayoutSharding, MeshLayout
        from bigdl_tpu.serve import InferenceServer, fit_bucket, pad_tail
        from bigdl_tpu.serve.continuous import DeployController
        from bigdl_tpu.utils import file_io, telemetry
        from bigdl_tpu.utils.engine import Engine

        # --- leg 0: the generic chain is literally workload-free -------
        hits = []
        for rel in GENERIC_FILES:
            src = open(os.path.join(_REPO_ROOT, rel)).read().lower()
            hits += [f"{rel}:{w}" for w in WORKLOAD_WORDS if w in src]
        out["generic_chain_clean"] = not hits
        if hits:
            out["error"] = f"workload-specific branches found: {hits}"
            return 1

        import jax
        Engine.init()
        if jax.device_count() < 4:
            out["error"] = (f"need >= 4 devices for the (1,2,2) layout, "
                            f"have {jax.device_count()} — run with "
                            "XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=8")
            return 1
        layout = MeshLayout(1, 2, 2)
        layout.install(jax.devices()[:4])
        n_shards = layout.fsdp * layout.tp
        out["layout"] = {"fsdp": layout.fsdp, "tp": layout.tp,
                         "n_shards": n_shards}

        spec = _spec()
        write_criteo_shards(os.path.join(args.data_dir, "criteo.bd"),
                            128, shards=4, seed=11, spec=spec)

        # spawn ALL trainers up front; serve recsys live, text after
        common = {"BIGDL_TPU_ELASTIC_WORLD": "2",
                  "BIGDL_TPU_ELASTIC_PEER_LOST": str(args.peer_lost),
                  "BIGDL_TPU_SUPERVISE_PEER_STALE": str(args.peer_lost / 2),
                  "BIGDL_TPU_SUPERVISE_STEP": "20"}
        p_rec0 = _spawn(args, "recsys", 0, ckpt_rec, args.epochs,
                        args.publish_every,
                        {**common, "BIGDL_TPU_ELASTIC_RANK": "0",
                         "BIGDL_TPU_CHAOS": "data.record=corrupt@6,13",
                         "BIGDL_TPU_DATA_SKIP_BUDGET": "4"})
        p_rec1 = _spawn(args, "recsys", 1, ckpt_rec, args.epochs,
                        args.publish_every,
                        {**common, "BIGDL_TPU_ELASTIC_RANK": "1",
                         "BIGDL_TPU_CHAOS":
                             f"host.lost@1=exit@1:{args.lost_iter}"})
        p_txt = _spawn(args, "text", 0, ckpt_txt, args.text_epochs,
                       args.text_publish_every, {})
        procs = [p_rec0, p_rec1, p_txt]

        # ============ workload 1: recommendation, served LIVE ==========
        rec = {}
        out["recsys"] = rec
        tracer = telemetry.Tracer(trace_rec, rank=SERVE_RANK)
        telemetry.set_active(tracer)
        arch = _widedeep(spec).build(jax.random.key(7))
        queries = np.stack(
            [spec.featurize(r).feature for r in
             synthetic_criteo_records(32, seed=21, spec=spec)])
        server = InferenceServer(
            arch, max_batch=4, max_wait_ms=2, queue_limit=4096,
            example=queries[0],
            strategy=LayoutSharding(arch, min_size=0),
            canary_min_batches=3, canary_window=16,
            canary_latency_ratio=20.0).start()
        controller = DeployController(
            server, ckpt_rec, canary_fraction=args.canary_fraction,
            rollback_budget=3, poll_s=0.05,
            decision_timeout=60.0).start()
        traffic = _Traffic(server, queries).start()

        out1, err1 = p_rec1.communicate(timeout=args.timeout)
        out0, err0 = p_rec0.communicate(timeout=args.timeout)
        rec["rank0_rc"], rec["rank1_rc"] = \
            p_rec0.returncode, p_rec1.returncode
        if p_rec1.returncode != LOST_EXIT:
            out["error"] = (f"recsys rank 1 exited {p_rec1.returncode}, "
                            f"expected the host-lost exit {LOST_EXIT}: "
                            f"{err1[-1500:]}")
            return 1
        if p_rec0.returncode != 0:
            out["error"] = f"recsys rank 0 failed: {err0[-2000:]}"
            return 1
        r0 = _last_json(out0)
        if not r0 or not r0.get("recovered") or not r0.get("published"):
            out["error"] = f"recsys rank 0 never recovered/published: {r0}"
            return 1
        if not r0.get("quarantined"):
            out["error"] = ("data.record chaos left nothing quarantined: "
                            f"{r0}")
            return 1
        published = int(r0["published"])
        rec.update(published=published, recovered=True,
                   quarantined=r0["quarantined"], loss=r0["loss"])

        st = _drain_controller(controller, published)
        traffic.stop()
        rec.update({k: st[k] for k in ("seen", "promoted", "rolled_back",
                                       "rejected")})
        rec["traffic"] = {"submitted": traffic.submitted,
                          "served": traffic.served,
                          "errors": traffic.errors[:5]}
        terminal = st["promoted"] + st["rolled_back"] + st["rejected"]
        if terminal < published:
            out["error"] = (f"recsys controller consumed {terminal} of "
                            f"{published} releases in time: {st}")
            return 1
        timeline = controller.versions()["timeline"]
        last, neval = _check_last_promoted(timeline)
        rec["final_release"], rec["final_neval"] = last, neval

        # the SERVED tables are resident at exactly 1/N per device
        fracs = _table_fractions(server.version.module,
                                 server.version._engine)
        rec["table_fractions"] = fracs
        if len(fracs) != 2 or \
                any(f != round(1.0 / n_shards, 6) for f in fracs):
            out["error"] = (f"served embedding tables not 1/{n_shards}-"
                            f"sharded: {fracs}")
            return 1

        # served answers bit-match the promoted snapshot's bulk oracle
        blob = file_io.load(os.path.join(ckpt_rec, f"model.{neval}"))
        oracle = _widedeep(spec).build(jax.random.key(0))
        oracle.attach(blob["params"], blob["state"])
        # the oracle runs the SAME fsdp×tp-sharded program as serving —
        # bit-identity includes the sharded reduction order
        ref = Predictor(oracle, strategy=LayoutSharding(oracle, min_size=0))
        mismatches = sum(
            not np.array_equal(server.predict(queries[i], timeout=60),
                               ref.predict(queries[i:i + 1])[0])
            for i in range(8))
        rec["bit_match"] = mismatches == 0
        if mismatches:
            out["error"] = (f"recsys: {mismatches}/8 served answers "
                            "differ from the promoted snapshot oracle")
            return 1
        if traffic.errors or traffic.served != traffic.submitted:
            out["error"] = f"recsys dropped requests: {rec['traffic']}"
            return 1
        controller.stop()
        server.stop()
        tracer.close()

        # ====== workload 2: text, variable-length over the ladder ======
        txt = {}
        out["text"] = txt
        outt, errt = p_txt.communicate(timeout=args.timeout)
        txt["rc"] = p_txt.returncode
        if p_txt.returncode != 0:
            out["error"] = f"text trainer failed: {errt[-2000:]}"
            return 1
        rt = _last_json(outt)
        if not rt or not rt.get("published"):
            out["error"] = f"text trainer never published: {rt}"
            return 1
        published_t = int(rt["published"])
        txt.update(published=published_t, loss=rt["loss"],
                   vocab=rt["vocab"])

        # the Dictionary shipped beside the lineage round-trips (pinned
        # UNK contract) — serving sizes its oracle from IT
        d = Dictionary.load(ckpt_txt)
        if d.vocab_size() != rt["vocab"] or \
                d.unk_index() != d.vocab_size() - 1:
            out["error"] = (f"dictionary round-trip broke: vocab "
                            f"{d.vocab_size()} vs {rt['vocab']}")
            return 1

        tracer = telemetry.Tracer(trace_txt, rank=SERVE_RANK)
        telemetry.set_active(tracer)
        arch_t = TextClassifier(3, embed_dim=16, seq_len=TEXT_SEQ,
                                vocab_size=d.vocab_size()).build(
            jax.random.key(8))
        rng = np.random.default_rng(5)
        lengths = [160, 192, 250, 300, 384]
        tqueries = [rng.integers(0, d.vocab_size(),
                                 size=(n,)).astype(np.int32)
                    for n in lengths for _ in range(3)]
        server = InferenceServer(
            arch_t, max_batch=4, max_wait_ms=2, queue_limit=4096,
            seq_buckets=SEQ_LADDER,
            example=np.zeros((TEXT_SEQ,), np.int32),
            strategy=LayoutSharding(arch_t, min_size=0),
            canary_min_batches=3, canary_window=16,
            canary_latency_ratio=20.0).start()
        controller = DeployController(
            server, ckpt_txt, canary_fraction=args.canary_fraction,
            rollback_budget=3, poll_s=0.05,
            decision_timeout=60.0).start()
        traffic = _Traffic(server, tqueries).start()

        st = _drain_controller(controller, published_t)
        traffic.stop()
        txt.update({k: st[k] for k in ("seen", "promoted", "rolled_back",
                                       "rejected")})
        txt["traffic"] = {"submitted": traffic.submitted,
                          "served": traffic.served,
                          "errors": traffic.errors[:5]}
        terminal = st["promoted"] + st["rolled_back"] + st["rejected"]
        if terminal < published_t:
            out["error"] = (f"text controller consumed {terminal} of "
                            f"{published_t} releases in time: {st}")
            return 1
        timeline = controller.versions()["timeline"]
        last, neval = _check_last_promoted(timeline)
        txt["final_release"], txt["final_neval"] = last, neval

        fracs = _table_fractions(server.version.module,
                                 server.version._engine)
        txt["table_fractions"] = fracs
        if len(fracs) != 1 or fracs[0] != round(1.0 / n_shards, 6):
            out["error"] = (f"served text embedding table not "
                            f"1/{n_shards}-sharded: {fracs}")
            return 1

        # bit-match at the SAME padded sequence bucket the server used
        blob = file_io.load(os.path.join(ckpt_txt, f"model.{neval}"))
        oracle = TextClassifier(3, embed_dim=16, seq_len=TEXT_SEQ,
                                vocab_size=d.vocab_size()).build(
            jax.random.key(0))
        oracle.attach(blob["params"], blob["state"])
        ref = Predictor(oracle, strategy=LayoutSharding(oracle, min_size=0))
        mismatches = 0
        for i in range(len(lengths)):
            q = tqueries[i * 3]
            seq = fit_bucket(len(q), SEQ_LADDER)
            got = server.predict(q, timeout=60)
            want = ref.predict(pad_tail(q, seq)[None, :])[0]
            if not np.array_equal(got, want):
                mismatches += 1
        txt["bit_match"] = mismatches == 0
        if mismatches:
            out["error"] = (f"text: {mismatches}/{len(lengths)} served "
                            "answers differ from the oracle at the same "
                            "padded bucket")
            return 1
        if traffic.errors or traffic.served != traffic.submitted:
            out["error"] = f"text dropped requests: {txt['traffic']}"
            return 1
        controller.stop()
        server.stop()
        tracer.close()

        # ====== cross-workload: identical generic serving tracks =======
        spans_r, counters_r = _serve_tracks(trace_rec)
        spans_t, counters_t = _serve_tracks(trace_txt)
        out["serve_spans"] = sorted(spans_r)
        out["serve_counters"] = sorted(counters_r)
        out["spans_equal"] = (spans_r == spans_t
                              and counters_r == counters_t)
        if not out["spans_equal"]:
            out["error"] = ("the two workloads ran DIFFERENT serve "
                            f"tracks: spans {sorted(spans_r ^ spans_t)}, "
                            f"counters {sorted(counters_r ^ counters_t)}")
            return 1
        if "serve.batch" not in spans_r:
            out["error"] = f"no serve.batch spans recorded: {spans_r}"
            return 1

        out["ok"] = True
        return 0
    except subprocess.TimeoutExpired as e:
        out["error"] = f"drill timed out: {e}"
        return 1
    except Exception as e:  # noqa: BLE001 — one JSON line, always
        import traceback
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
        return 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        print(json.dumps(out))
        sys.stdout.flush()
        if cleanup:
            shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
