"""Continuous train->serve deployment (bigdl_tpu/serve/continuous.py).

The contract under test (docs/continuous.md):
  - ``file_io.watch_lineage`` yields new lineage entries in id order on
    any scheme, never yields ``.corrupt``/``.tmp`` names, and paces
    itself with the injectable clock/sleep (wall-clock-free here);
  - ``file_io.frame_fingerprint`` reads the integrity footer without the
    payload and pins a snapshot's identity into its release entry;
  - the publisher emits monotonic CRC-framed release entries (ids never
    reused, resumed from the directory, quarantined ids skipped) and the
    ``deploy.publish`` chaos point corrupts exactly the framed bytes;
  - the controller deploys only verified releases IN ORDER: corrupt or
    truncated entries, missing/rewritten snapshots (fingerprint
    mismatch) are quarantined + rejected typed, the next good release
    still deploys;
  - canary verdicts drive the state machine: promote resets the
    consecutive-rollback counter, rollbacks past the budget FREEZE the
    controller (healthy() False) instead of flapping;
  - the Optimizer's checkpoint path publishes releases (writer rank,
    every publish_every-th write), and an InferenceServer +
    DeployController serve the latest promoted release bit-for-bit;
  - the timeline rides stats()["deploy"], /v1/stats and /v1/versions,
    and the ``deploy`` counter track is a first-class trace_report
    section;
  - THE acceptance drill (tools/continuous_smoke.py): trainer and
    server as separate processes sharing only a lineage dir, all three
    chaos legs in one run, zero dropped requests.
"""

import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import Engine
from bigdl_tpu.optim import Predictor
from bigdl_tpu.serve import (DeployController, InferenceServer,
                             ReleasePublisher, ReleaseRejected,
                             read_release)
from bigdl_tpu.serve.continuous import RELEASE_PATTERN
from bigdl_tpu.utils import chaos, file_io, telemetry

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert pred(), "condition not reached in time"


def _snapshot(path, seed=0, din=6, dout=2):
    """A servable model snapshot blob on storage + the module that made
    it (the shape serve.swap loads: {"params", "state"})."""
    m = nn.Sequential().add(nn.Linear(din, dout)).build(
        jax.random.key(seed))
    file_io.save({"params": m.params, "state": m.state}, str(path))
    return m


class _StubServer:
    """Duck-typed swap/stats target for controller state-machine tests:
    records every swap, answers the canary summary the test scripts."""

    def __init__(self, default="promoted", decisions=None):
        self.swaps = []
        self.default = default
        self.decisions = dict(decisions or {})  # swap # -> state
        self.deploy = None
        self._vid = 1

    def attach_deploy(self, controller):
        self.deploy = controller

    def swap(self, source, canary_fraction=None):
        self._vid += 1
        self.swaps.append((str(source), canary_fraction))
        return self._vid

    def stats(self):
        state = self.decisions.get(len(self.swaps), self.default)
        return {"canary": {"version": self._vid, "state": state,
                           "reason": "scripted", "routed": 1, "total": 4}}


# ---------------------------------------------------------------------------
# watch_lineage + frame_fingerprint (utils/file_io.py)
# ---------------------------------------------------------------------------


def test_watch_lineage_local_order_and_filters(tmp_path):
    d = tmp_path / "lin"
    d.mkdir()
    (d / "release.2").write_bytes(b"b")
    (d / "release.1").write_bytes(b"a")
    (d / "release.3.corrupt").write_bytes(b"q")   # quarantined: invisible
    (d / "release.4.tmp").write_bytes(b"t")       # half-written: invisible
    got = []
    for n, p in file_io.watch_lineage(
            str(d), since=0, pattern=RELEASE_PATTERN, poll=0,
            sleep=lambda s: None, stop=lambda: len(got) >= 2):
        got.append((n, os.path.basename(p)))
    assert got == [(1, "release.1"), (2, "release.2")]
    # since= filters consumed ids; later entries picked up
    (d / "release.5").write_bytes(b"e")
    got2 = []
    for n, _p in file_io.watch_lineage(
            str(d), since=2, pattern=RELEASE_PATTERN, poll=0,
            sleep=lambda s: None, stop=lambda: len(got2) >= 1):
        got2.append(n)
    assert got2 == [5]


def test_watch_lineage_memory_scheme():
    d = f"memory://watch_lin_{os.getpid()}"
    fs = file_io.get_filesystem(d)
    fs.makedirs(d)
    fs.write_bytes(d + "/release.1", b"a")
    fs.write_bytes(d + "/release.7", b"b")
    got = []
    for n, p in file_io.watch_lineage(
            d, since=0, pattern=RELEASE_PATTERN, poll=0,
            sleep=lambda s: None, stop=lambda: len(got) >= 2):
        got.append(n)
        assert p.startswith("memory://")
    assert got == [1, 7]


def test_watch_lineage_idle_backoff_and_timeout(tmp_path):
    """Empty dir: the watch backs off on the injectable clock/sleep (no
    wall time burned) and ends after idle_timeout."""
    t = [0.0]
    delays = []

    def clock():
        return t[0]

    def sleep(s):
        delays.append(s)
        t[0] += max(s, 1e-3)

    out = list(file_io.watch_lineage(
        str(tmp_path / "nothing_here"), since=0,
        pattern=RELEASE_PATTERN, clock=clock, sleep=sleep,
        idle_timeout=1.0))
    assert out == []
    assert delays, "idle watch never slept"
    assert delays[1] > delays[0]          # exponential start
    assert max(delays) <= 2.0             # capped at IO_BACKOFF_MAX


def test_watch_lineage_absorbs_transient_remote_faults():
    """fail*2 inside the listing window: the IO retry layer absorbs the
    faults BELOW the watch — every release id still comes out, in order,
    none skipped, and nothing healthy gets quarantined."""
    d = f"memory://watch_chaos_{os.getpid()}"
    fs = file_io.get_filesystem(d)
    fs.makedirs(d)
    for i in (1, 2, 3):
        fs.write_bytes(f"{d}/release.{i}", b"r%d" % i)
    got = []
    with chaos.scoped("fs.remote=fail*2@2"):
        for n, _p in file_io.watch_lineage(
                d, since=0, pattern=RELEASE_PATTERN, poll=0,
                sleep=lambda s: None, stop=lambda: len(got) >= 4):
            got.append(n)
            if n == 3:  # keep publishing THROUGH the chaos window
                fs.write_bytes(f"{d}/release.4", b"r4")
    assert got == [1, 2, 3, 4]
    assert not [n for n in fs.listdir(d) if n.endswith(".corrupt")]


def test_watch_lineage_survives_retry_exhaustion():
    """A fault burst LONGER than the per-op retry budget: the failed
    listings read as empty polls (warn, not crash), and once the burst
    drains every id is yielded exactly once — no skips, no false
    quarantine, no dead watch."""
    d = f"memory://watch_burst_{os.getpid()}"
    fs = file_io.get_filesystem(d)
    fs.makedirs(d)
    fs.write_bytes(f"{d}/release.1", b"a")
    fs.write_bytes(f"{d}/release.2", b"b")
    got, polls = [], [0]

    def stop():
        polls[0] += 1
        assert polls[0] < 200, "watch never recovered from the burst"
        return len(got) >= 2

    # IO_RETRIES=3 -> 4 attempts per op: 8 faults = two full polls where
    # even the retried listing fails, then storage heals
    with chaos.scoped("fs.remote=fail*8@1"):
        for n, _p in file_io.watch_lineage(
                d, since=0, pattern=RELEASE_PATTERN, poll=0,
                sleep=lambda s: None, stop=stop):
            got.append(n)
    assert got == [1, 2]
    assert polls[0] > 2  # the burst really cost empty polls first
    assert not [n for n in fs.listdir(d) if n.endswith(".corrupt")]


def test_frame_fingerprint(tmp_path):
    p = tmp_path / "blob"
    file_io.save({"w": np.arange(8.0)}, str(p))
    fp = file_io.frame_fingerprint(str(p))
    assert fp is not None and len(fp) == 2
    length, crc = fp
    assert length == os.path.getsize(p) - 20  # footer = u64+u32+magic
    # rewriting the blob changes the fingerprint
    file_io.save({"w": np.arange(8.0) + 1}, str(p))
    assert file_io.frame_fingerprint(str(p)) != fp
    # legacy unframed files have none
    raw = tmp_path / "legacy"
    raw.write_bytes(pickle.dumps({"w": 1}))
    assert file_io.frame_fingerprint(str(raw)) is None


# ---------------------------------------------------------------------------
# the publisher
# ---------------------------------------------------------------------------


def test_publisher_entries_and_monotonic_ids(tmp_path):
    snap = tmp_path / "model.3"
    _snapshot(snap, seed=1)
    pub = ReleasePublisher(str(tmp_path))
    r1 = pub.publish(str(snap), neval=3, epoch=1,
                     metrics={"loss": 0.25})
    r2 = pub.publish(str(snap), neval=3)
    assert (r1, r2) == (1, 2)
    entry = read_release(str(tmp_path / "release.1"))
    assert entry["release_id"] == 1
    assert entry["neval"] == 3 and entry["epoch"] == 1
    assert entry["metrics"]["loss"] == 0.25
    assert entry["model_name"] == "model.3"
    assert tuple(entry["fingerprint"]) == \
        file_io.frame_fingerprint(str(snap))
    # a fresh publisher resumes AFTER every existing id — including
    # quarantined ones, which must never be reused
    (tmp_path / "release.2").rename(tmp_path / "release.2.corrupt")
    assert ReleasePublisher(str(tmp_path)).publish(
        str(snap), neval=4) == 3


def test_publisher_corrupt_chaos_point(tmp_path):
    """deploy.publish=corrupt@1 lands an entry whose CRC verification
    fails at the consumer — the mid-publish corruption drill."""
    snap = tmp_path / "model.1"
    _snapshot(snap)
    with chaos.scoped("deploy.publish=corrupt@1"):
        pub = ReleasePublisher(str(tmp_path))
        pub.publish(str(snap), neval=1)
        pub.publish(str(snap), neval=1)
    with pytest.raises(file_io.CorruptCheckpoint):
        read_release(str(tmp_path / "release.1"))
    read_release(str(tmp_path / "release.2"))  # next entry is clean


# ---------------------------------------------------------------------------
# the controller state machine (stub server: no jax, no threads beyond
# the controller's own)
# ---------------------------------------------------------------------------


def test_controller_lineage_walk_skips_bad_entries(tmp_path):
    """THE satellite walk: good release, truncated frame, quarantined
    entry, good release — only the good ones deploy, in order; the
    truncated one is quarantined with a typed rejection."""
    snap = tmp_path / "model.1"
    _snapshot(snap)
    pub = ReleasePublisher(str(tmp_path))
    pub.publish(str(snap), neval=1)                      # release.1 good
    payload = pickle.dumps({"format": "bigdl_tpu-release-v1"})
    framed = file_io.frame_bytes(payload)
    # a torn write: half the payload gone, footer intact -> the frame
    # declares more bytes than the file holds
    (tmp_path / "release.2").write_bytes(
        framed[len(payload) // 2:])
    # an already-quarantined entry: must never even be listed
    (tmp_path / "release.3.corrupt").write_bytes(framed)
    pub._next = 4
    pub.publish(str(snap), neval=2)                      # release.4 good
    srv = _StubServer()
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["promoted"] + ctl.stats()["rejected"]
              >= 3)
    finally:
        ctl.stop()
    st = ctl.stats()
    assert srv.deploy is ctl                   # attach_deploy happened
    assert [e["release"] for e in ctl.versions()["timeline"]
            if e["action"] == "deployed"] == [1, 4]
    rejected = [e for e in ctl.versions()["timeline"]
                if e["action"] == "rejected"]
    assert [e["release"] for e in rejected] == [2]
    assert rejected[0]["reason_type"] == "ReleaseRejected"
    assert (tmp_path / "release.2.corrupt").exists()
    assert st["healthy"] and st["promoted"] == 2 and st["rejected"] == 1


def test_controller_canary_promote_records_verdict(tmp_path):
    snap = tmp_path / "model.1"
    _snapshot(snap)
    ReleasePublisher(str(tmp_path)).publish(str(snap), neval=1)
    srv = _StubServer(default="promoted")
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0.25,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["promoted"] >= 1)
    finally:
        ctl.stop()
    assert srv.swaps[0][1] == 0.25             # canary fraction forwarded
    promoted = [e for e in ctl.versions()["timeline"]
                if e["action"] == "promoted"]
    assert promoted[0]["verdict"]["state"] == "promoted"
    assert ctl.stats()["consecutive_rollbacks"] == 0


def test_controller_rollback_budget_freezes(tmp_path):
    """Consecutive rollbacks past the budget freeze the controller:
    healthy() False, frozen timeline event, NO further releases consumed
    — fail-stop beats flapping a bad trainer into production."""
    snap = tmp_path / "model.1"
    _snapshot(snap)
    pub = ReleasePublisher(str(tmp_path))
    for i in range(5):
        pub.publish(str(snap), neval=i + 1)
    srv = _StubServer(default="rolled_back")
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0.25,
                           rollback_budget=2, poll_s=0.01).start()
    try:
        _wait(lambda: not ctl.healthy())
    finally:
        ctl.stop()
    st = ctl.stats()
    assert st["frozen"] and "consecutive canary rollbacks" in \
        st["frozen_reason"]
    assert st["rolled_back"] == 3              # budget 2 -> frozen on #3
    assert st["deployed"] == 3                 # releases 4, 5 never swap
    assert len(srv.swaps) == 3
    actions = [e["action"] for e in ctl.versions()["timeline"]]
    assert actions[-1] == "frozen"
    # a promote in between resets the counter (separate controller)
    srv2 = _StubServer(default="rolled_back", decisions={2: "promoted"})
    ctl2 = DeployController(srv2, str(tmp_path), canary_fraction=0.25,
                            rollback_budget=2, poll_s=0.01).start()
    try:
        _wait(lambda: not ctl2.healthy())
    finally:
        ctl2.stop()
    # rollback(1) promote(reset) rollback(1) rollback(2) rollback(3=freeze)
    assert ctl2.stats()["rolled_back"] == 4
    assert ctl2.stats()["promoted"] == 1
    assert len(srv2.swaps) == 5


def test_controller_rejects_rewritten_snapshot(tmp_path):
    """A snapshot rewritten AFTER publication (fingerprint mismatch)
    must never deploy — the elastic-recovery-rewrites-the-lineage case."""
    snap = tmp_path / "model.1"
    _snapshot(snap, seed=1)
    ReleasePublisher(str(tmp_path)).publish(str(snap), neval=1)
    _snapshot(snap, seed=2)                    # rewritten: new CRC
    srv = _StubServer()
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["rejected"] >= 1)
    finally:
        ctl.stop()
    ev = [e for e in ctl.versions()["timeline"]
          if e["action"] == "rejected"][0]
    assert "fingerprint" in ev["reason"]
    assert not srv.swaps
    assert (tmp_path / "release.1.corrupt").exists()


def test_controller_missing_snapshot_rejected(tmp_path):
    """A release whose snapshot was pruned/quarantined after publication
    is rejected typed, not crashed on."""
    snap = tmp_path / "model.9"
    _snapshot(snap)
    ReleasePublisher(str(tmp_path)).publish(str(snap), neval=9)
    snap.unlink()
    srv = _StubServer()
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["rejected"] >= 1)
    finally:
        ctl.stop()
    ev = [e for e in ctl.versions()["timeline"]
          if e["action"] == "rejected"][0]
    assert "does not exist" in ev["reason"]
    assert not srv.swaps


# ---------------------------------------------------------------------------
# the optimizer publish hook
# ---------------------------------------------------------------------------


def _tiny_optimizer(ckpt_dir, epochs=2, publish_every=2):
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Adam, Optimizer, Trigger

    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(32)]
    ds = DataSet.rdd(samples).transform(
        SampleToMiniBatch(16, drop_last=True))
    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                     nn.CrossEntropyCriterion())
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(epochs)))
    opt.set_checkpoint(str(ckpt_dir), Trigger.several_iteration(1),
                       publish=True, publish_every=publish_every)
    return opt


def test_optimizer_publishes_releases(tmp_path):
    """set_checkpoint(publish=True, publish_every=2): every 2nd snapshot
    write emits a verified release entry whose fingerprint matches the
    snapshot on disk."""
    opt = _tiny_optimizer(tmp_path / "ckpt")
    opt.optimize()
    # 2 epochs x 2 iterations + epoch-boundary writes, publish every 2nd
    # write -> releases 1..3 (write counts 1, 3, 5)
    assert opt._publisher is not None and opt._publisher.published == 3
    nevals = []
    for rid in (1, 2, 3):
        entry = read_release(str(tmp_path / "ckpt" / f"release.{rid}"))
        assert entry["release_id"] == rid
        mp = entry["model_path"]
        assert os.path.exists(mp)
        file_io.verify(mp)
        assert tuple(entry["fingerprint"]) == \
            file_io.frame_fingerprint(mp)
        assert "loss" in entry["metrics"]
        nevals.append(entry["neval"])
    assert nevals == sorted(nevals)


def test_optimizer_publish_async_write(tmp_path):
    """Async checkpoint writes publish from the write future — a release
    can never point at bytes that are not on storage yet."""
    opt = _tiny_optimizer(tmp_path / "ckpt")
    opt.checkpoint_async = True
    opt.optimize()
    # the final join guarantees the snapshots; the publish callbacks run
    # on write completion, so give the last one a beat
    _wait(lambda: os.path.exists(str(tmp_path / "ckpt" / "release.3")),
          timeout=10.0)
    for rid in (1, 2, 3):
        entry = read_release(str(tmp_path / "ckpt" / f"release.{rid}"))
        file_io.verify(entry["model_path"])
        assert tuple(entry["fingerprint"]) == \
            file_io.frame_fingerprint(entry["model_path"])


# ---------------------------------------------------------------------------
# live server integration: swap bit-match, stats, HTTP, trace section
# ---------------------------------------------------------------------------


def test_live_server_serves_last_promoted_release(tmp_path):
    """Real InferenceServer + controller: two published releases deploy
    in order (plain swaps) and the server then answers bit-for-bit what
    bulk Predictor computes from the LAST promoted snapshot."""
    Engine.init()
    _snapshot(tmp_path / "model.1", seed=1)
    m2 = _snapshot(tmp_path / "model.2", seed=2)
    pub = ReleasePublisher(str(tmp_path))
    pub.publish(str(tmp_path / "model.1"), neval=1)
    pub.publish(str(tmp_path / "model.2"), neval=2)
    arch = nn.Sequential().add(nn.Linear(6, 2)).build(jax.random.key(9))
    x = np.random.default_rng(3).normal(size=(8, 6)).astype(np.float32)
    server = InferenceServer(arch, example=x[0], max_batch=4).start()
    ctl = DeployController(server, str(tmp_path), canary_fraction=0,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["promoted"] >= 2)
        st = server.stats()
        assert st["deploy"]["healthy"] and st["deploy"]["promoted"] == 2
        assert st["version"] == 3              # initial=1, two swaps
        ref = np.stack([Predictor(m2).predict(x[i:i + 1])[0]
                        for i in range(len(x))])
        got = np.stack([server.predict(x[i]) for i in range(len(x))])
        assert np.array_equal(got, ref)
    finally:
        ctl.stop()
        server.stop()


def test_http_versions_and_stats(tmp_path):
    """/v1/versions exposes the model-version timeline + healthy/frozen
    state; /v1/stats carries the deploy block."""
    import urllib.request

    tools_dir = os.path.join(_REPO_ROOT, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import serve_http

    Engine.init()
    _snapshot(tmp_path / "model.1", seed=1)
    ReleasePublisher(str(tmp_path)).publish(str(tmp_path / "model.1"),
                                            neval=1)
    arch = nn.Sequential().add(nn.Linear(6, 2)).build(jax.random.key(0))
    server = InferenceServer(arch,
                             example=np.zeros((6,), np.float32)).start()
    httpd = serve_http.serve_forever(server, "127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return json.loads(r.read())

    ctl = None
    try:
        # no controller attached yet
        assert get("/v1/versions") == {"deploy": False, "timeline": [],
                                       "version": 1}
        ctl = DeployController(server, str(tmp_path), canary_fraction=0,
                               poll_s=0.01).start()
        _wait(lambda: ctl.stats()["promoted"] >= 1)
        v = get("/v1/versions")
        assert v["deploy"] and v["healthy"] and not v["frozen"]
        actions = [(e["release"], e["action"]) for e in v["timeline"]]
        assert (1, "deployed") in actions and (1, "promoted") in actions
        st = get("/v1/stats")
        assert st["deploy"]["healthy"] is True
        assert st["deploy"]["frozen"] is False
        assert st["deploy"]["last_release"] == 1
    finally:
        httpd.shutdown()
        if ctl is not None:
            ctl.stop()
        server.stop()


def test_deploy_counter_track_in_trace_report(tmp_path):
    """The deploy track is a first-class report section: publishes from
    the publisher, outcome totals from the controller, one merged
    timeline (tools/trace_report.py satellite)."""
    trace_dir = tmp_path / "trace"
    tracer = telemetry.Tracer(str(trace_dir), rank=0)
    telemetry.set_active(tracer)
    try:
        snap = tmp_path / "model.1"
        _snapshot(snap)
        pub = ReleasePublisher(str(tmp_path))
        pub.publish(str(snap), neval=1)
        pub.publish(str(snap), neval=2)
        srv = _StubServer()
        ctl = DeployController(srv, str(tmp_path), canary_fraction=0.5,
                               poll_s=0.01).start()
        try:
            _wait(lambda: ctl.stats()["promoted"] >= 2)
        finally:
            ctl.stop()
    finally:
        tracer.close()
        telemetry.set_active(None)
    breakdown = telemetry.phase_breakdown(
        telemetry.merge_traces(str(trace_dir)))
    dep = breakdown["deploy"]
    assert dep["published"] == 2
    assert dep["deployed"] == 2 and dep["promoted"] == 2
    assert dep["frozen"] == 0
    assert dep["events"] >= 6   # 2 publishes + 2 deploys + 2 promotes
    report = telemetry.format_report(breakdown)
    assert "deploy: " in report
    assert "instant events" in report


# ---------------------------------------------------------------------------
# THE acceptance drill
# ---------------------------------------------------------------------------


def test_continuous_drill_end_to_end(tmp_path):
    """THE acceptance drill (ISSUE 15): trainer (2 elastic subprocess
    ranks, rank 1 chaos-killed mid-train) and this server process share
    ONLY a lineage directory.  One run must show: the corrupt
    mid-publish entry skipped typed + quarantined, the host loss never
    interrupting the release feed, the latency-inflated canary rolled
    back exactly once, the LAST release promoted, the served model
    bit-matching its snapshot, and zero dropped requests — driven
    through tools/continuous_smoke.py, the CPU drill itself."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "continuous_smoke.py"),
         "--platform", "cpu", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no JSON from the drill:\n{proc.stderr[-3000:]}"
    out = json.loads(lines[-1])
    assert proc.returncode == 0, out
    assert out["ok"] is True
    assert out["rank1_rc"] == 117              # chaos ExitAt's drill code
    assert out["recovered"] is True            # elastic leg closed
    assert out["rejected"] >= 1                # corrupt publish skipped
    assert out["rolled_back"] == 1             # canary regression leg
    assert out["healthy"] and not out["frozen"]
    assert out["bit_match"] is True
    assert out["traffic"]["served"] == out["traffic"]["submitted"]
    assert not out["traffic"]["errors"]
    assert out["deploy_report"]["published"] == out["published"]
    # the quarantined corrupt entry is still on disk for forensics
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt",
                                       "release.2.corrupt"))
