"""Cross-process serving fleet (bigdl_tpu/serve/fleet + fleetfront).

The contract under test (docs/serving.md "Fleet"):
  - member records are CRC-framed: a torn/bit-rotted record reads as
    ABSENT (never half a registration), the newest verified generation
    wins, and the writer sweeps dead generations so a flapping member
    cannot grow the registry forever;
  - condemnation is a monotonic generation bump: records at or below
    the condemned generation are invisible to the registry, so a zombie
    can never attract traffic and a late verdict cannot un-condemn;
  - liveness is heartbeat publication freshness (the elastic-training
    silence rule): a registry record WITHOUT a fresh heartbeat is a
    stale entry, not a member;
  - the supervisor promotes silence into a typed MemberLostError,
    condemns, kills, respawns at generation+1 under backoff, and past
    the restart budget DEGRADES the slot instead of flapping;
  - the front tier routes by the TopologyRouter key over local
    in-flight counts, maps member HTTP rejections back to the typed
    serve exceptions, retries transport failures on the NEXT member
    (idempotent predicts only), and raises MemberLostError — a
    ReplicaLostError, so the HTTP 503 + Retry-After mapping applies —
    when no member is live;
  - DeployController detects a fleet target and fans the release out
    with the max-unavailable bound (rolling fleet mode);
  - THE acceptance drill (tools/fleet_smoke.py): kill -9, a wedged
    zombie, and a stale registry entry in one run, zero accepted loss.
"""

import json
import os
import threading
import time

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import Engine
from bigdl_tpu.optim import Predictor
from bigdl_tpu.serve import (DeployController, FleetFront, FleetSupervisor,
                             InferenceServer, MemberLostError, RequestTimeout,
                             ServeError, ServerOverloaded)
from bigdl_tpu.serve import fleet
from bigdl_tpu.serve.control import ReplicaLostError
from bigdl_tpu.utils import file_io

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert pred(), "condition not reached in time"


# ------------------------------------------------------------- registry


def test_member_record_roundtrip(tmp_path):
    d = str(tmp_path)
    path = fleet.publish_member(d, index=2, generation=3, pid=4242,
                                port=8011, devices=["cpu:0"],
                                buckets=[1, 2, 4], max_batch=4)
    rec = fleet.read_member(path)
    assert rec["index"] == 2 and rec["generation"] == 3
    assert rec["pid"] == 4242 and rec["port"] == 8011
    assert rec["buckets"] == [1, 2, 4] and rec["max_batch"] == 4
    assert fleet.read_registry(d) == {2: rec}


def test_torn_record_reads_absent(tmp_path):
    """A half-written or bit-rotted record fails the CRC frame and is
    invisible — a consumer can never act on half a registration."""
    d = str(tmp_path)
    good = fleet.publish_member(d, index=0, generation=2, pid=1, port=8000)
    blob = open(good, "rb").read()
    (tmp_path / "member.0.3").write_bytes(blob[: len(blob) // 2])  # torn
    flipped = bytearray(blob)
    flipped[len(blob) // 2] ^= 0xFF
    (tmp_path / "member.0.4").write_bytes(bytes(flipped))  # bit rot
    assert fleet.read_member(str(tmp_path / "member.0.3")) is None
    assert fleet.read_member(str(tmp_path / "member.0.4")) is None
    # the registry falls back to the newest VERIFIED generation
    assert fleet.read_registry(d)[0]["generation"] == 2


def test_registry_newest_generation_wins(tmp_path):
    d = str(tmp_path)
    for gen in (1, 2, 3):
        fleet.publish_member(d, index=0, generation=gen, pid=gen, port=8000)
    assert fleet.read_registry(d)[0]["generation"] == 3


def test_publish_sweeps_dead_generations(tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_FLEET_KEEP_GENERATIONS", "3")
    d = str(tmp_path)
    for gen in range(1, 9):
        fleet.publish_member(d, index=0, generation=gen, pid=gen, port=8000)
    names = sorted(n for n in os.listdir(d) if n.startswith("member."))
    assert names == ["member.0.6", "member.0.7", "member.0.8"]
    # other indices are untouched by this member's sweep
    fleet.publish_member(d, index=1, generation=1, pid=99, port=8001)
    assert (tmp_path / "member.0.8").exists()


def test_condemn_is_monotonic(tmp_path):
    d = str(tmp_path)
    assert fleet.condemned_generation(d, 0) == 0
    fleet.condemn(d, 0, 5)
    assert fleet.condemned_generation(d, 0) == 5
    fleet.condemn(d, 0, 3)  # a LATE verdict for an old generation
    assert fleet.condemned_generation(d, 0) == 5


def test_registry_skips_condemned_generations(tmp_path):
    d = str(tmp_path)
    for gen in (1, 2, 3):
        fleet.publish_member(d, index=0, generation=gen, pid=gen, port=8000)
    fleet.condemn(d, 0, 3)
    assert fleet.read_registry(d) == {}
    fleet.publish_member(d, index=0, generation=4, pid=4, port=8000)
    assert fleet.read_registry(d)[0]["generation"] == 4


def test_member_alive_is_publication_freshness(tmp_path):
    d = str(tmp_path)
    assert not fleet.member_alive(d, 0, lost_after=5.0)  # no heartbeat
    fleet.beat(d, 0, 2, 1, wall_time=1000.0)
    assert fleet.member_alive(d, 0, lost_after=5.0, now=1003.0)
    assert not fleet.member_alive(d, 0, lost_after=5.0, now=1006.0)
    # generation filter: an OLD life's heartbeat does not vouch for a
    # newer one
    assert not fleet.member_alive(d, 0, generation=3, lost_after=5.0,
                                  now=1001.0)
    assert fleet.member_alive(d, 0, generation=2, lost_after=5.0,
                              now=1001.0)


def test_sweep_numbered_retention(tmp_path):
    for i in (1, 3, 5, 7, 9):
        (tmp_path / f"grow.{i}").write_text("x")
    (tmp_path / "grow.2.corrupt").write_text("x")  # quarantine: kept
    (tmp_path / "other.4").write_text("x")
    removed = file_io.sweep_numbered(str(tmp_path), r"grow\.(\d+)", keep=2)
    assert sorted(removed) == ["grow.1", "grow.3", "grow.5"]
    left = sorted(os.listdir(tmp_path))
    assert left == ["grow.2.corrupt", "grow.7", "grow.9", "other.4"]
    # keep<=0 disables the sweep entirely
    assert file_io.sweep_numbered(str(tmp_path), r"grow\.(\d+)",
                                  keep=0) == []
    assert (tmp_path / "grow.7").exists()


def test_grow_offer_sweep_keeps_newest(tmp_path, monkeypatch):
    """elastic's grow-offer files ride the same bounded retention —
    and the sweep never touches the newest offer the scale-up
    negotiation reads."""
    monkeypatch.setenv("BIGDL_TPU_PROTOCOL_KEEP", "2")
    from bigdl_tpu.parallel import elastic
    d = str(tmp_path)
    for epoch in range(1, 6):
        elastic.publish_grow_offer(d, 0, epoch, [0, 1], float(epoch))
    names = sorted(n for n in os.listdir(elastic.elastic_dir(d))
                   if n.startswith("grow."))
    assert names == ["grow.4", "grow.5"]
    assert elastic.latest_grow_epoch(d) == 5


# ----------------------------------------------------------- supervisor


class _FakeProc:
    """A Popen stand-in the supervisor can poll/kill."""

    _pids = iter(range(30000, 40000))

    def __init__(self):
        self.pid = next(self._pids)
        self.returncode = None
        self.killed = False

    def poll(self):
        return self.returncode

    def kill(self):
        self.killed = True
        self.returncode = -9

    def terminate(self):
        self.returncode = -15

    def wait(self, timeout=None):
        return self.returncode


class _FakeMember:
    """A fake worker life: publishes its record, beats on a thread until
    told to go silent (the wedge) or killed."""

    def __init__(self, fleet_dir, index, generation):
        self.proc = _FakeProc()
        self.fleet_dir, self.index, self.generation = \
            fleet_dir, index, generation
        self._silent = threading.Event()
        fleet.publish_member(fleet_dir, index=index, generation=generation,
                             pid=self.proc.pid, port=8000 + index)
        fleet.beat(fleet_dir, index, generation, 0)
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _beat(self):
        count = 0
        while not self._silent.is_set() and self.proc.poll() is None:
            count += 1
            fleet.beat(self.fleet_dir, self.index, self.generation, count)
            self._silent.wait(0.03)

    def wedge(self):
        self._silent.set()


def test_supervisor_condemns_and_respawns_silent_member(tmp_path):
    d = str(tmp_path)
    lives = []

    def spawn(index, generation):
        lives.append(_FakeMember(d, index, generation))
        return lives[-1].proc

    sup = FleetSupervisor(d, spawn, members=1, lost_after_s=0.15,
                          poll_s=0.03, backoff_s=0.03, grace_s=5.0,
                          restart_budget=10)
    sup.start()
    try:
        _wait(lambda: sup.live_count() == 1)
        lives[0].wedge()  # publication silence; the process still "runs"
        _wait(lambda: len(lives) >= 2 and sup.live_count() == 1)
    finally:
        sup.stop(terminate=False)
    # the lost life was condemned (the bump a waking zombie exits on),
    # best-effort killed, and replaced at generation+1
    assert [m.generation for m in lives[:2]] == [1, 2]
    assert fleet.condemned_generation(d, 0) >= 1
    assert lives[0].proc.killed
    assert isinstance(sup.last_error, MemberLostError)
    assert sup.last_error.index == 0 and sup.last_error.generation == 1
    st = sup.stats()
    assert st["restarts"] >= 1 and st["degraded"] == 0
    assert fleet.read_registry(d)[0]["generation"] == lives[-1].generation


def test_supervisor_degrades_past_restart_budget(tmp_path):
    d = str(tmp_path)
    spawns = []

    def spawn(index, generation):  # never beats: every life is lost
        spawns.append(generation)
        return _FakeProc()

    sup = FleetSupervisor(d, spawn, members=1, lost_after_s=0.05,
                          poll_s=0.02, backoff_s=0.01, grace_s=0.05,
                          restart_budget=2)
    sup.start()
    try:
        _wait(lambda: sup.stats()["degraded"] == 1)
        n = len(spawns)
        time.sleep(0.1)  # degraded means NO further respawns
        assert len(spawns) == n
    finally:
        sup.stop(terminate=False)
    # budget=2 -> the first life + 2 respawns, then the slot degrades
    assert spawns == [1, 2, 3]
    assert not sup.healthy()
    st = sup.stats()
    assert st["slots"]["0"]["degraded"] and st["live"] == 0


def test_supervisor_degrades_at_once_when_a_worker_has_no_device(tmp_path):
    """A worker that exits EXIT_NO_DEVICE did not crash: it could not get
    the device it was told to use.  No respawn, no back-off — the slot
    degrades at its first loss and the error says what to look at."""
    d = str(tmp_path)
    spawns = []

    def spawn(index, generation):
        spawns.append(generation)
        proc = _FakeProc()
        proc.returncode = fleet.EXIT_NO_DEVICE
        return proc

    sup = FleetSupervisor(d, spawn, members=1, lost_after_s=0.05,
                          poll_s=0.02, backoff_s=0.01, grace_s=5.0,
                          restart_budget=5)
    sup.start()
    try:
        _wait(lambda: sup.stats()["degraded"] == 1)
        time.sleep(0.1)
    finally:
        sup.stop(terminate=False)
    assert spawns == [1]
    assert "could not get its device" in str(sup.last_error)


def test_worker_that_cannot_get_its_device_exits_with_the_error(tmp_path):
    """tools/serve_worker.py: the platform is explicit (cpu by default); a
    platform jax cannot start ends the process with EXIT_NO_DEVICE and the
    reason on stderr, before anything is published."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_worker.py"),
         "--fleet-dir", str(tmp_path), "--index", "0",
         "--platform", "no_such_platform"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == fleet.EXIT_NO_DEVICE, r.stderr[-800:]
    assert "cannot get a 'no_such_platform' device" in r.stderr
    assert fleet.read_registry(str(tmp_path)) == {}


def test_only_a_device_that_is_definitely_absent_skips_the_back_off(
        monkeypatch, tmp_path):
    """EXIT_NO_DEVICE is for what waiting cannot cure.  A TPU that is busy
    or still held by a dying predecessor raises at start-up too, and that
    worker must leave through the ordinary crash path (respawn, back-off)."""
    from tools import serve_worker
    busy = RuntimeError("Unable to initialize backend 'tpu': UNAVAILABLE: "
                        "TPU is already in use by process with pid 41")
    vendor = tmp_path / "vendor"
    monkeypatch.setattr(serve_worker.glob, "glob", lambda pat: [str(vendor)])
    vendor.write_text("0x1ae0\n")      # a Google accelerator on the bus
    assert not serve_worker.no_such_device("tpu", busy)
    vendor.write_text("0x8086\n")      # nothing but someone else's devices
    assert serve_worker.no_such_device("tpu", busy)
    assert not serve_worker.no_such_device("cpu", RuntimeError("boom"))
    assert serve_worker.no_such_device("cpu", RuntimeError(
        "Backend 'x' is not in the list of known backends: ['cpu']"))


def test_supervisor_spawns_past_ghost_heartbeat(tmp_path):
    """A returning supervisor must outrank BOTH the condemnation floor
    and any frozen heartbeat a previous run left behind (the elastic
    announce_join rule)."""
    d = str(tmp_path)
    fleet.condemn(d, 0, 3)
    fleet.beat(d, 0, 7, 42, wall_time=time.time() - 3600)  # stale ghost
    seen = []

    def spawn(index, generation):
        seen.append((index, generation))
        return _FakeProc()

    sup = FleetSupervisor(d, spawn, members=1, grace_s=30.0)
    sup._spawn(0)
    assert seen == [(0, 8)]
    assert sup.stats()["slots"]["0"]["generation"] == 8


def test_supervisor_stop_condemns_survivors(tmp_path):
    d = str(tmp_path)
    lives = []

    def spawn(index, generation):
        lives.append(_FakeMember(d, index, generation))
        return lives[-1].proc

    sup = FleetSupervisor(d, spawn, members=2, lost_after_s=5.0,
                          poll_s=0.02, grace_s=5.0)
    sup.start()
    _wait(lambda: sup.live_count() == 2)
    sup.stop()
    for idx in (0, 1):
        assert fleet.condemned_generation(d, idx) >= 1
    assert all(m.proc.poll() is not None for m in lives)


# ----------------------------------------------------------- front tier


def test_front_no_live_member_is_typed(tmp_path):
    front = FleetFront(str(tmp_path), refresh_s=0)
    assert not front.healthy()
    with pytest.raises(MemberLostError) as ei:
        front.submit(np.zeros((4,), np.float32))
    assert isinstance(ei.value, ReplicaLostError)  # -> HTTP 503 mapping
    assert ei.value.retry_after_s is not None
    front.close()


def test_front_ignores_stale_registry_entry(tmp_path):
    """A record without a fresh heartbeat — or from a condemned
    generation — can never attract traffic."""
    d = str(tmp_path)
    fleet.publish_member(d, index=7, generation=1, pid=1, port=9999)
    front = FleetFront(d, refresh_s=0, lost_after_s=0.5)
    assert front.members() == {}          # no heartbeat at all
    fleet.beat(d, 7, 1, 1, wall_time=time.time() - 60)
    assert front.members() == {}          # stale heartbeat
    fleet.publish_member(d, index=0, generation=2, pid=2, port=8000)
    fleet.beat(d, 0, 2, 1)
    assert sorted(front.members()) == [0]  # only the fresh member
    fleet.condemn(d, 0, 2)
    assert front.members() == {}          # condemned = gone
    front.close()


def test_front_typed_error_mapping():
    err = FleetFront._typed(429, {"error": "full", "retry_after_s": 2.5})
    assert isinstance(err, ServerOverloaded) and err.retry_after_s == 2.5
    assert isinstance(FleetFront._typed(504, {"error": "late"}),
                      RequestTimeout)
    assert isinstance(FleetFront._typed(400, {"error": "bad"}), ServeError)
    # 503/5xx are NOT terminal: the caller retries on the next member
    assert FleetFront._typed(503, {}) is None
    assert FleetFront._typed(500, {}) is None


def test_front_pick_routing_key(tmp_path):
    d = str(tmp_path)
    for i in (0, 1):
        fleet.publish_member(d, index=i, generation=1, pid=i, port=8000 + i,
                             max_batch=4)
        fleet.beat(d, i, 1, 1)
    front = FleetFront(d, refresh_s=0, lost_after_s=60)
    try:
        assert front._pick() == 0                    # tie -> lowest index
        front._inflight = {0: 9}
        assert front._pick() == 1                    # fewest pending
        assert front._pick(exclude={1}) == 0         # failover bound
        assert front._pick(exclude={0, 1}) is None   # exhausted
        front._inflight = {}
        front._deploying = {0}
        assert front._pick() == 1                    # in-swap deprioritized
        front._deploying = {0, 1}
        assert front._pick() == 0                    # ...but never excluded
    finally:
        front.close()


def test_front_swap_requires_path(tmp_path):
    d = str(tmp_path)
    fleet.publish_member(d, index=0, generation=1, pid=1, port=8000)
    fleet.beat(d, 0, 1, 1)
    front = FleetFront(d, refresh_s=0, lost_after_s=60)
    with pytest.raises(ServeError):
        front.swap({"params": {}})  # members load the path themselves
    front.close()


# ------------------------------------- front over real member processes


def _linear_model(seed=0):
    return nn.Sequential().add(nn.Linear(4, 3)).build(jax.random.key(seed))


def _start_member(tmp_path, index, server):
    """One in-process 'member': a real InferenceServer behind the stock
    HTTP handler, registered in the fleet dir."""
    import sys
    tools_dir = os.path.join(_REPO_ROOT, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import serve_http

    httpd = serve_http.serve_forever(server, "127.0.0.1", 0)
    port = httpd.server_address[1]
    d = str(tmp_path)
    fleet.publish_member(d, index=index, generation=1, pid=os.getpid(),
                         port=port, max_batch=server.max_batch)
    fleet.beat(d, index, 1, 1)
    return httpd


def test_front_end_to_end_route_retry_and_roll(tmp_path):
    """Two real members behind the front: bit-exact routing vs bulk
    Predictor, transport-failure failover onto the surviving member, and
    a rolling swap that lands the release on the whole fleet."""
    Engine.init()
    model = _linear_model(0)
    servers = [InferenceServer(_linear_model(0), max_wait_ms=2,
                               example=np.zeros((4,), np.float32)).start()
               for _ in range(2)]
    httpds = [_start_member(tmp_path, i, s) for i, s in enumerate(servers)]
    front = FleetFront(str(tmp_path), refresh_s=0, lost_after_s=3600,
                       retries=2, timeout_s=30)
    try:
        assert front.healthy() and sorted(front.members()) == [0, 1]
        rng = np.random.default_rng(7)
        x = rng.standard_normal((6, 4)).astype(np.float32)
        want = Predictor(model).predict(x)
        handles = [front.submit(row) for row in x]
        got = np.stack([h.result(timeout=30) for h in handles])
        # float32 survives the JSON round trip bit-for-bit
        np.testing.assert_array_equal(got, want)
        st = front.stats()
        assert st["replicas_live"] == 2
        assert sum(m["routed"] for m in st["fleet"]["members"].values()) == 6

        # rolling deploy: full swap (no canary) fans out to every member
        new = _snapshot_model(tmp_path / "model.new", seed=1)
        front.swap(str(tmp_path / "model.new"))
        want2 = Predictor(new).predict(x)
        np.testing.assert_array_equal(
            np.stack([front.predict(row, timeout=30) for row in x]), want2)
        assert front.stats()["canary"]["reason"] == "full_swap"

        # kill member 0's socket mid-fleet (close, not just stop — a
        # kill -9'd process refuses connections): the front retries the
        # transport failure on member 1 — no caller-visible error
        httpds[0].shutdown()
        httpds[0].server_close()
        np.testing.assert_array_equal(front.predict(x[0], timeout=30),
                                      want2[0])
        assert front.stats()["fleet"]["retried"] >= 1
    finally:
        front.close()
        for httpd in httpds:
            httpd.shutdown()
        for s in servers:
            s.stop()


def _snapshot_model(path, seed=1):
    m = _linear_model(seed)
    file_io.save({"params": m.params, "state": m.state}, str(path))
    return m


# -------------------------------------------- deploy controller (fleet)


class _StubFront:
    """Duck-typed fleet target: records the rolling-deploy kwargs the
    controller passes and answers a promoted canary."""

    fleet = True

    def __init__(self):
        self.swaps = []
        self.deploy = None
        self._vid = 1

    def attach_deploy(self, controller):
        self.deploy = controller

    def swap(self, source, canary_fraction=None, max_unavailable=None):
        self._vid += 1
        self.swaps.append((str(source), canary_fraction, max_unavailable))
        return self._vid

    def stats(self):
        return {"canary": {"version": self._vid, "state": "promoted",
                           "fraction": 0.25, "routed": 8, "total": 32}}

    def healthy(self):
        return True


def test_deploy_controller_fleet_mode(tmp_path):
    """A fleet-shaped server flips the controller into rolling mode: the
    max-unavailable bound rides every swap and the timeline records the
    fleet deploy."""
    from bigdl_tpu.serve import ReleasePublisher
    snap = _snapshot_model(tmp_path / "model.1")
    del snap
    pub = ReleasePublisher(str(tmp_path))
    pub.publish(str(tmp_path / "model.1"), neval=1)
    front = _StubFront()
    ctl = DeployController(front, str(tmp_path), canary_fraction=0.25,
                           poll_s=0.01, max_unavailable=2).start()
    try:
        _wait(lambda: ctl.stats()["promoted"] >= 1)
    finally:
        ctl.stop()
    assert ctl.fleet_mode
    assert front.swaps == [(str(tmp_path / "model.1"), 0.25, 2)]
    deployed = [e for e in ctl.versions()["timeline"]
                if e["action"] == "deployed"]
    assert deployed and deployed[0]["fleet"] is True


def test_deploy_controller_plain_server_unchanged(tmp_path):
    """A non-fleet target never sees the fleet kwarg (the PR 15 swap
    signature is untouched)."""
    from bigdl_tpu.serve import ReleasePublisher

    class _Plain:
        def __init__(self):
            self.kwargs = []
            self._vid = 1

        def swap(self, source, canary_fraction=None):
            self._vid += 1
            self.kwargs.append(canary_fraction)
            return self._vid

        def stats(self):
            return {"canary": {"version": self._vid, "state": "promoted"}}

    _snapshot_model(tmp_path / "model.1")
    pub = ReleasePublisher(str(tmp_path))
    pub.publish(str(tmp_path / "model.1"), neval=1)
    srv = _Plain()
    ctl = DeployController(srv, str(tmp_path), canary_fraction=0.25,
                           poll_s=0.01).start()
    try:
        _wait(lambda: ctl.stats()["promoted"] >= 1)
    finally:
        ctl.stop()
    assert not ctl.fleet_mode and srv.kwargs == [0.25]


# --------------------------------------------------- worker process (1)


@pytest.mark.slow
def test_worker_process_registers_and_exits_on_condemn(tmp_path):
    """One REAL worker process: registers with its bound port, beats,
    answers /v1/predict with the bulk-Predictor answer, and exits
    gracefully when its generation is condemned."""
    import subprocess
    import sys
    import urllib.request

    d = str(tmp_path / "fleet")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("BIGDL_TPU_ELASTIC", "BIGDL_TPU_CHAOS",
                                "BIGDL_TPU_TRACE"))}
    env.update(PYTHONPATH=_REPO_ROOT, JAX_PLATFORMS="cpu",
               BIGDL_TPU_PREFETCH_DEPTH="0", BIGDL_TPU_FLEET_HEARTBEAT="0.1")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_ROOT, "tools", "serve_worker.py"),
         "--fleet-dir", d, "--index", "0", "--generation", "1",
         "--model", "linear", "--platform", "cpu"],
        env=env, stdout=subprocess.PIPE, text=True)
    try:
        _wait(lambda: 0 in fleet.read_registry(d), timeout=120)
        _wait(lambda: fleet.member_alive(d, 0, generation=1, lost_after=5.0),
              timeout=30)
        rec = fleet.read_registry(d)[0]
        assert rec["pid"] == proc.pid and rec["port"] > 0
        body = json.dumps({"inputs": [0.0, 0.0, 0.0, 0.0]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{rec['port']}/v1/predict", data=body,
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert np.asarray(out["outputs"]).shape == (3,)
        fleet.condemn(d, 0, 1)
        assert proc.wait(timeout=30) == 0  # graceful condemned exit
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
