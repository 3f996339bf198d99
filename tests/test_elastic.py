"""Elastic multi-host training: coordinated host-loss recovery
(bigdl_tpu.parallel.elastic + the supervisor/engine/optimizer wiring).

The failure mode under test is the one neither checkpoint lineage (PR 1)
nor stall supervision (PR 2) can reach alone: a peer HOST dies, every
surviving rank's next collective would hang forever, and recovering in
place is useless because the dead rank will never rejoin.  The elastic
subsystem turns the supervisor's stale-peer observation into a typed
PeerLostError, negotiates the newest lineage entry valid for every
survivor over pure file_io (no collectives), re-forms the topology over
the surviving slice with the global batch preserved, and resumes — the
BigDL driver's re-form-the-job semantics without a driver.
"""

import glob
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import Adam, Optimizer, Trigger
from bigdl_tpu.parallel import elastic
from bigdl_tpu.parallel.sharding import DataParallel, ShardedDataParallel
from bigdl_tpu.utils import chaos, file_io, telemetry
from bigdl_tpu.utils import supervisor as sup_mod
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.supervisor import Supervisor

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    chaos.clear()
    yield
    chaos.clear()
    sup_mod.set_active(None)
    telemetry.set_active(None)


def _write_lineage(path, nevals):
    for n in nevals:
        file_io.save_checkpoint(str(path), n,
                                {"params": {"w": np.arange(4.0) + n},
                                 "state": {}},
                                {"method": {}, "driver_state": {}})


# ---------------------------------------------------------------------------
# lineage negotiation (pure file_io — no jax backend needed)
# ---------------------------------------------------------------------------

def test_survey_lists_valid_entries_newest_first(tmp_path):
    _write_lineage(tmp_path, [3, 5, 8])
    assert elastic.survey(str(tmp_path)) == [8, 5, 3]


def test_survey_excludes_corrupt_entries_without_quarantining(tmp_path):
    _write_lineage(tmp_path, [3, 5])
    p = tmp_path / "model.5"
    data = p.read_bytes()
    p.write_bytes(data[:10] + bytes([data[10] ^ 0xFF]) + data[11:])
    assert elastic.survey(str(tmp_path)) == [3]
    # exclusion is an observation, not a mutation: whether 5 dies is the
    # CLUSTER's call during negotiation
    assert (tmp_path / "model.5").exists()


def test_negotiate_single_survivor_picks_newest(tmp_path):
    _write_lineage(tmp_path, [3, 5, 8])
    plan = elastic.negotiate(str(tmp_path), rank=0, survivors=[0],
                             epoch=1, timeout=0.1, poll=0.01)
    assert plan.neval == 8
    assert plan.model_path.endswith("model.8")
    assert plan.survivors == (0,)


def test_negotiate_disjoint_newest_entries(tmp_path):
    """Survivors whose newest entries differ (store visibility lag) must
    agree on the newest COMMON one, and the divergent tail must be
    quarantined so every later resume converges."""
    _write_lineage(tmp_path, [3, 5, 8])
    # rank 1 cannot see entry 8 yet; its published view is [5, 3]
    elastic.publish_lineage_view(str(tmp_path), 1, 2, [5, 3])
    plan = elastic.negotiate(str(tmp_path), rank=0, survivors=[0, 1],
                             epoch=2, timeout=1.0, poll=0.01)
    assert plan.neval == 5
    # the leader (rank 0) quarantined the tail: 8 is .corrupt now
    assert (tmp_path / "model.8.corrupt").exists()
    assert (tmp_path / "optimMethod.8.corrupt").exists()
    assert not (tmp_path / "model.8").exists()
    # a late/independent recovery now lands on the same entry
    assert elastic.survey(str(tmp_path))[0] == 5


def test_negotiate_corrupt_on_one_rank_skipped_cluster_wide(tmp_path):
    """An entry corrupt for ONE survivor must be skipped by everyone:
    it drops out of the intersection and the tail quarantine removes it
    from the shared lineage."""
    _write_lineage(tmp_path, [3, 5, 8])
    # rank 1 verified the lineage and found 8 corrupt on its mount
    elastic.publish_lineage_view(str(tmp_path), 1, 4, [5, 3])
    plan = elastic.negotiate(str(tmp_path), rank=0, survivors=[0, 1],
                             epoch=4, my_valid=[8, 5, 3],
                             timeout=1.0, poll=0.01)
    assert plan.neval == 5
    assert (tmp_path / "model.8.corrupt").exists()


def test_negotiate_empty_lineage_typed_failure_no_hang(tmp_path):
    """No snapshots anywhere -> typed ElasticNegotiationError, not a
    hang (driven with an injected clock: zero wall-time waiting)."""
    fake = {"t": 0.0}

    def clock():
        return fake["t"]

    def sleep(s):
        fake["t"] += s

    with pytest.raises(elastic.ElasticNegotiationError,
                       match="no checkpoint lineage entry"):
        elastic.negotiate(str(tmp_path), rank=0, survivors=[0, 1],
                          epoch=1, timeout=5.0, poll=0.5,
                          clock=clock, sleep=sleep)
    assert fake["t"] >= 5.0  # it waited for rank 1's view, then gave up


def test_negotiate_drops_silent_survivor_after_timeout(tmp_path):
    """A survivor that never publishes its view is dropped from the
    agreement (it is effectively lost too) instead of blocking forever."""
    _write_lineage(tmp_path, [3, 5])
    fake = {"t": 0.0}
    plan = elastic.negotiate(
        str(tmp_path), rank=0, survivors=[0, 1], epoch=1, timeout=2.0,
        poll=0.5, clock=lambda: fake["t"],
        sleep=lambda s: fake.__setitem__("t", fake["t"] + s))
    assert plan.neval == 5
    assert plan.survivors == (0,)


def test_joiner_divergent_tail_quarantined_then_adopted(tmp_path):
    """GROW negotiation from the JOINER's seat: the returning rank's
    previous life can hold lineage entries the survivor never saw
    (written in the instants before it died).  The widened set agrees on
    the newest COMMON entry, the leader (the lowest rank — a survivor)
    quarantines the divergent tail, and the joiner ADOPTS the agreed
    snapshot — never the reverse."""
    _write_lineage(tmp_path, [3, 5, 8])
    # survivor rank 0's published view: it never saw the joiner's 8
    elastic.publish_lineage_view(str(tmp_path), 0, 7, [5, 3])
    plan = elastic.negotiate(str(tmp_path), rank=1, survivors=[0, 1],
                             epoch=7, my_valid=[8, 5, 3],
                             timeout=1.0, poll=0.01)
    assert plan.neval == 5 and plan.survivors == (0, 1)
    assert plan.model_path.endswith("model.5")
    # the joiner is NOT the leader: the tail is still intact here...
    assert (tmp_path / "model.8").exists()
    # ...until the survivor's own negotiate call (same round) runs
    plan0 = elastic.negotiate(str(tmp_path), rank=0, survivors=[0, 1],
                              epoch=7, my_valid=[5, 3],
                              timeout=1.0, poll=0.01)
    assert plan0.neval == 5
    assert (tmp_path / "model.8.corrupt").exists()
    assert not (tmp_path / "model.8").exists()
    assert elastic.survey(str(tmp_path))[0] == 5  # every later resume agrees


def test_stale_intents_from_previous_rounds_ignored(tmp_path):
    elastic.publish_intent(str(tmp_path), 1, epoch=1, lost=[2],
                           wall_time=0.0)
    elastic.publish_intent(str(tmp_path), 2, epoch=3, lost=[0],
                           wall_time=0.0)
    intents = elastic.read_intents(str(tmp_path), min_epoch=2)
    assert list(intents) == [2]
    assert intents[2]["lost"] == [0]
    # own intent excluded
    assert elastic.read_intents(str(tmp_path), min_epoch=2,
                                exclude_rank=2) == {}


# ---------------------------------------------------------------------------
# detection: supervisor promotes publication silence to PeerLostError
# ---------------------------------------------------------------------------

def _lost_supervisor(ckpt, rank, wall, **kw):
    return Supervisor({}, peer_dir=os.path.join(ckpt, "heartbeats"),
                      rank=rank, world=2, peer_stale=5.0, peer_lost=10.0,
                      wall_clock=lambda: wall["now"], publish_interval=0.0,
                      lineage_dir=ckpt, poll_interval=0.05, **kw)


def test_peer_lost_promotion_raises_and_publishes_intent(tmp_path):
    """A peer whose heartbeat PUBLICATION goes silent past the elastic
    threshold -> PeerLostError async-raised into the supervised thread
    (carrying the lost ranks + recovery round) and an epoch-stamped
    intent file for the slower survivors."""
    ckpt = str(tmp_path)
    wall = {"now": 1000.0}
    dead = _lost_supervisor(ckpt, 1, wall)
    dead.beat("step")
    dead._publish_heartbeat()  # last sign of life from rank 1

    sup = _lost_supervisor(ckpt, 0, wall)
    caught = {}

    def worker():
        sup.beat("step")
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                time.sleep(0.01)
            caught["err"] = None
        except elastic.PeerLostError as e:
            caught["err"] = e

    t = threading.Thread(target=worker, name="elastic-supervised")
    t.start()
    time.sleep(0.1)
    sup.start()
    wall["now"] = 1030.0  # rank 1 publication-silent for 30s > 10s
    t.join(10)
    sup.stop()
    assert not t.is_alive(), "PeerLostError never landed"
    err = caught["err"]
    assert isinstance(err, elastic.PeerLostError)
    assert err.lost_ranks == (1,) and err.epoch == 1
    assert "host(s) [1]" in str(err)
    intents = elastic.read_intents(ckpt, min_epoch=1)
    assert intents[0]["lost"] == [1] and intents[0]["epoch"] == 1
    # accessors: beat-staleness and publication-loss views
    assert list(sup.stale_peers()) == [1]
    assert sup.lost_peers()[1] == pytest.approx(30.0)
    # reform() records the round and stops re-promoting the dead rank
    sup.reform(rank=0, world=1, epoch=1, lost=[1])
    assert sup.elastic_epoch == 1 and sup.stale_peers() == {}


def test_foreign_intent_converges_other_survivor(tmp_path):
    """A rank that has NOT yet observed the silence itself must promote
    as soon as another survivor's recover intent appears."""
    ckpt = str(tmp_path)
    wall = {"now": 50.0}
    # rank 1 already called recovery round 1 against lost rank 2
    elastic.publish_intent(ckpt, 1, epoch=1, lost=[2], wall_time=50.0)
    sup = Supervisor({}, peer_dir=os.path.join(ckpt, "heartbeats"),
                     rank=0, world=3, peer_stale=500.0, peer_lost=1000.0,
                     wall_clock=lambda: wall["now"], publish_interval=0.0,
                     lineage_dir=ckpt, poll_interval=0.05)
    caught = {}

    def worker():
        sup.beat("step")
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                time.sleep(0.01)
            caught["err"] = None
        except elastic.PeerLostError as e:
            caught["err"] = e

    t = threading.Thread(target=worker, name="elastic-follower")
    t.start()
    time.sleep(0.1)
    sup.start()
    t.join(10)
    sup.stop()
    assert not t.is_alive(), "intent convergence never fired"
    err = caught["err"]
    assert isinstance(err, elastic.PeerLostError)
    assert err.lost_ranks == (2,) and err.epoch == 1


def test_stale_peer_ages_on_telemetry_counter_track(tmp_path):
    """Stragglers-about-to-die show in traces: stale-peer ages land on
    the 'peers' counter track every monitor poll."""
    ckpt = str(tmp_path)
    wall = {"now": 100.0}
    seen = []
    dead = _lost_supervisor(ckpt, 1, wall)
    dead.beat("step")
    dead._publish_heartbeat()
    sup = _lost_supervisor(ckpt, 0, wall,
                           on_peer_stale=lambda r, age: seen.append((r,
                                                                     age)))
    tr = telemetry.Tracer(str(tmp_path / "trace"), rank=0)
    telemetry.set_active(tr)
    try:
        wall["now"] = 107.0  # stale (> 5s) but not lost (< 10s)
        sup._check_peers(log=True)
        sup._check_peers(log=True)
    finally:
        telemetry.set_active(None)
        tr.close()
    counters = [e for e in tr.events_tail(64)
                if e.get("ph") == "C" and e.get("name") == "peers"]
    assert counters and counters[-1]["args"]["stale_age_r1"] == \
        pytest.approx(7.0)
    # the programmatic callback fired ONCE (new stale episode), not per poll
    assert seen == [(1, pytest.approx(7.0))]


def test_heartbeat_publish_failure_counted_retried_monitor_survives():
    """Satellite: a transient store failure publishing heartbeat.<rank>
    is counted and re-attempted on the next poll — never allowed to kill
    the monitor or silently stop beats."""

    class FlakyFS:
        def __init__(self, fail_first):
            self.fail_first = fail_first
            self.writes = 0
            self.stored = {}

        def write_bytes(self, path, data):
            self.writes += 1
            if self.writes <= self.fail_first:
                raise IOError("store flake")
            self.stored[path] = data

        def read_bytes(self, path):
            return self.stored[path]

        def exists(self, path):
            return path in self.stored

        def isdir(self, path):
            return True

        def listdir(self, path):
            return [p.rsplit("/", 1)[-1] for p in self.stored]

        def makedirs(self, path):
            pass

        def rename(self, src, dst):
            self.stored[dst] = self.stored.pop(src)

        def remove(self, path):
            self.stored.pop(path, None)

    fs = FlakyFS(fail_first=5)  # first op: 4 attempts all fail; next: ok
    file_io.register_filesystem("elastictest", fs)
    prev = file_io.set_retry_timebase(lambda: 0.0, lambda s: None)
    try:
        sup = Supervisor({"step": 60.0},
                         peer_dir="elastictest://hb", rank=0, world=2,
                         publish_interval=0.0)
        sup.beat("step")
        sup._publish_heartbeat()  # fails after retries -> counted
        assert sup.heartbeat_errors == 1
        assert sup._last_publish is None  # next poll retries immediately
        sup._publish_heartbeat()  # attempt 5 fails, 6 succeeds
        assert sup.heartbeat_errors == 1
        assert fs.exists("elastictest://hb/heartbeat.0")
        blob = json.loads(fs.read_bytes("elastictest://hb/heartbeat.0"))
        assert "published" in blob and "time" in blob
    finally:
        file_io.set_retry_timebase(*prev)


def test_suspend_heartbeat_stops_publication(tmp_path):
    wall = {"now": 10.0}
    sup = _lost_supervisor(str(tmp_path), 0, wall)
    sup.beat("step")
    sup._publish_heartbeat()
    hb = os.path.join(str(tmp_path), "heartbeats", "heartbeat.0")
    first = open(hb).read()
    wall["now"] = 20.0
    sup.suspend_heartbeat()  # the host.lost drill's go-silent switch
    sup._publish_heartbeat()
    assert open(hb).read() == first


def test_supervisor_peer_returned_once_per_episode(tmp_path):
    """A lost peer's RETURN (a generation-bumped heartbeat) is observed
    exactly once per episode: on_peer_returned fires once, the rank
    parks in returned_peers() until reform(returned=...) re-admits it to
    the live watch — admission itself happens at the optimizer's next
    checkpoint boundary, never from the monitor thread."""
    ckpt = str(tmp_path)
    wall = {"now": 1000.0}
    dead = _lost_supervisor(ckpt, 1, wall)
    dead.beat("step")
    dead._publish_heartbeat()                        # generation-0 life
    seen = []
    sup = _lost_supervisor(
        ckpt, 0, wall,
        on_peer_returned=lambda r, g: seen.append((r, g)))
    sup._check_peers(log=True)                       # baseline generation
    sup.reform(rank=0, world=1, epoch=1, lost=[1])   # shrink completed
    sup._check_peers(log=True)                       # frozen file: not news
    assert seen == [] and sup.returned_peers() == {}
    elastic.announce_join(ckpt, 1, wall["now"])      # next life: gen 1
    sup._check_peers(log=True)
    sup._check_peers(log=True)                       # same episode: silent
    assert seen == [(1, 1)]
    assert sup.returned_peers() == {1: 1}
    sup.reform(rank=0, world=2, epoch=2, returned=[1])
    assert sup.returned_peers() == {}
    # re-admitted to the live watch: staleness applies to it again
    wall["now"] = 1020.0
    assert sup._check_peers(log=False)[1] == pytest.approx(20.0)


def test_reform_grace_holds_promotion_then_rearms(tmp_path):
    """Every member recompiles its jitted step right after a re-form; a
    compile can starve the monitor past a tight peer_lost threshold.
    reform() therefore arms a detection-grace window: silence inside it
    is observed, never promoted — and promotion re-arms after it."""
    ckpt = str(tmp_path)
    wall = {"now": 1000.0}
    dead = _lost_supervisor(ckpt, 1, wall)
    dead.beat("step")
    dead._publish_heartbeat()
    mono = {"t": 100.0}
    sup = _lost_supervisor(ckpt, 0, wall, clock=lambda: mono["t"])
    sup._thread_id = 1 << 30  # raise delivery is another test's business
    sup.reform(rank=0, world=2, epoch=0)             # arms the grace
    assert sup._promotion_grace_until == pytest.approx(
        100.0 + sup.reform_grace)
    wall["now"] = 1030.0                             # silent 30s > lost 10s
    sup._check_elastic(sup._check_peers(log=False))  # inside grace: held
    assert elastic.read_intents(ckpt, min_epoch=1) == {}
    assert not sup.peer_lost_pending()
    mono["t"] += sup.reform_grace + 0.1              # grace expired
    sup._check_elastic(sup._check_peers(log=False))  # now it promotes
    assert elastic.read_intents(ckpt, min_epoch=1)[0]["lost"] == [1]
    assert sup.peer_lost_pending()


# ---------------------------------------------------------------------------
# GROW: join intents, announcement hygiene, admission (pure file_io)
# ---------------------------------------------------------------------------

def test_join_intent_roundtrip_and_clear(tmp_path):
    ckpt = str(tmp_path)
    elastic.publish_join_intent(ckpt, 1, 5.0, generation=3)
    intents = elastic.read_join_intents(ckpt)
    assert intents[1]["generation"] == 3 and intents[1]["rank"] == 1
    # own intent excluded (a joiner never admits itself)
    assert elastic.read_join_intents(ckpt, exclude_rank=1) == {}
    elastic.clear_join_intent(ckpt, 1)
    assert elastic.read_join_intents(ckpt) == {}
    elastic.clear_join_intent(ckpt, 1)  # consuming twice is harmless


def test_announce_join_hygiene_and_generation_bump(tmp_path):
    """The returning rank's previous life left a frozen heartbeat and
    stale protocol files; announce_join must bump the heartbeat
    GENERATION past the old one, delete the stale recover./lineage.
    views, and record the grow floor BEFORE publishing the intent."""
    ckpt = str(tmp_path)
    hb_dir = tmp_path / "heartbeats"
    hb_dir.mkdir()
    (hb_dir / "heartbeat.1").write_text(json.dumps(
        {"rank": 1, "phase": "step", "count": 7, "time": 1.0,
         "published": 1.0, "generation": 3}))
    elastic.publish_intent(ckpt, 1, epoch=1, lost=[0], wall_time=1.0)
    elastic.publish_lineage_view(ckpt, 1, 1, [5, 3])
    elastic.publish_grow_offer(ckpt, 0, 2, [0, 1], 1.0)  # older episode
    info = elastic.announce_join(ckpt, 1, 9.0)
    assert info == {"generation": 4, "floor": 2}
    edir = tmp_path / "elastic"
    assert not (edir / "recover.1").exists()   # stale previous-life view
    assert not (edir / "lineage.1").exists()
    hb = json.loads((hb_dir / "heartbeat.1").read_text())
    assert hb["generation"] == 4 and hb["phase"] == "join"
    assert elastic.read_join_intents(ckpt)[1]["generation"] == 4
    # a genuinely NEW rank announces at generation 1 with no floor
    fresh = elastic.announce_join(str(tmp_path / "other"), 0, 9.0)
    assert fresh == {"generation": 1, "floor": 0}


def test_death_certificate_and_previous_generation(tmp_path):
    """A RETURNING rank (previous_generation is not None) must hold its
    announcement until a survivor's recovery round declares it lost — a
    generation-bumped fresh heartbeat would otherwise reset the very
    publication silence the loss is detected by, and the shrink this
    grow stacks on would never run."""
    ckpt = str(tmp_path)
    assert elastic.previous_generation(ckpt, 1) is None   # a NEW rank
    assert elastic.death_certificate(ckpt, 1) == 0        # not declared
    wall = {"now": 10.0}
    dead = _lost_supervisor(ckpt, 1, wall)
    dead.beat("step")
    dead._publish_heartbeat()
    assert elastic.previous_generation(ckpt, 1) == 0      # a previous life
    elastic.publish_intent(ckpt, 0, epoch=3, lost=[1], wall_time=10.0)
    assert elastic.death_certificate(ckpt, 1) == 3
    assert elastic.death_certificate(ckpt, 0) == 0        # not this rank
    # rounds at or below the grow floor are a PREVIOUS episode's news
    assert elastic.death_certificate(ckpt, 1, floor=3) == 0
    assert elastic.death_certificate(ckpt, 1, floor=2) == 3


def test_grow_offer_floor_and_wait_for_admission(tmp_path):
    ckpt = str(tmp_path)
    assert elastic.latest_grow_epoch(ckpt) == 0
    elastic.publish_grow_offer(ckpt, 0, 2, [0, 1], 1.0)
    elastic.publish_grow_offer(ckpt, 0, 5, [0, 2], 2.0)
    assert elastic.latest_grow_epoch(ckpt) == 5
    # newest offer above the floor NAMING the rank, or nothing
    assert elastic.read_grow_offer(ckpt, min_epoch=0, rank=1)["epoch"] == 2
    assert elastic.read_grow_offer(ckpt, min_epoch=2, rank=1) is None
    assert elastic.read_grow_offer(ckpt, min_epoch=0, rank=2)["epoch"] == 5
    got = elastic.wait_for_admission(ckpt, 2, floor=2, timeout=1.0,
                                     poll=0.01)
    assert got["epoch"] == 5 and got["survivors"] == [0, 2]
    # typed failure — never a hang — when no survivor answers (injected
    # clock: zero wall-time waiting)
    fake = {"t": 0.0}
    with pytest.raises(elastic.ElasticJoinError, match="no survivor"):
        elastic.wait_for_admission(
            ckpt, 7, floor=5, timeout=30.0, poll=1.0,
            clock=lambda: fake["t"],
            sleep=lambda s: fake.__setitem__("t", fake["t"] + s))
    assert fake["t"] >= 30.0


def test_cluster_position_reads_newest_loadable_driver_state(tmp_path):
    """cluster_position is the joiner's gate coordinate: the newest
    loadable snapshot's (epoch, neval) — stored already incremented to
    the NEXT iteration, the exact coordinate chaos.at_position
    publishes, so host.return@rank=@epoch:iteration gates line up."""
    assert elastic.cluster_position(str(tmp_path)) is None
    file_io.save_checkpoint(str(tmp_path), 4,
                            {"params": {}, "state": {}},
                            {"method": {},
                             "driver_state": {"epoch": 2, "neval": 5}})
    assert elastic.cluster_position(str(tmp_path)) == (2, 5)
    # an entry without a position is skipped, the older one still answers
    file_io.save_checkpoint(str(tmp_path), 9,
                            {"params": {}, "state": {}},
                            {"method": {}, "driver_state": {}})
    assert elastic.cluster_position(str(tmp_path)) == (2, 5)


def test_join_deferred_during_inflight_shrink(tmp_path, monkeypatch):
    """A join intent observed while a SHRINK promotion is pending must
    be DEFERRED (not dropped): re-forms never interleave.  Once the
    shrink's reform completes, the same boundary check raises the
    planned _ElasticJoinSignal — internal control flow that consumes no
    retry budget."""
    from bigdl_tpu.optim.optimizer import _ElasticJoinSignal
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_WORLD", "2")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_RANK", "0")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_PEER_LOST", "3600")
    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), _dataset(),
                     nn.CrossEntropyCriterion())
           .set_checkpoint(str(tmp_path), Trigger.every_epoch()))
    try:
        Engine.reform(world=1, rank=0, survivors=[0])  # post-shrink world
        elastic.publish_join_intent(str(tmp_path), 1, 0.0, generation=1)
        sup = Supervisor({}, peer_dir=os.path.join(str(tmp_path),
                                                   "heartbeats"),
                         rank=0, world=1, publish_interval=0.0)
        opt._sup = sup
        sup.hold_elastic()                    # an in-flight shrink round
        opt._check_join(None)                 # deferred: no signal
        sup.reform(rank=0, world=1, epoch=1, lost=[1])  # shrink done
        with pytest.raises(_ElasticJoinSignal) as ei:
            opt._check_join(None)
        assert ei.value.joiners == (1,)
        # an intent from THIS rank is excluded outright
        elastic.clear_join_intent(str(tmp_path), 1)
        elastic.publish_join_intent(str(tmp_path), 0, 0.0, generation=1)
        opt._check_join(None)                 # no signal
    finally:
        Engine.reset()


# ---------------------------------------------------------------------------
# re-form: Engine topology + sharding remap + batch rescale
# ---------------------------------------------------------------------------

def test_engine_logical_world_env_and_reform(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_WORLD", "2")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_RANK", "1")
    assert Engine.world() == 2 and Engine.rank() == 1
    assert Engine.elastic_active()
    assert Engine.data_shard_info() == (1, 2)
    assert not Engine.is_writer()
    # shrink to the surviving slice: rank 1 alone, keeping its id
    Engine.reform(world=1, rank=1, survivors=[1])
    assert Engine.world() == 1 and Engine.rank() == 1
    assert Engine.survivors() == (1,)
    assert Engine.data_shard_info() == (0, 1)
    assert Engine.is_writer()
    with pytest.raises(ValueError, match="not in survivors"):
        Engine.reform(rank=0, survivors=[1])
    Engine.reset()
    assert Engine._elastic is None


def test_engine_reform_device_subset_rebuilds_mesh():
    """In-process simulated host loss: reform over a device subset
    rebuilds the 1-D data mesh (8 virtual devices -> 4)."""
    import jax
    Engine.init()
    assert Engine.device_count() == 8
    mesh = Engine.reform(world=1, rank=0, survivors=[0],
                         devices=jax.devices()[:4])
    assert mesh.shape["data"] == 4
    assert Engine.mesh() is mesh


def test_mesh_reform_error_when_widened_world_breaks_shard_groups():
    """Widening must keep the non-data shard block intact or fail TYPED
    (MeshReformError) — never silently re-lay-out sharded parameters."""
    import jax
    from jax.sharding import Mesh

    from bigdl_tpu.parallel.layout import MeshReformError
    Engine.init()
    devs = jax.devices()
    narrow = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "fsdp"))
    # the happy widen: data 1 -> 2, the fsdp block of 4 preserved
    wide = Engine._reform_data_axis(narrow, devs[:8])
    assert wide.shape["data"] == 2 and wide.shape["fsdp"] == 4
    # 2x3 -> 8 devices: 8 % 3 != 0, the fsdp groups cannot survive
    mesh = Mesh(np.array(devs[:6]).reshape(2, 3), ("data", "fsdp"))
    with pytest.raises(MeshReformError, match="must divide"):
        Engine._reform_data_axis(mesh, devs[:8])
    # no data axis at all: nothing to widen
    flat = Mesh(np.array(devs[:4]), ("fsdp",))
    with pytest.raises(MeshReformError, match="no 'data' axis"):
        Engine._reform_data_axis(flat, devs[:8])


def test_sharding_remap_widens_zero_params_value_equal():
    """Grow direction: ZeRO slots sharded 1/1 re-place to 1/2 with
    identical values — the joiner-admission re-slice of _elastic_grow."""
    import jax
    from jax.sharding import Mesh

    strategy = ShardedDataParallel(min_size=1)
    one = Mesh(np.array(jax.devices()[:1]), ("data",))
    two = Mesh(np.array(jax.devices()[:2]), ("data",))
    params = {"w": np.arange(32.0, dtype=np.float32).reshape(4, 8),
              "b": np.arange(8.0, dtype=np.float32)}
    placed = strategy.remap(one, params)
    widened = strategy.remap(two, placed)
    assert widened["w"].sharding.mesh.shape["data"] == 2
    np.testing.assert_array_equal(np.asarray(widened["w"]), params["w"])
    np.testing.assert_array_equal(np.asarray(widened["b"]), params["b"])


def test_sharding_remap_reslices_zero_params():
    """ZeRO params sharded 1/N re-place to 1/N' on the shrunken mesh with
    identical values — the fused-buffer/slot re-slice the compiled-step
    rebuild relies on."""
    import jax
    from jax.sharding import Mesh

    strategy = ShardedDataParallel(min_size=1)
    big = Mesh(np.array(jax.devices()[:8]), ("data",))
    small = Mesh(np.array(jax.devices()[:4]), ("data",))
    params = {"w": np.arange(64.0, dtype=np.float32).reshape(8, 8),
              "b": np.arange(8.0, dtype=np.float32)}
    placed = strategy.remap(big, params)
    assert placed["w"].sharding.mesh.shape["data"] == 8
    replaced = strategy.remap(small, placed)
    assert replaced["w"].sharding.mesh.shape["data"] == 4
    np.testing.assert_array_equal(np.asarray(replaced["w"]), params["w"])
    np.testing.assert_array_equal(np.asarray(replaced["b"]), params["b"])
    # DataParallel remap lands replicated on the new mesh
    rep = DataParallel().remap(small, placed)
    assert rep["w"].sharding.is_fully_replicated


def _dataset(n=64, batch=16):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(n)]
    return DataSet.array(samples).transform(
        SampleToMiniBatch(batch, drop_last=True))


def test_rescale_batches_ceil_rounding_rule():
    """Global batch preserved across the shrink: per-host batch becomes
    ceil(B*W/W') — it may GROW by up to W'-1 rows, never shrink."""
    opt = Optimizer(nn.Sequential().add(nn.Linear(6, 2)), _dataset(),
                    nn.CrossEntropyCriterion())
    opt._rescale_batches(4, 2)           # 16*4=64 over 2 -> 32
    b = opt._find_batchers(opt.dataset)[0]
    assert b.batch_size == 32
    opt._rescale_batches(2, 3)           # 32*2=64 over 3 -> ceil = 22
    assert b.batch_size == math.ceil(64 / 3) == 22
    opt._rescale_batches(3, 3)           # no-op on equal worlds
    assert b.batch_size == 22


def test_rescale_batches_grow_restores_configured_value():
    """The grow invariant: after a shrink DOUBLES the per-host batch, a
    grow back to the original world returns it exactly to the configured
    value (the shrink/grow round-trip is lossless), and ceil rounding
    applies in the grow direction too."""
    opt = Optimizer(nn.Sequential().add(nn.Linear(6, 2)), _dataset(),
                    nn.CrossEntropyCriterion())
    b = opt._find_batchers(opt.dataset)[0]
    opt._rescale_batches(2, 1)           # shrink: 16*2=32 over 1
    assert b.batch_size == 32
    opt._rescale_batches(1, 2)           # grow back: 32 over 2 -> 16
    assert b.batch_size == 16
    opt._rescale_batches(2, 3)           # widen past it: ceil(32/3) = 11
    assert b.batch_size == math.ceil(32 / 3) == 11


# ---------------------------------------------------------------------------
# acceptance: armed-but-no-fault bit-identity + the 2-rank drill
# ---------------------------------------------------------------------------

def _train_losses(tmp_path, tag):
    from bigdl_tpu.common import set_seed
    set_seed(11)
    losses = []
    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), _dataset(),
                     nn.CrossEntropyCriterion())
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(2))
           .set_checkpoint(str(tmp_path / tag), Trigger.every_epoch()))
    orig = opt._observe_loss
    opt._observe_loss = lambda lossf, state: losses.append(
        orig(lossf, state)) or losses[-1]
    trained = opt.optimize()
    import jax
    params = [np.asarray(l).tobytes() for l in jax.tree.leaves(
        trained.params)]
    return losses, params


def test_elasticity_armed_no_fault_bit_identical(tmp_path, monkeypatch):
    """Acceptance bound: arming elasticity (threshold + supervision)
    with no fault must leave training bit-identical to an unarmed run —
    the subsystem watches, it never touches the math."""
    base_losses, base_params = _train_losses(tmp_path, "plain")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_PEER_LOST", "60")
    monkeypatch.setenv("BIGDL_TPU_SUPERVISE_STEP", "120")
    armed_losses, armed_params = _train_losses(tmp_path, "armed")
    assert armed_losses == base_losses
    assert armed_params == base_params


def test_optimizer_elastic_recover_in_process_zero(tmp_path, monkeypatch):
    """Optimizer-level recovery without subprocesses: a logical world-2
    run under ShardedDataParallel checkpoints, a staged PeerLostError
    drives _elastic_recover, and the run RE-TRAINS to completion on the
    shrunken world with the per-host batch rescaled — proving the jitted
    step, ZeRO slices, and fused-buffer specs rebuild against the
    re-formed topology."""
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_WORLD", "2")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_RANK", "0")
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_PEER_LOST", "3600")
    ds = _dataset(n=128, batch=16)
    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                     nn.CrossEntropyCriterion(),
                     strategy=ShardedDataParallel(min_size=1))
           .set_optim_method(Adam(1e-2))
           .set_end_when(Trigger.max_epoch(2))
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(1)))
    opt.optimize()
    assert file_io.latest_checkpoint(str(tmp_path)) is not None
    assert Engine.data_shard_info() == (0, 2)  # fed half the corpus

    elastic.set_last_peer_lost("host 1 gone", [1], 1)
    err = elastic.PeerLostError()
    opt._elastic_recover(err)
    plan = opt._elastic_plan
    assert plan.neval == file_io.latest_checkpoint(str(tmp_path))[2]
    assert Engine.world() == 1 and Engine.survivors() == (0,)
    assert opt._find_batchers(opt.dataset)[0].batch_size == 32
    assert opt._compiled is None  # the old-world step is torn down

    # the shrunken world trains to the (restored) end trigger: the
    # compiled step rebuilt with the new shardings and batch shape
    opt.set_end_when(Trigger.max_epoch(3))
    trained = opt.optimize()
    import jax
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(trained.params))
    assert Engine.data_shard_info() == (0, 1)  # full corpus now


def test_elastic_drill_two_ranks_end_to_end(tmp_path):
    """THE acceptance drill (ISSUE 8): 2 subprocess CPU ranks, chaos
    host.lost@1 kills rank 1 mid-epoch; rank 0 detects, negotiates,
    shrinks to world=1 with the global batch preserved, resumes from the
    negotiated entry with elastic.* events in its trace, and its final
    loss bit-matches a clean world-1 run from the same entry.  Driven
    through tools/elastic_smoke.py, the CPU drill itself."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "elastic_smoke.py"),
         "--platform", "cpu", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no JSON from the drill:\n{proc.stderr[-3000:]}"
    out = json.loads(lines[-1])
    assert proc.returncode == 0, out
    assert out["recovered"] is True
    assert out["world_after"] == 1
    assert out["batch_after"] == 32          # 16 x 2 ranks, preserved
    assert out["rank1_rc"] == 117            # chaos ExitAt's drill code
    assert out["loss_match"] is True
    assert {"elastic.detect", "elastic.negotiate", "elastic.reform",
            "elastic.resume"} <= set(out["elastic_events"])
    # the drill rolled back to a real lineage entry
    assert out["neval_resumed"] >= 1
    snaps = glob.glob(os.path.join(str(tmp_path), "ckpt", "model.*"))
    assert snaps, "drill left no lineage behind"


def test_elastic_grow_drill_two_ranks_end_to_end(tmp_path):
    """THE acceptance drill (ISSUE 16): kill-then-return in ONE run.
    Chaos kills rank 1 mid-epoch (world 2 -> 1, per-host batch doubles);
    the same rank re-spawns as a joiner, waits for its own death
    certificate, announces via host.return@1 chaos gating, and is
    admitted at the next checkpoint boundary (world 1 -> 2, batch back
    down).  The release feed must stay gap-free across BOTH resizes with
    promotions after the grow, and both ranks must bit-match a clean
    world-2 run resumed from the join snapshot.  Driven through
    tools/elastic_smoke.py --grow, the CPU drill itself."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "elastic_smoke.py"),
         "--grow", "--platform", "cpu", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no JSON from the drill:\n{proc.stderr[-3000:]}"
    out = json.loads(lines[-1])
    assert proc.returncode == 0, out
    assert out["recovered"] is True and out["joined"] is True
    assert out["rank1_rc"] == 117            # chaos ExitAt's drill code
    # the survivor lived through shrink THEN grow, batch 16 -> 32 -> 16
    assert [h["kind"] for h in out["history_rank0"]] == ["shrink", "grow"]
    assert [h["world"] for h in out["history_rank0"]] == [1, 2]
    assert [h["batch"] for h in out["history_rank0"]] == [32, 16]
    assert [h["kind"] for h in out["history_joiner"]] == ["join"]
    # both ranks' final params bit-match the clean world-2 resume
    assert out["loss_match"] is True
    # the deployment loop never saw a gap or a rejection, and promoted
    # a release published AFTER the grow
    assert out["release_gap_free"] is True and out["rejected"] == 0
    assert out["promoted_after_grow"] >= 1
    for events in out["elastic_events"].values():
        assert {"elastic.join", "elastic.agree", "elastic.reform",
                "elastic.resume"} <= set(events)
