"""Serving scale-out (serve/autoscale.py, serve/router.py,
serve/tracefile.py): queue-driven autoscaling, topology-aware routing,
recorded-traffic replay.

The scale-out contract under test (docs/serving.md "Scale-out"):
  - the autoscaler grows the pool on sustained over-target queue wait
    (hysteresis + cooldown, never past max), shrinks it one step per
    sustained idle window (never below min), and freezes entirely on an
    unhealthy pool;
  - a pool shrink loses zero accepted requests: a condemned replica's
    requeued batch goes back to the queue HEAD and is never evicted
    below its original admission priority;
  - scale-up takes the warm spawn path — zero fresh lowers with the AOT
    executable cache armed (plain server AND router members);
  - the topology router places replicas on DISJOINT device subsets
    (typed PlacementError otherwise), routes by (bucket, per-replica
    queue depth), answers bit-identical to bulk Predictor.predict, and
    degrades to the surviving members on replica loss;
  - traces round-trip through the CRC-framed recordio format, replay
    with open-loop pacing, and reduce to per-tenant / per-priority SLO
    attainment with real errors in their own bucket;
  - replay acceptance: under a pinned per-batch service time, the
    autoscaled pool's attainment is STRICTLY higher than the fixed
    1-replica pool's on the same trace.
"""

import json
import os
import time

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import Engine
from bigdl_tpu.optim import Predictor
from bigdl_tpu.serve import (AutoScaler, DynamicBatcher, InferenceServer,
                             PlacementError, ServerOverloaded,
                             TopologyRouter, TraceEvent, TraceFormatError,
                             plan_subsets, read_trace, replay,
                             resolve_outcomes, slo_report, write_trace)
from bigdl_tpu.utils import chaos


def _linear_model(seed=0, din=4, dout=3):
    return nn.Sequential().add(nn.Linear(din, dout)).build(
        jax.random.key(seed))


def _rows(n, din=4, seed=0):
    return np.random.default_rng(seed).normal(size=(n, din)) \
        .astype(np.float32)


def _stall_spec(seconds, n=2000):
    counts = ",".join(str(i) for i in range(1, n + 1))
    return f"serve.batch=stall*{seconds}@{counts}"


# ------------------------------------------------- autoscaler decisions


class _StubPool:
    """Scripted scale-protocol target: pure controller-logic tests."""

    def __init__(self, replicas=1):
        self.replicas = replicas
        self.depth = 0
        self.row_s = None
        self.batches = 0
        self._healthy = True
        self.calls = []

    def healthy(self):
        return self._healthy

    def autoscale_signals(self):
        return {"depth": self.depth, "row_s_ema": self.row_s,
                "batches": self.batches, "live": self.replicas}

    def scale_to(self, n):
        self.calls.append(n)
        self.replicas = n


def test_autoscaler_up_hysteresis_cooldown_and_max():
    pool = _StubPool(replicas=1)
    sc = AutoScaler(pool, min_replicas=1, max_replicas=3,
                    target_wait_ms=100.0, up_polls=2, idle_s=10.0,
                    cooldown_s=0.5, step=1, clock=lambda: 0.0)
    pool.depth, pool.row_s = 40, 0.01  # est wait 0.4s >> 0.1s target
    assert sc.check(now=0.0) is None          # hysteresis: 1 poll is not
    assert sc.check(now=0.1) == "up"          # 2 consecutive polls are
    assert pool.replicas == 2
    assert sc.check(now=0.2) is None          # cooldown holds...
    assert sc.check(now=0.3) is None
    assert sc.check(now=0.7) == "up"          # ...then the next step
    assert pool.replicas == 3
    # at max: over-target forever never scales past the ceiling
    for t in (1.5, 1.6, 1.7, 2.5):
        assert sc.check(now=t) is None
    assert pool.replicas == 3
    assert sc.scale_ups == 2 and sc.scale_downs == 0
    st = sc.stats()
    assert st["events"][-1]["direction"] == "up"
    assert st["events"][-1]["to"] == 3


def test_autoscaler_idle_shrink_floor_and_unhealthy_freeze():
    pool = _StubPool(replicas=3)
    sc = AutoScaler(pool, min_replicas=1, max_replicas=4,
                    target_wait_ms=100.0, up_polls=1, idle_s=1.0,
                    cooldown_s=0.1, clock=lambda: 0.0)
    pool.depth = 0
    assert sc.check(now=0.0) is None          # idle window starts
    assert sc.check(now=0.5) is None          # not idle long enough
    assert sc.check(now=1.1) == "down"        # one step per window
    assert pool.replicas == 2
    assert sc.check(now=1.3) is None          # window restarted
    assert sc.check(now=2.2) == "down"
    assert pool.replicas == 1
    # at the floor: idle forever never goes below min
    assert sc.check(now=5.0) is None
    assert pool.replicas == 1
    # queued work interrupts the idle window (no shrink while busy)
    pool.replicas, pool.depth, pool.row_s = 2, 3, 0.0001
    sc._last_busy = None
    assert sc.check(now=10.0) is None
    assert sc.check(now=12.0) is None         # busy at 10.0 reset window
    # an unhealthy pool freezes the controller entirely
    pool._healthy = False
    pool.depth, pool.row_s = 100, 1.0
    for t in (20.0, 21.0):
        assert sc.check(now=t) is None
    assert pool.replicas == 2


def test_autoscaler_bounds_validated():
    with pytest.raises(ValueError):
        AutoScaler(_StubPool(), min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        AutoScaler(_StubPool(), min_replicas=0, max_replicas=2)


# --------------------------------------------- server pool elasticity


def test_server_scale_to_grow_and_shrink_live_workers():
    Engine.init()
    model = _linear_model()
    x = _rows(12)
    with InferenceServer(model, max_batch=4, max_wait_ms=2,
                         example=x[0]) as server:
        assert server.autoscale_signals()["live"] == 1
        server.scale_to(3)
        time.sleep(0.1)
        st = server.stats()
        assert st["replicas"] == 3 and st["replicas_live"] == 3
        outs = [server.submit(r) for r in x]
        got = np.stack([h.result(30) for h in outs])
        # per-sample oracle: every forward (server bucket or reference)
        # pads to the same shape on the 8-device mesh — the bit-identity
        # precondition (see test_serve.py's coalescing test)
        ref = np.stack([Predictor(model).predict(x[i:i + 1])[0]
                        for i in range(len(x))])
        np.testing.assert_array_equal(got, ref)
        server.scale_to(1)
        # condemned workers parked on the EMPTY queue must exit at the
        # next wait slice (collect stop_when), not linger until traffic
        deadline = time.monotonic() + 5.0
        while server.stats()["replicas_live"] > 1 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        st = server.stats()
        assert st["replicas"] == 1 and st["replicas_live"] == 1
        # the shrunken pool still serves
        assert server.predict(x[0], timeout=30) is not None


def test_scale_up_zero_fresh_lowers_plain_server(tmp_path, monkeypatch):
    """Plain-server scale-up shares the already-warm engine: the whole
    grow happens with zero fresh lowers on the AOT ledger."""
    from bigdl_tpu.utils import aot
    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", str(tmp_path / "aot"))
    aot.reset()
    Engine.init()
    model = _linear_model(seed=3)
    x = _rows(16, seed=3)
    with InferenceServer(model, max_batch=4, max_wait_ms=2,
                         example=x[0]) as server:
        s0 = aot.stats()
        server.scale_to(4)
        outs = [server.submit(r) for r in x]
        for h in outs:
            h.result(30)
        s1 = aot.stats()
        assert int(s1["lowers"] - s0["lowers"]) == 0
        assert int(s1["compiles"] - s0["compiles"]) == 0
        assert server.stats()["aot"]["lowers"] == int(s1["lowers"])


def test_autoscale_end_to_end_grows_then_shrinks():
    """Armed controller on a live server: a chaos-pinned service time +
    a request flood must grow the pool; the post-flood idle window must
    hand the capacity back.  Decisions land in stats()["autoscale"]."""
    Engine.init()
    model = _linear_model(seed=1)
    x = _rows(64, seed=1)
    with chaos.scoped(_stall_spec(0.03)):
        with InferenceServer(model, max_batch=4, max_wait_ms=2,
                             queue_limit=256, example=x[0],
                             autoscale_min=1, autoscale_max=3,
                             autoscale_target_wait_ms=30.0,
                             autoscale_up_polls=1,
                             autoscale_cooldown_s=0.05,
                             autoscale_idle_s=0.3,
                             autoscale_poll_s=0.01) as server:
            handles = [server.submit(r) for r in x]
            for h in handles:
                h.result(60)
            deadline = time.monotonic() + 5.0
            grew = server.stats()["autoscale"]["scale_ups"]
            while server.stats()["replicas"] > 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            st = server.stats()
    assert grew >= 1
    assert st["autoscale"]["scale_ups"] >= 1
    assert st["autoscale"]["scale_downs"] >= 1
    assert st["replicas"] == 1
    ev = st["autoscale"]["events"]
    assert ev and {"direction", "from", "to", "est_wait_ms",
                   "queue_depth"} <= set(ev[0])


# ------------------------- requeue x priority-eviction x pool shrink


def test_requeue_not_evicted_below_admission_priority():
    """The satellite contract: a condemned replica's requeued batch goes
    back to the queue HEAD and keeps its ORIGINAL admission priority —
    equal- or lower-priority arrivals can never evict it; a strictly
    higher one still can (normal priority semantics)."""
    b = DynamicBatcher(max_batch=4, max_wait_s=0.01, queue_limit=4)
    held = [b.submit(i, priority=1) for i in range(4)]
    got = b.collect()
    assert [r.payload for r in got] == [0, 1, 2, 3]
    # the condemned replica hands its batch back (original order, HEAD)
    b.requeue(got)
    assert b.depth() == 4
    # a lower-priority arrival cannot displace the requeued batch: IT is
    # refused (typed), the batch is untouched
    with pytest.raises(ServerOverloaded):
        b.submit(99, priority=0)
    # an equal-priority arrival cannot either (eviction needs a STRICT
    # outrank)
    with pytest.raises(ServerOverloaded):
        b.submit(99, priority=1)
    assert b.depth() == 4 and not any(r.done() for r in held)
    # a strictly higher-priority arrival may evict — and evicts the
    # NEWEST of the lowest class, exactly one
    b.submit(100, priority=2)
    evicted = [r for r in held if r.done()]
    assert len(evicted) == 1 and evicted[0] is held[-1]
    with pytest.raises(ServerOverloaded):
        evicted[0].result(0.1)
    # the survivors drain in original order, head first (the arrival
    # that evicted joined the TAIL behind the requeued batch)
    out = b.collect()
    assert [r.payload for r in out] == [0, 1, 2, 100]


def test_shrink_requeues_condemned_replicas_batch_zero_loss():
    """End to end: replica 1 is wedged holding a collected batch while
    the pool shrinks to 1 — on waking it must notice its condemnation,
    requeue the batch, and exit; replica 0 serves everything.  Zero
    accepted-request loss across an autoscaler shrink."""
    Engine.init()
    model = _linear_model(seed=2)
    x = _rows(8, seed=2)
    ref = np.asarray(Predictor(model).predict(x))
    # serve.replica@1 wedges replica 1 AFTER it collected its 1st batch
    # and BEFORE it executes — it holds the batch through the shrink
    with chaos.scoped("serve.replica@1=wedge*0.4@1"):
        server = InferenceServer(model, replicas=2, max_batch=4,
                                 max_wait_ms=40, queue_limit=64,
                                 example=x[0]).start()
        try:
            handles = [server.submit(r) for r in x]
            time.sleep(0.1)          # let replica 1 collect + wedge
            server.scale_to(1)       # condemn slot 1 mid-wedge
            got = np.stack([h.result(30) for h in handles])
        finally:
            server.stop()
    np.testing.assert_array_equal(got, ref)


# --------------------------------------------------- topology routing


def test_plan_subsets_disjoint_and_typed_placement_error():
    devs = jax.devices()
    subsets = plan_subsets(devs, 2, 4)
    assert len(subsets) == 4 and all(len(s) == 2 for s in subsets)
    seen = [d for s in subsets for d in s]
    assert len(set(seen)) == len(seen)  # disjoint
    with pytest.raises(PlacementError):
        plan_subsets(devs, 3, 3)  # 9 > 8 devices
    with pytest.raises(PlacementError):
        TopologyRouter(_linear_model(), replicas=9,
                       example=np.zeros(4, np.float32))


def test_router_bit_match_and_bucket_depth_routing():
    Engine.init()
    model = _linear_model()
    x = _rows(16)
    with TopologyRouter(model, replicas=2, max_batch=4, max_wait_ms=5,
                        example=x[0]) as router:
        handles = [router.submit(r) for r in x]
        got = np.stack([h.result(30) for h in handles])
        np.testing.assert_array_equal(
            got, np.asarray(Predictor(model).predict(x)))
        st = router.stats()
        assert st["router"]["replicas"] == 2
        assert sum(st["router"]["routed"]) == 16
        assert set(st["router"]["members"]) == {"0", "1"}
    # the dispatch decision, on an UNSTARTED pool (no workers draining
    # the queues out from under the assertions): fewest full buckets,
    # then prefer the partially-filled coalescing batch, then depth,
    # then index
    probe = TopologyRouter(model, replicas=2, max_batch=4,
                           example=_rows(1)[0])
    for i in range(2):
        probe._members[i] = probe._build_member(i)
    m0, m1 = probe._members[0], probe._members[1]
    assert probe._pick() == 0                    # all idle -> index
    m0.batcher._q.extend([object()] * 4)         # 1 full bucket
    assert probe._pick() == 1
    m1.batcher._q.extend([object()] * 5)         # 1 full + a partial
    # equal full-bucket counts: the PARTIAL coalescing batch wins (its
    # flush window is already ticking; joining raises fill)
    assert probe._pick() == 1
    m0.batcher._q.clear()
    m1.batcher._q.clear()
    m1.batcher._q.append(object())               # lone partial batch
    assert probe._pick() == 1                    # join it, fill it
    m1.batcher._q.clear()
    # an unhealthy member never receives traffic
    from bigdl_tpu.serve import ReplicaLostError
    m1.batcher._q.clear()
    m0._unhealthy = ReplicaLostError("drill")
    assert probe._pick() == 1


def test_router_tp_sharded_members_serve_bit_identical():
    """Mesh-sharded members: layout (1,1,2) members own 2 devices each
    and serve tp-sharded through LayoutSharding — answers still
    bit-match bulk Predictor.predict (the PR 9 serving contract, now
    per-subset)."""
    from bigdl_tpu.parallel import MeshLayout
    Engine.init()
    model = nn.Sequential().add(nn.Linear(8, 6)).add(nn.ReLU()) \
        .add(nn.Linear(6, 4)).build(jax.random.key(5))
    x = _rows(12, din=8, seed=5)
    with TopologyRouter(model, layout=MeshLayout(1, 1, 2), replicas=2,
                        max_batch=4, example=x[0]) as router:
        st = router.stats()["router"]
        assert st["devices_per_replica"] == 2
        devs = [tuple(m["devices"]) for m in st["members"].values()]
        assert len(set(d for s in devs for d in s)) == 4  # disjoint
        handles = [router.submit(r) for r in x]
        got = np.stack([h.result(30) for h in handles])
    np.testing.assert_array_equal(
        got, np.asarray(Predictor(model).predict(x)))


def test_router_degrades_to_surviving_members_on_loss():
    """A member whose pool is beyond recovery stops receiving traffic;
    the router keeps serving through the survivors and stays healthy."""
    Engine.init()
    model = _linear_model(seed=7)
    x = _rows(12, seed=7)
    with TopologyRouter(model, replicas=2, max_batch=4,
                        example=x[0]) as router:
        # member 0's restart budget is spent: the PR 10 signal
        from bigdl_tpu.serve import ReplicaLostError
        router._members[0]._mark_unhealthy(
            ReplicaLostError("drill: member 0 lost"))
        routed_before = list(router._routed)
        handles = [router.submit(r) for r in x]
        got = np.stack([h.result(30) for h in handles])
        np.testing.assert_array_equal(
            got, np.asarray(Predictor(model).predict(x)))
        assert router._routed[0] == routed_before[0]  # nothing new to 0
        assert router.healthy()  # the POOL survives one member's loss
        st = router.stats()
        assert st["router"]["members"]["0"]["healthy"] is False
        assert st["router"]["members"]["1"]["healthy"] is True


def test_router_scale_up_is_aot_cache_reads(tmp_path, monkeypatch):
    """Router scale-up builds FRESH engines on new subsets — with the
    cache armed and subsets prewarmed, the whole grow is cache reads:
    zero fresh lowers, zero misses (the ISSUE 14 acceptance ledger).

    The XLA persistent cache is un-latched for the duration (same
    attribution discipline as the restart x AOT test in test_serve.py):
    an executable XLA read back from its disk cache is not stored by the
    AOT layer, so with that cache warm the ledger would show misses."""
    from jax._src import compilation_cache as _cc

    from bigdl_tpu.utils import aot
    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", str(tmp_path / "aot"))
    aot.reset()
    prior_xla = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    Engine.init()
    model = _linear_model(seed=9)
    x = _rows(16, seed=9)
    router = TopologyRouter(model, replicas=1, max_replicas=3,
                            max_batch=4, example=x[0],
                            prewarm=True).start()
    try:
        s0 = aot.stats()
        router.scale_to(3)
        handles = [router.submit(r) for r in x]
        got = np.stack([h.result(30) for h in handles])
        s1 = aot.stats()
        assert int(s1["lowers"] - s0["lowers"]) == 0
        assert int(s1["misses"] - s0["misses"]) == 0
        assert int(s1["hits"] - s0["hits"]) > 0
        np.testing.assert_array_equal(
            got, np.asarray(Predictor(model).predict(x)))
        # shrink drains gracefully and the survivors keep serving
        router.scale_to(1)
        assert router.predict(x[0], timeout=30) is not None
    finally:
        router.stop()
        jax.config.update("jax_compilation_cache_dir", prior_xla)
        _cc.reset_cache()


# ------------------------------------------------ trace record/replay


def test_trace_roundtrip_and_corruption_typed(tmp_path):
    path = str(tmp_path / "trace.rec")
    x = _rows(3)
    events = [TraceEvent(0.0, x[0], tenant="a", priority=2,
                         deadline_ms=50.0),
              TraceEvent(0.01, x[1], tenant="b", priority=0),
              TraceEvent(0.25, x[2])]
    write_trace(path, events, meta={"source": "test"})
    header, loaded = read_trace(path)
    assert header["format"] == "bigdl_tpu-serve-trace-v1"
    assert header["count"] == 3
    assert header["sample_shape"] == [4]
    assert header["meta"]["source"] == "test"
    assert [e.dt for e in loaded] == [0.0, 0.01, 0.25]
    assert [e.tenant for e in loaded] == ["a", "b", None]
    assert [e.priority for e in loaded] == [2, 0, 0]
    assert loaded[0].deadline_ms == 50.0 and loaded[1].deadline_ms is None
    np.testing.assert_array_equal(loaded[2].payload, x[2])
    # a flipped payload byte is a typed CorruptRecord, not a bad bench
    from bigdl_tpu.utils.recordio import CorruptRecord
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with pytest.raises(CorruptRecord):
        read_trace(path)
    # a non-trace recordio file is a typed format error
    other = str(tmp_path / "other.rec")
    from bigdl_tpu.utils import recordio
    recordio.write_records(other, [{"not": "a trace"}])
    with pytest.raises(TraceFormatError):
        read_trace(other)


def test_server_records_offered_traffic(tmp_path):
    """record_trace captures the OFFERED stream — shed requests
    included — with tenants/priorities/deadlines, through the real
    admission path, and stop_trace writes the recordio file."""
    Engine.init()
    model = _linear_model()
    x = _rows(8)
    path = str(tmp_path / "offered.rec")
    with InferenceServer(model, max_batch=4, queue_limit=2,
                         max_wait_ms=1, example=x[0]) as server:
        server.record_trace(path)
        shed = 0
        with chaos.scoped("serve.batch=stall*0.15@1"):
            for i, r in enumerate(x):
                try:
                    server.submit(r, tenant=f"t{i % 2}", priority=i % 3,
                                  deadline_ms=200.0)
                except ServerOverloaded:
                    shed += 1
        assert shed > 0  # the tiny queue really shed some
        assert server.stats()["trace_recording"]["events"] == len(x)
        n = len(server.stop_trace())
    header, events = read_trace(path)
    assert header["count"] == n == len(x)  # sheds recorded too
    assert {e.tenant for e in events} == {"t0", "t1"}
    assert all(e.deadline_ms == 200.0 for e in events)


def test_replay_open_loop_pacing_and_lag():
    """Pacing is open-loop on an injected clock: submit times follow the
    recorded arrivals / speed, and a slow submit shows up as LAG on the
    events behind it instead of stretching the schedule."""
    t = [0.0]
    sleeps = []

    def clock():
        return t[0]

    def sleep(s):
        sleeps.append(round(s, 6))
        t[0] += s

    events = [TraceEvent(0.0, 0), TraceEvent(1.0, 1), TraceEvent(1.0, 2)]
    seen = []

    def submit(e):
        seen.append((e.payload, round(t[0], 6)))
        return None

    out = replay(events, submit, speed=10.0, clock=clock, sleep=sleep)
    assert [p for p, _ in seen] == [0, 1, 2]
    assert [at for _, at in seen] == [0.0, 0.1, 0.2]
    assert sleeps == [0.1, 0.1]
    assert all(o.lag_s == 0.0 for o in out)

    # a slow submit makes later events LATE (lag), never re-paced
    t[0] = 0.0
    slow = [True]

    def slow_submit(e):
        if slow[0]:
            slow[0] = False
            t[0] += 0.5  # the first submit burns half a second
        return None

    out = replay(events, slow_submit, speed=10.0, clock=clock,
                 sleep=sleep)
    assert out[0].lag_s == 0.0
    assert out[1].lag_s == pytest.approx(0.4, abs=1e-6)
    assert out[2].lag_s == pytest.approx(0.3, abs=1e-6)
    with pytest.raises(ValueError):
        replay(events, submit, speed=0.0)


def test_slo_report_attainment_and_shed_classification():
    """Attainment counts served-within-own-deadline over OFFERED, per
    tenant and per priority; overload/timeout are shedding, anything
    else is a real error in its own bucket."""
    from bigdl_tpu.serve import RequestTimeout
    from bigdl_tpu.serve.tracefile import ReplayOutcome

    def ev(tenant, priority, deadline_ms):
        return TraceEvent(0.0, 0, tenant=tenant, priority=priority,
                          deadline_ms=deadline_ms)

    def served(e, lat_s):
        o = ReplayOutcome(e)
        o.handle = object()
        o.latency_s = lat_s
        return o

    def failed(e, err):
        return ReplayOutcome(e, error=err)

    outcomes = [
        served(ev("a", 2, 100.0), 0.05),            # attained
        served(ev("a", 2, 100.0), 0.25),            # served, too late
        served(ev("a", 0, None), 1.0),              # no deadline: attains
        failed(ev("b", 1, 100.0), ServerOverloaded("full")),
        failed(ev("b", 1, 100.0), RequestTimeout("late")),
        failed(ev("b", 0, 100.0), RuntimeError("backend died")),
    ]
    rep = slo_report(outcomes)
    assert rep["offered"] == 6 and rep["served"] == 3
    assert rep["attainment"] == pytest.approx(2 / 6, abs=1e-4)
    assert rep["shed"] == {"overload": 1, "timeout": 1, "errors": 1}
    a, b = rep["per_tenant"]["a"], rep["per_tenant"]["b"]
    assert a["attainment"] == pytest.approx(2 / 3, abs=1e-4)
    assert b["attainment"] == 0.0
    assert b["errors"] == 1 and b["shed_overload"] == 1
    assert rep["per_priority"]["2"]["offered"] == 2
    assert rep["p50_ms"] is not None
    # default deadline applies where the event carried none
    rep2 = slo_report([served(ev("c", 0, None), 1.0)],
                      default_deadline_ms=100.0)
    assert rep2["attainment"] == 0.0


def test_replay_acceptance_autoscaled_beats_fixed(tmp_path):
    """ISSUE 14 acceptance: a recorded trace replayed at >= 10x produces
    per-tenant SLO attainment, and under the same trace + pinned
    service time the autoscaled pool attains STRICTLY more than the
    fixed 1-replica pool."""
    Engine.init()
    model = _linear_model(seed=4)
    xs = _rows(16, seed=4)
    path = str(tmp_path / "accept.rec")
    # record a real offered stream through the server's admission path
    with InferenceServer(model, max_batch=4, queue_limit=512,
                         example=xs[0]) as rec_server:
        rec_server.record_trace(path)
        hs = []
        for i in range(90):
            hs.append(rec_server.submit(
                xs[i % len(xs)], tenant=f"t{i % 3}", priority=i % 3,
                deadline_ms=250.0))
            time.sleep(0.01)
        for h in hs:
            h.result(30)
        rec_server.stop_trace()
    _header, events = read_trace(path)
    assert len(events) == 90

    def run(pool):
        def submit(e):
            return pool.submit(e.payload, deadline_ms=e.deadline_ms,
                               tenant=e.tenant, priority=e.priority)
        outcomes = replay(events, submit, speed=10.0)
        resolve_outcomes(outcomes, timeout=60)
        return slo_report(outcomes)

    with chaos.scoped(_stall_spec(0.03)):
        with InferenceServer(model, max_batch=4, queue_limit=512,
                             example=xs[0]) as fixed:
            rep_fixed = run(fixed)
    with chaos.scoped(_stall_spec(0.03)):
        with InferenceServer(model, max_batch=4, queue_limit=512,
                             example=xs[0], autoscale_min=1,
                             autoscale_max=4,
                             autoscale_target_wait_ms=30.0,
                             autoscale_up_polls=1,
                             autoscale_cooldown_s=0.03,
                             autoscale_poll_s=0.01) as auto:
            rep_auto = run(auto)
            grew = auto.stats()["autoscale"]["scale_ups"]
    assert set(rep_auto["per_tenant"]) == {"t0", "t1", "t2"}
    assert set(rep_auto["per_priority"]) == {"0", "1", "2"}
    assert grew >= 1
    assert rep_auto["attainment"] > rep_fixed["attainment"]


# ------------------------------------------------- HTTP front end


def test_http_autoscale_stats_retry_after_503_and_trace_header(tmp_path):
    """/v1/stats surfaces the autoscaler block, the unhealthy 503 path
    carries Retry-After (healthz AND predict), and the
    X-BigDL-Record-Trace header arms/flushes trace recording."""
    import sys
    import urllib.error
    import urllib.request

    tools_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import serve_http

    Engine.init()
    model = _linear_model()
    x = _rows(2)
    server = InferenceServer(model, example=np.zeros((4,), np.float32),
                             autoscale_max=2).start()
    httpd = serve_http.serve_forever(server, "127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    trace_path = str(tmp_path / "http_trace.rec")

    def post(path, obj, headers=None):
        req = urllib.request.Request(base + path,
                                     data=json.dumps(obj).encode(),
                                     method="POST",
                                     headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, dict(r.headers), json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), json.loads(e.read())

    try:
        # autoscaler state in /v1/stats
        with urllib.request.urlopen(base + "/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["autoscale"]["max"] == 2
        assert stats["autoscale"]["replicas"] == 1
        # trace header arms recording; 'off' stops BEFORE its own
        # request and writes the file
        status, _h, _b = post("/v1/predict", {"inputs": x[0].tolist()},
                              headers={"X-BigDL-Record-Trace": trace_path})
        assert status == 200
        status, _h, _b = post("/v1/predict", {"inputs": x[1].tolist()})
        assert status == 200
        status, _h, _b = post("/v1/predict", {"inputs": x[0].tolist()},
                              headers={"X-BigDL-Record-Trace": "off"})
        assert status == 200
        header, events = read_trace(trace_path)
        assert header["count"] == len(events) == 2
        # unhealthy 503s carry Retry-After now (not just the 429 path):
        # budget-spent marker + a dead pool is the admission 503 path
        from bigdl_tpu.serve import ReplicaLostError
        server._unhealthy = ReplicaLostError("drill: budget spent")
        server.batcher.close(drain=True)
        for t in server._threads:
            t.join(5)
        code, headers, body = post("/v1/predict",
                                   {"inputs": x[0].tolist()})
        assert code == 503 and body["type"] in ("ReplicaLostError",
                                                "ServerClosed")
        assert "Retry-After" in headers
        req = urllib.request.Request(base + "/healthz")
        try:
            urllib.request.urlopen(req, timeout=30)
            assert False, "healthz should be 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers.get("Retry-After") is not None
    finally:
        httpd.shutdown()
        server._unhealthy = None
        server.stop()


# ------------------------------------------- the autoscale report section


def test_autoscale_track_is_a_report_section():
    """The autoscale counter track becomes a section of the trace report,
    like the aot ledger."""
    from bigdl_tpu.utils import telemetry
    bd = telemetry.phase_breakdown({"traceEvents": [
        {"ph": "C", "name": "serve.autoscale", "ts": 1.0,
         "args": {"replicas": 2, "est_wait_ms": 12.0}},
        {"ph": "i", "name": "serve.autoscale", "ts": 1.0},
    ]})
    assert bd["autoscale"]["replicas"] == 2
    assert bd["autoscale"]["decisions"] == 1
    assert "autoscale:" in telemetry.format_report(bd)
