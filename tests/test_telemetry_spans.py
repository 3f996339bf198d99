"""The spans of the two host loops, the names of the device programs, the
request stamps and the device's idle time by cause (ISSUE 25).

- the optimizer loop's ``iteration`` span is covered by its children to
  within its self time, every span carries ``neval``, ``data`` is what it
  was;
- with no tracer active neither loop creates a span or an annotation;
- the decode engine's two programs have names and AOT keys of their own;
  a tick's children; ``enqueued <= admitted <= first_token <= resolve``;
- ``idle_by_cause`` keeps ``trace_reduce``'s total on the benchmark's
  recorded trace and names synthetic host spans, the rest ``unattributed``.
"""

import gzip
import json
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.models.transformer_lm import TransformerLM
from bigdl_tpu.optim import Adam, Optimizer, Trigger
from bigdl_tpu.serve import DecodeEngine
from bigdl_tpu.utils import aot as aot_mod
from bigdl_tpu.utils import metrics_export, profiling, telemetry
from bigdl_tpu.utils.telemetry import Tracer, idle_by_cause

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_CHILDREN = ("data", "prepare", "dispatch", "loss_fetch", "summary",
                 "triggers")


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_TRACE", raising=False)
    telemetry.set_active(None)
    yield
    telemetry.set_active(None)


@pytest.fixture
def tracer(tmp_path):
    tr = Tracer(str(tmp_path / "trace"), flush_every=0, ring=1 << 16)
    telemetry.set_active(tr)
    return tr


def _optimizer(n=96, batch=16, epochs=2):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(n)]
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(batch, drop_last=True))
    return (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                      nn.CrossEntropyCriterion())
            .set_optim_method(Adam(1e-2))
            .set_end_when(Trigger.max_epoch(epochs)))


def _spans(tr, name=None):
    return [e for e in tr.events_tail(1 << 16) if e["ph"] == "X"
            and (name is None or e["name"] == name)]


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=64, max_len=64, d_model=32,
                         num_heads=2, num_layers=2).build(jax.random.key(0))


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, size=int(rng.integers(3, 10)))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# the optimizer loop
# ---------------------------------------------------------------------------

def test_loop_spans_cover_each_iteration_and_carry_neval(tracer):
    _optimizer().optimize()
    steps = 12                      # 2 epochs x 96 / 16
    whole = {e["args"]["neval"]: e for e in _spans(tracer, "iteration")}
    assert sorted(whole) == list(range(1, steps + 1))
    kids = {}
    for e in _spans(tracer):
        if e["name"] in LOOP_CHILDREN and "args" in e:
            kids.setdefault(e["args"]["neval"], []).append(e)
    for n, it in whole.items():
        mine = sorted(kids[n], key=lambda e: e["ts"])
        # `loss_fetch` waits for the step before: an epoch's first
        # iteration (1 and 7) has none in flight
        assert [e["name"] for e in mine] == [
            c for c in LOOP_CHILDREN if c != "loss_fetch" or n % 6 != 1]
        # nested in the iteration, in order, without overlap
        edges = [it["ts"]] + [t for e in mine
                              for t in (e["ts"], e["ts"] + e["dur"])] \
            + [it["ts"] + it["dur"]]
        assert all(b - a >= -0.2 for a, b in zip(edges, edges[1:])), (n, edges)
        # what no child covers: under a millisecond, on any machine
        self_us = it["dur"] - sum(e["dur"] for e in mine)
        assert 0 <= self_us < 1000, (n, self_us)
        # same thread
        assert {e["tid"] for e in mine} == {it["tid"]}
    # an epoch's last loss is fetched after its loop, in no iteration: the
    # span has no `neval`
    flushed = [e for e in _spans(tracer, "loss_fetch") if "args" not in e]
    assert len(flushed) == 2
    assert all(e["ts"] >= whole[n]["ts"] + whole[n]["dur"] - 0.2
               for e, n in zip(flushed, (6, 12)))
    # `step` is what it was: one an iteration, from inside `prepare` (the
    # learning rate is part of it) to the end of the log line
    step = {e["args"]["neval"]: e for e in _spans(tracer, "step")}
    assert sorted(step) == sorted(whole)
    # the epochs' ends (the `next()` that finds nothing) leave no event
    assert len(_spans(tracer, "data")) == steps


class _LogMark:
    """A train summary that marks on the trace when an iteration's loss is
    handed over."""

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            telemetry.instant("logged", neval=step)
        return self


def test_step_n_plus_1_is_called_before_loss_n_is_read(tracer):
    """The order of events: iteration n + 1's `dispatch` has returned
    before the `loss_fetch` that brings loss n to the host begins, and loss
    n is logged after it, under its own number; an epoch's last loss is
    read by the flush after the loop.  The `train` track's `ran_ahead`
    says the same: every step but an epoch's first."""
    _optimizer().set_train_summary(_LogMark()).optimize()
    events = tracer.events_tail(1 << 16)
    logged = {e["args"]["neval"]: e["ts"] for e in events
              if e["ph"] == "i" and e["name"] == "logged"}
    assert list(logged) == list(range(1, 13))           # once each, in order
    whole, dispatch, fetch = (
        {e["args"]["neval"]: e for e in _spans(tracer, name) if "args" in e}
        for name in ("iteration", "dispatch", "loss_fetch"))
    for n in range(1, 13):
        if n % 6 == 0:
            # an epoch's last: no later iteration; read after its own ends
            assert logged[n] >= whole[n]["ts"] + whole[n]["dur"] - 0.2
            continue
        called = dispatch[n + 1]["ts"] + dispatch[n + 1]["dur"]
        assert called <= fetch[n + 1]["ts"] + 0.2 and \
            fetch[n + 1]["ts"] + fetch[n + 1]["dur"] <= logged[n] + 0.2, n
    ahead = [e["args"]["ran_ahead"] for e in events
             if e["ph"] == "C" and e["name"] == "train"]
    assert ahead == [0.0, 1.0, 1.0, 1.0, 1.0, 1.0] * 2


def test_data_span_is_the_blocking_next(tracer, monkeypatch):
    """``data`` as before this PR: one for each iteration, ``neval``, and
    as long as the loop waited for its batch (``data_wait_s`` of the
    counter track is the same wait on the loop's own clock)."""
    monkeypatch.setenv("BIGDL_TPU_PREFETCH_DEPTH", "0")
    _optimizer(epochs=1).optimize()
    data = {e["args"]["neval"]: e for e in _spans(tracer, "data")}
    assert sorted(data) == list(range(1, 7))
    assert all(set(e["args"]) == {"neval"} and e["cat"] == "phase"
               for e in data.values())
    waits = [e["args"]["data_wait_s"] for e in tracer.events_tail(1 << 16)
             if e["ph"] == "C" and e["name"] == "train"]
    assert len(waits) == 6
    for e, wait in zip(sorted(data.values(), key=lambda e: e["ts"]), waits):
        assert abs(e["dur"] / 1e6 - wait) < 2e-3


def test_prefetch_item_splits_into_produce_and_stage(tracer):
    _optimizer(epochs=1).optimize()
    item, produce, stage = (_spans(tracer, "prefetch." + n)
                            for n in ("item", "produce", "stage"))
    assert len(item) == len(produce) == len(stage) == 6
    loop_tid = _spans(tracer, "iteration")[0]["tid"]
    for it, pr, st in zip(item, produce, stage):
        assert it["tid"] == pr["tid"] == st["tid"] != loop_tid
        assert pr["dur"] + st["dur"] <= it["dur"] + 1.0
        assert pr["ts"] + pr["dur"] <= st["ts"] + 0.2


class _Boom:
    def __init__(self, *a, **kw):
        raise AssertionError("created with no tracer active")


def test_no_tracer_no_span_and_no_annotation(monkeypatch, lm):
    """Tracing off: the loop and ``_tick`` allocate nothing, neither a
    ``_Span`` nor a ``TraceAnnotation``."""
    monkeypatch.setattr(telemetry, "_Span", _Boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Boom)
    assert telemetry.get_active() is None
    _optimizer(epochs=1).optimize()
    with DecodeEngine(lm, slots=2, page=16) as eng:
        out = eng.generate(_prompts(1)[0], 4)
    assert len(out) >= 4
    assert telemetry.span("x", neval=1) is telemetry._NULL_SPAN


def test_span_holds_an_annotation_the_profiler_records(tracer, tmp_path):
    """Every open span holds ``bigdl:<name>`` open for as long: a profiler
    session with the host tracer on carries it on the profiler's clock."""
    logdir = str(tmp_path / "prof")
    with profiling.profiler_session(logdir):
        with telemetry.span("outer", neval=3):
            with telemetry.span("inner"):
                jax.block_until_ready(jax.numpy.ones((8, 8)) @
                                      jax.numpy.ones((8, 8)))
    rows = profiling.xplane_rows(logdir)
    named = {r[2]: r for r in rows if r[2].startswith("bigdl:")}
    assert set(named) == {"bigdl:outer", "bigdl:inner"}
    outer, inner = named["bigdl:outer"], named["bigdl:inner"]
    assert outer[:2] == inner[:2]                       # one thread's line
    assert outer[3] <= inner[3] and \
        inner[3] + inner[4] <= outer[3] + outer[4]      # nested
    # and the tracer's own events are as before
    assert [e["name"] for e in _spans(tracer)] == ["inner", "outer"]
    with pytest.raises(FileNotFoundError):
        profiling.xplane_rows(str(tmp_path / "nothing"))


# ---------------------------------------------------------------------------
# the decode engine
# ---------------------------------------------------------------------------

def test_decode_programs_have_their_own_names_and_aot_keys(lm, monkeypatch):
    seen = {}
    real = aot_mod.get_or_compile

    def spy(key_fields, lower_fn, *, label, card_extra=None):
        seen[label] = (dict(key_fields, label=label),
                       lower_fn().as_text())
        return real(key_fields, lower_fn, label=label,
                    card_extra=card_extra)

    monkeypatch.setattr(aot_mod, "get_or_compile", spy)
    eng = DecodeEngine(lm, slots=2, page=16)
    eng._step_exe(16)
    eng._prefill_exe(1, 8, 16)
    step_fields, step_text = seen["decode.step"]
    pre_fields, pre_text = seen["decode.prefill"]
    assert "jit_decode_step" in step_text and "jit_fn" not in step_text
    assert "jit_decode_prefill" in pre_text and "jit_fn" not in pre_text
    assert step_fields["program"] == "jit_decode_step"
    assert pre_fields["program"] == "jit_decode_prefill"
    assert aot_mod.fingerprint(step_fields) != aot_mod.fingerprint(pre_fields)
    # the name is part of the key: an executable stored under another
    # name (`jit_fn`, before this PR) cannot be handed back
    assert aot_mod.fingerprint(dict(step_fields, program="jit_fn")) \
        != aot_mod.fingerprint(step_fields)


def test_decode_tick_has_admit_step_and_sample_children(tracer, lm):
    prompts = _prompts(5, seed=1)
    with DecodeEngine(lm, slots=2, page=16) as eng:
        reqs = [eng.submit(p, 5) for p in prompts]
        for r in reqs:
            r.result(120)
    ticks = _spans(tracer, "decode.tick")
    admits = _spans(tracer, "decode.admit")
    steps = _spans(tracer, "decode.step")
    samples = _spans(tracer, "decode.sample")
    # one span a prefill call: `rows` requests of one bucket, `prompt_len`
    # their real tokens together
    assert sum(a["args"]["rows"] for a in admits) == 5 and ticks
    assert len(admits) == eng.prefill_steps <= 5
    assert len(steps) == len(samples) == eng.decode_steps
    assert sum(t["args"]["admitted"] for t in ticks) == 5
    assert all(0 <= t["args"]["active"] <= 2 for t in ticks)
    assert sum(a["args"]["prompt_len"] for a in admits) \
        == sum(len(p) for p in prompts)
    assert all(a["args"]["bucket"] * a["args"]["rows"]
               >= a["args"]["prompt_len"]
               and a["args"]["slot"] in (0, 1) for a in admits)
    tid = {e["tid"] for e in ticks}
    assert len(tid) == 1
    for child in admits + steps + samples:
        assert child["tid"] in tid
        assert any(t["ts"] - 0.2 <= child["ts"] and child["ts"]
                   + child["dur"] <= t["ts"] + t["dur"] + 0.2
                   for t in ticks), child
    for t in ticks:     # a tick's children cover it but for its self time
        inside = [c for c in admits + steps + samples
                  if t["ts"] - 0.2 <= c["ts"] <= t["ts"] + t["dur"]]
        assert sum(c["dur"] for c in inside) <= t["dur"] + 1.0
    # the request's span carries its two stamps
    done = _spans(tracer, "serve.request")
    assert len(done) == 5
    for e in done:
        a = e["args"]
        assert 0 <= a["queue_wait_ms"] <= a["ttft_ms"] <= e["dur"] / 1e3 + 1e-6


def test_request_stamps_are_ordered_and_summed(lm, monkeypatch):
    # a fresh registry, whatever ran before in this process (put back after)
    monkeypatch.setattr(metrics_export, "_REGISTRY", None)
    reg = metrics_export.arm()
    try:
        with DecodeEngine(lm, slots=2, page=16) as eng:
            before = eng.stats()
            reqs = [eng.submit(p, 4) for p in _prompts(6, seed=2)]
            for r in reqs:
                r.result(120)
            resolved = eng.clock()
            after = eng.stats()
    finally:
        metrics_export.disarm()
    assert metrics_export._REGISTRY is None
    assert before["admitted"] == 0 and before["ttft_s"] == 0.0
    for r in reqs:
        assert r.enqueued <= r.admitted <= r.first_token \
            <= r.enqueued + r.latency_s + 1e-9 <= resolved + 1e-9
    assert after["admitted"] == after["first_tokens"] == 6
    assert after["queue_wait_s"] == pytest.approx(
        sum(r.admitted - r.enqueued for r in reqs))
    assert after["ttft_s"] == pytest.approx(
        sum(r.first_token - r.enqueued for r in reqs))
    text = reg.render()
    for name in ("bigdl_decode_queue_wait_seconds",
                 "bigdl_decode_ttft_seconds", "bigdl_decode_ttlt_seconds"):
        assert f"{name}_count 6" in text, text[-1500:]


def _count(monkeypatch, owner, name, calls):
    """Append ``name`` to ``calls`` at every call of ``owner.name``; for a
    telemetry helper ``name:<span or track>``, and only where the engine or
    its queue made it (compiling an executable has spans and tracks of its
    own)."""
    real = getattr(owner, name)

    def spy(*a, **kw):
        if owner is not telemetry:
            calls.append(name)
        elif str(a[0]).startswith(("decode.", "serve")):
            calls.append(f"{name}:{a[0]}")
        return real(*a, **kw)

    monkeypatch.setattr(owner, name, spy)


def _run_closed(lm, prompts, max_tokens, **kw):
    """Every request queued and the queue closed before the loop starts: the
    engine works from its first pass to its last and never waits."""
    eng = DecodeEngine(lm, slots=2, page=16, **kw)
    reqs = [eng.submit(p, max_tokens) for p in prompts]
    eng.queue.close(drain=True)
    eng.start()
    eng.stop()
    return eng, [r.result(120) for r in reqs]


def test_decode_counter_arguments_wait_for_a_reader(lm, monkeypatch):
    """The per-tick track is emitted only when a tracer or a registry will
    read it, and nothing a tick does walks the model: the bytes a slot holds
    are reckoned where the cache's length changes."""
    from bigdl_tpu.models import decode as kv
    # no registry, whatever ran before in this process (put back after)
    monkeypatch.setattr(metrics_export, "_REGISTRY", None)
    calls = []
    _count(monkeypatch, kv, "state_bytes_per_row", calls)
    _count(monkeypatch, telemetry, "counter", calls)
    eng, _rows = _run_closed(lm, _prompts(1), 6)
    # the constructor's state bytes and the one cache length; no track
    assert sorted(calls) == ["counter:serve"] + ["state_bytes_per_row"] * 2
    assert eng.stats()["cache_bytes_per_slot"] == eng.cache_bytes_per_slot() \
        == kv.state_bytes_per_row(lm, 16, eng.cache_dtype)[0] > 0
    del calls[:]
    tr = Tracer("memory://unused", flush_every=0)
    telemetry.set_active(tr)
    eng, _rows = _run_closed(lm, _prompts(1), 6)
    assert calls.count("state_bytes_per_row") == 2
    # one a working pass: every step's, and the one that reads the last step
    assert calls.count("counter:serve.decode") == eng.decode_steps + 1
    track = [e for e in tr.events_tail(4096)
             if e["ph"] == "C" and e["name"] == "serve.decode"]
    assert len(track) == eng.decode_steps + 1
    assert track[-1]["args"]["cache_bytes_per_slot"] \
        == eng.cache_bytes_per_slot()
    # requests a prefill call, and the slots' steps kept free for a group
    assert track[-1]["args"]["prefill_group"] == 1.0
    assert track[-1]["args"]["held_share"] == 0.0
    # `ran_ahead` a step: each was called with the call before it unread
    assert [e["args"].get("ran_ahead") for e in track] \
        == [1.0] * eng.decode_steps + [None]
    assert eng.stats()["steps_ahead"] == eng.decode_steps


def test_no_tracer_no_telemetry_call_per_token(lm, monkeypatch):
    """Tracing off, the telemetry calls of a run are those of its ticks and
    its prefill calls: a second request decoded beside the first adds one
    prefill call's spans where its prompt is of another bucket (none where
    the two share a call) and nothing for its tokens."""
    monkeypatch.setattr(metrics_export, "_REGISTRY", None)
    calls = []
    for name in ("span", "counter", "complete", "instant", "flow_start",
                 "flow_step", "flow_finish"):
        _count(monkeypatch, telemetry, name, calls)
    n = 7
    eng, rows = _run_closed(lm, _prompts(1, seed=3), n)
    assert eng.tokens_out == n and eng.decode_steps == n - 1
    one = list(calls)
    del calls[:]
    eng, rows = _run_closed(lm, _prompts(2, seed=3), n)
    assert eng.tokens_out == 2 * n and eng.decode_steps == n - 1
    # a prefill is called and not waited for: no fetch of its own
    assert eng.prefill_rows == 2
    admission = ["span:decode.admit", "span:decode.call"] \
        * (eng.prefill_steps - 1) + ["counter:serve"]    # submit's depth
    assert sorted(calls) == sorted(one + admission)
    # a pass that calls a step: itself, step > call; each but the first
    # then reads the pass before (step > fetch, sample), and a last pass
    # reads the last step
    called = ["span:decode.tick", "span:decode.step", "span:decode.call"]
    read = ["span:decode.fetch", "span:decode.sample"]
    assert sorted(one) == sorted(called * (n - 1) + read * (n - 1)
                                 + ["span:decode.tick", "span:decode.admit",
                                    "span:decode.call", "counter:serve"])


def _inside(child, parent, slack=0.2):
    return parent["ts"] - slack <= child["ts"] and child["ts"] \
        + child["dur"] <= parent["ts"] + parent["dur"] + slack


def test_call_and_fetch_nest_in_step_and_admit_and_cover_them(tracer, lm):
    """``decode.call`` is the host's part up to the executable's return,
    ``decode.fetch`` the one ``device_get``, of the calls of the pass before:
    an admission holds its call alone, a step its call and then the fetch;
    what is left of a step is under half a millisecond by the median."""
    eng, _rows = _run_closed(lm, _prompts(5, seed=1), 5)
    calls, fetches = _spans(tracer, "decode.call"), \
        _spans(tracer, "decode.fetch")
    ticks = sorted(_spans(tracer, "decode.tick"), key=lambda t: t["ts"])
    for p in _spans(tracer, "decode.admit"):
        mine = [c for c in calls + fetches if _inside(c, p)]
        assert [(c["name"], c["args"]["program"]) for c in mine] \
            == [("decode.call", "decode_prefill")], (p, mine)
    left = []
    steps = _spans(tracer, "decode.step")
    for p in steps:
        mine = sorted([c for c in calls + fetches if _inside(c, p)],
                      key=lambda c: c["ts"])
        assert [c["name"] for c in mine] in (
            ["decode.call"], ["decode.call", "decode.fetch"]), (p, mine)
        assert mine[0]["args"]["program"] == "decode_step"
        assert all(c["tid"] == p["tid"] for c in mine)
        left.append(p["dur"] - sum(c["dur"] for c in mine))
    assert min(left) >= -0.5
    assert sorted(left)[len(left) // 2] < 500.0, left     # microseconds
    # every call is read once: a fetch brings the int32 tokens of two slots
    # for each call of the pass before, whose number it carries
    assert len(calls) == eng.decode_steps + eng.prefill_steps \
        == sum(f["args"]["calls"] for f in fetches)
    for f in fetches:
        assert f["args"]["bytes"] == 8 * f["args"]["calls"]
        mine = [i for i, t in enumerate(ticks, 1) if _inside(f, t)]
        assert mine == [f["args"]["tick"] + 1], (f, mine)
    # all but the first step's and the last pass's lie in a step
    assert sum(1 for f in fetches if any(_inside(f, p) for p in steps)) \
        == len(fetches) - 1 == len(steps) - 1
    # a working engine never slept
    assert not _spans(tracer, "decode.idle")


def test_an_idle_engine_leaves_idle_spans_outside_any_tick(tracer, lm):
    import time
    with DecodeEngine(lm, slots=2, page=16) as eng:
        time.sleep(0.3)
        eng.generate(_prompts(1)[0], 3)
        time.sleep(0.15)
    idle = _spans(tracer, "decode.idle")
    ticks = _spans(tracer, "decode.tick")
    assert len(idle) >= 3 and ticks
    assert {e["tid"] for e in idle} == {e["tid"] for e in ticks}
    # one span a sleep of at most the queue's slice; none inside a tick
    assert all(e["dur"] <= 1e6 * (eng.queue._SLICE + 0.5) for e in idle)
    assert sum(e["dur"] for e in idle) >= 0.2e6
    assert not any(_inside(i, t, slack=0.0) for i in idle for t in ticks)


def test_a_traced_request_leaves_four_flow_events_whatever_its_length(
        tracer, lm):
    n = 9
    eng, rows = _run_closed(lm, _prompts(2, seed=4), n)
    flows = [e for e in tracer.events_tail(1 << 16)
             if e["ph"] in ("s", "t", "f")]
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    assert len(by_id) == 2 and len(flows) == 8           # not 2 x (n + 3)
    for evs in by_id.values():
        assert [(e["ph"], e["args"]["hop"]) for e in evs] == [
            ("s", "queue.enqueue"), ("t", "decode.admit"),
            ("t", "decode.first_token"), ("f", "resolve")]
    # the segments still sum to the request's time: queue up to the
    # admission, device from there to the result
    rb = telemetry.request_breakdown(
        {"traceEvents": tracer.events_tail(1 << 16)})
    assert rb["count"] == 2
    for st in rb["requests"].values():
        assert set(st["segments"]) <= {"queue", "device"}
        assert sum(st["segments"].values()) == pytest.approx(
            st["total_ms"], abs=0.01)
    # the request's span says how long it was, so time per token needs no
    # stamp per token
    done = _spans(tracer, "serve.request")
    assert sorted(e["args"]["prompt_len"] for e in done) \
        == sorted(len(p) for p in _prompts(2, seed=4))
    assert all(e["args"]["tokens"] == n for e in done)
    assert all(len(r) == e["args"]["prompt_len"] + n
               for r, e in zip(sorted(rows, key=len),
                               sorted(done,
                                      key=lambda e: e["args"]["prompt_len"])))


def test_a_full_ring_keeps_the_newest_events(tmp_path):
    tr = Tracer(str(tmp_path / "ring"), flush_every=0, ring=8)
    for i in range(20):
        tr.instant("e", n=i)
    assert tr.dropped == 12
    assert [e["args"]["n"] for e in tr.events_tail(64)] == list(range(12, 20))
    assert [e["args"]["n"] for e in tr.events_tail(3)] == [17, 18, 19]
    with open(tr.flush()) as f:
        blob = json.load(f)
    assert [e["args"]["n"] for e in blob["traceEvents"]
            if e["ph"] == "i"] == list(range(12, 20))
    assert blob["otherData"]["dropped_events"] == 12
    # a ring that never filled dropped nothing
    tr = Tracer(str(tmp_path / "ring2"), flush_every=0, ring=32)
    for i in range(20):
        tr.instant("e", n=i)
    assert tr.dropped == 0 and len(tr.events_tail(64)) == 20


def test_the_decode_line_names_the_deepest_spans(tracer):
    """``trace_report``'s ``decode:`` line: the medians of ``decode.call``
    and ``decode.fetch`` by program, the bytes a fetch brought, the seconds
    asleep, and a request's time per token after its first."""
    for ms, nbytes in ((0.4, 128), (0.6, 128), (0.5, 128)):
        tracer.complete("decode.call", 2e-3, cat="serve",
                        program="decode_step")
        tracer.complete("decode.fetch", ms / 1e3, cat="serve",
                        program="decode_step", bytes=nbytes)
    tracer.complete("decode.call", 1e-3, cat="serve",
                    program="decode_prefill")
    tracer.complete("decode.fetch", 3e-3, cat="serve",
                    program="decode_prefill", bytes=4)
    tracer.complete("decode.idle", 0.05, cat="serve")
    tracer.complete("decode.idle", 0.02, cat="serve")
    tracer.complete("serve.request", 0.110, cat="serve", status="ok",
                    queue_wait_ms=2.0, ttft_ms=10.0, prompt_len=40, tokens=11)
    tracer.complete("serve.request", 0.5, cat="serve", status="ok")  # one-shot
    # `ran_ahead` is 0 or 1 a step (a pass that calls none leaves it out):
    # the line prints the share, as `train:` does
    for ahead in (1.0, 1.0, 0.0, 1.0):
        tracer.counter("serve.decode", fill=0.5, ran_ahead=ahead)
    tracer.counter("serve.decode", fill=0.75)
    bd = telemetry.phase_breakdown(
        {"traceEvents": tracer.events_tail(1 << 16)})
    d = bd["decode"]
    assert d["fill"] == 0.75 and d["ran_ahead"] == pytest.approx(0.75)
    assert d["call_ms.step"] == pytest.approx(2.0)
    assert d["fetch_ms.step"] == pytest.approx(0.5)
    assert d["fetch_bytes.step"] == 128 and d["fetch_bytes.prefill"] == 4
    assert d["call_ms.prefill"] == pytest.approx(1.0)
    assert d["fetch_ms.prefill"] == pytest.approx(3.0)
    assert d["idle_s"] == pytest.approx(0.07)
    assert d["request_token_ms"] == pytest.approx(10.0)
    assert d["request_prompt_len"] == 40
    line = [ln for ln in telemetry.format_report(bd).splitlines()
            if ln.startswith("decode:")][0]
    for key in ("call_ms.step=2", "fetch_ms.step=0.5", "idle_s=0.07",
                "request_token_ms=10", "ran_ahead=0.75"):
        assert key in line, line
    # a trace without the engine's spans keeps the track alone
    tracer2 = Tracer("memory://unused2", flush_every=0)
    tracer2.counter("serve.decode", fill=0.5)
    assert telemetry.phase_breakdown(
        {"traceEvents": tracer2.events_tail(64)})["decode"] == {"fill": 0.5}


# ---------------------------------------------------------------------------
# the device's idle time by cause
# ---------------------------------------------------------------------------

def _recorded_rows():
    path = os.path.join(_REPO_ROOT, "tests", "benchmark",
                        "recorded_trace_rows.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)["rows"]


def _host(name, start, end, line="loop"):
    return ["/host:CPU", line, "bigdl:" + name, float(start),
            float(end - start)]


def test_idle_by_cause_names_spans_and_keeps_the_total():
    """The recorded trace holds two runs of the ResNet-50 step and the
    29.7 ms between them (the step ends at 110.38 ms, the next starts at
    140.07 ms, three tiny programs in between)."""
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    from benchmark import trace_reduce
    rows = _recorded_rows()
    reduced = trace_reduce.reduce_rows(rows)
    idle_s = reduced["window_s"] - reduced["busy_s"]
    assert idle_s > 0.02
    # no host spans: one unattributed total, the harness's line
    alone = idle_by_cause(rows)
    assert [c for c, _s in alone] == ["unattributed"]
    assert alone[0][1] == pytest.approx(idle_s, rel=1e-9)
    ms = 1e6
    host = [
        _host("iteration", 100 * ms, 138 * ms),
        _host("loss_fetch", 100 * ms, 111 * ms),     # 0.62 ms of the gap
        _host("summary", 111 * ms, 114 * ms),
        _host("triggers", 114 * ms, 115 * ms),
        # 115-116: the iteration's self time
        _host("iteration", 116 * ms, 260 * ms),
        _host("data", 116 * ms, 124 * ms),
        _host("prepare", 124 * ms, 129 * ms),
        _host("dispatch", 129 * ms, 137 * ms),
        # from 137 ms to the next step's start at 140.07: nothing open but
        # the iteration
        _host("loss_fetch", 137.5 * ms, 255 * ms),
        # another thread's spans never count
        _host("prefetch.item", 100 * ms, 140 * ms, line="worker"),
        _host("prefetch.stage", 120 * ms, 139 * ms, line="worker"),
    ]
    causes = dict(idle_by_cause(rows + host))
    assert sum(causes.values()) == pytest.approx(idle_s, rel=1e-9)
    assert not any(c.startswith("prefetch") for c in causes)
    assert causes["summary"] == pytest.approx(3e-3, rel=1e-6)
    assert causes["triggers"] == pytest.approx(1e-3, rel=1e-6)
    assert causes["dispatch"] == pytest.approx(8e-3, abs=2e-5)
    assert causes["prepare"] == pytest.approx(5e-3, abs=2e-5)
    assert 7.9e-3 < causes["data"] <= 8e-3
    assert 0 < causes["loss_fetch"] < 4e-3
    # the stretches of both iterations that no child covers
    assert 1e-3 <= causes["iteration"] < 2e-3
    # sorted, largest first
    listed = idle_by_cause(rows + host)
    assert [s for _c, s in listed] == sorted((s for _c, s in listed),
                                             reverse=True)
    # spans that cover only part of the gaps leave the rest unattributed:
    # never spread over the neighbours
    part = dict(idle_by_cause(rows + [_host("dispatch", 129 * ms, 137 * ms)]))
    assert part["dispatch"] == pytest.approx(causes["dispatch"])
    assert part["unattributed"] == pytest.approx(idle_s - part["dispatch"])
    # no thread that dispatches: nobody's spans explain the device
    assert [c for c, _s in idle_by_cause(rows + host[1:4])] == ["unattributed"]


def test_idle_by_cause_names_the_decode_engines_deepest_spans():
    """The same gap (110.38 to 140.07 ms) under the decode engine's spans:
    each stretch goes to ``decode.call``, ``decode.fetch`` or ``decode.idle``
    where one is open, and to their parents only where none is."""
    rows = _recorded_rows()
    ms = 1e6
    host = [
        _host("decode.tick", 100 * ms, 139 * ms),
        _host("decode.step", 112 * ms, 138 * ms),
        _host("decode.call", 112 * ms, 115 * ms),
        _host("decode.fetch", 115 * ms, 137 * ms),
        _host("decode.idle", 139 * ms, 139.8 * ms),
    ]
    causes = dict(idle_by_cause(rows + host))
    alone = dict(idle_by_cause(rows))
    assert sum(causes.values()) == pytest.approx(alone["unattributed"],
                                                 rel=1e-9)
    assert causes["decode.call"] == pytest.approx(3e-3, abs=2e-5)
    assert causes["decode.fetch"] == pytest.approx(22e-3, abs=2e-5)
    assert causes["decode.step"] == pytest.approx(1e-3, abs=2e-5)
    assert causes["decode.idle"] == pytest.approx(0.8e-3, abs=2e-5)
    # the tick's own: from the device's last operation to the step's span,
    # and from the step's end to its own
    assert 2e-3 < causes["decode.tick"] < 3e-3
    assert 0 < causes["unattributed"] < 0.5e-3


def test_trace_report_xplane_cli(tmp_path):
    tool = os.path.join(_REPO_ROOT, "tools", "trace_report.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, tool, "--xplane", str(tmp_path)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and "no *.xplane.pb" in p.stderr
    p = subprocess.run([sys.executable, tool], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 2 and "--xplane" in p.stderr
    assert "device idle" in telemetry.format_idle(
        [["dispatch", 0.008], ["unattributed", 0.002]])
