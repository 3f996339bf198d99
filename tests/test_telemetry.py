"""Unified run telemetry: span tracer, per-step metrics timeline,
cross-host trace merge (bigdl_tpu.utils.telemetry + tools/trace_report).

Covers the PR-4 acceptance surface: emitted traces are valid Chrome
trace-event JSON with correct span nesting; a crashed/stalled run's
trace survives (flush-on-crash, supervisor trace tail); multi-rank
traces merge into one timeline with a phase breakdown + straggler
detection; and with tracing off the train loop allocates no tracer
thread and emits nothing.
"""

import glob
import json
import logging
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
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.utils import chaos, file_io, telemetry
from bigdl_tpu.utils.supervisor import Supervisor
from bigdl_tpu.utils.telemetry import (Tracer, merge_traces,
                                       phase_breakdown, format_report)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_TRACE", raising=False)
    telemetry.set_active(None)
    chaos.clear()
    yield
    tr = telemetry.get_active()
    if tr is not None:
        tr.close()
    telemetry.set_active(None)
    chaos.clear()


def _dataset(n=64, d=6, batch=16):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(d).astype(np.float32),
                      np.float32(i % 2)) for i in range(n)]
    return DataSet.array(samples).transform(
        SampleToMiniBatch(batch, drop_last=True))


def _linear_opt(ds=None, **kw):
    return (Optimizer(nn.Sequential().add(nn.Linear(6, 2)),
                      ds or _dataset(), nn.CrossEntropyCriterion(), **kw)
            .set_optim_method(Adam(1e-2))
            .set_end_when(Trigger.max_epoch(1)))


def _load_trace(path):
    blob = json.loads(file_io.get_filesystem(path).read_bytes(path))
    assert isinstance(blob["traceEvents"], list)
    return blob


# ---------------------------------------------------------------------------
# the Tracer core
# ---------------------------------------------------------------------------

def test_spans_nest_and_json_is_perfetto_shaped(tmp_path):
    tr = Tracer(str(tmp_path), rank=0)
    with tr.span("outer", kind="test"):
        time.sleep(0.002)
        with tr.span("inner"):
            time.sleep(0.002)
        tr.instant("marker", reason="mid-outer")
    tr.counter("train", data_wait_s=0.25, step_s=0.5)
    path = tr.flush()
    blob = _load_trace(path)
    evs = blob["traceEvents"]
    # metadata names the process by rank
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert meta and "rank 0" in meta[0]["args"]["name"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    outer, inner = spans["outer"], spans["inner"]
    assert outer["args"] == {"kind": "test"}
    # nesting by time containment on the same pid/tid (how Perfetto nests)
    assert inner["pid"] == outer["pid"] == 0
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "marker"
    ctr = [e for e in evs if e["ph"] == "C"]
    assert ctr and ctr[0]["args"] == {"data_wait_s": 0.25, "step_s": 0.5}
    # every timed event carries a wall-anchored timestamp in micros
    assert all(e["ts"] > 1e12 for e in evs if e["ph"] != "M")


def test_ring_bounds_memory_and_counts_drops(tmp_path):
    tr = Tracer(str(tmp_path), rank=0, ring=10, flush_every=0)
    for i in range(25):
        tr.instant(f"e{i}")
    assert len(tr.events_tail(100)) == 10
    assert tr.dropped == 15
    blob = _load_trace(tr.flush())
    assert blob["otherData"]["dropped_events"] == 15
    names = [e["name"] for e in blob["traceEvents"] if e["ph"] == "i"]
    assert names == [f"e{i}" for i in range(15, 25)]  # newest survive


def test_flush_through_memory_scheme_and_autoflush():
    dir_ = f"memory://telemetry_{os.getpid()}"
    tr = Tracer(dir_, rank=3, flush_every=2)
    tr.instant("a")
    tr.instant("b")  # second append crosses flush_every -> inline flush
    blob = _load_trace(tr.path)
    assert blob["otherData"]["rank"] == 3
    assert [e["name"] for e in blob["traceEvents"]
            if e["ph"] == "i"] == ["a", "b"]


def test_worker_threads_get_named_tracks(tmp_path):
    tr = Tracer(str(tmp_path), rank=0)
    telemetry.set_active(tr)

    def worker():
        telemetry.thread_name("my-worker")
        telemetry.complete("prefetch.item", 0.004)

    t = threading.Thread(target=worker, name="py-worker")
    t.start()
    t.join()
    with telemetry.span("data"):
        pass
    blob = _load_trace(tr.flush())
    names = {e["args"]["name"] for e in blob["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "my-worker" in names
    spans = {e["name"]: e for e in blob["traceEvents"] if e["ph"] == "X"}
    assert spans["prefetch.item"]["tid"] != spans["data"]["tid"]


# ---------------------------------------------------------------------------
# disabled mode: zero overhead, no thread, no events
# ---------------------------------------------------------------------------

def test_disabled_mode_is_inert_and_allocation_free(tmp_path):
    assert not telemetry.enabled()
    assert telemetry.maybe_start() is None
    # module helpers hand out one shared no-op singleton and emit nothing
    s1, s2 = telemetry.span("data"), telemetry.span("step", x=1)
    assert s1 is s2
    with s1:
        pass
    telemetry.complete("step", 0.1)
    telemetry.instant("x")
    telemetry.counter("train", v=1.0)
    threads_before = threading.active_count()
    opt = _linear_opt()
    opt.optimize()
    assert telemetry.get_active() is None
    # the tracer has no thread even when ON; OFF certainly adds none
    assert threading.active_count() <= threads_before
    assert glob.glob(str(tmp_path / "trace.*.json")) == []


# ---------------------------------------------------------------------------
# instrumented training: spans, counters, prefetch worker, chaos markers
# ---------------------------------------------------------------------------

def test_traced_lenet_run_has_phase_spans_and_counters(tmp_path,
                                                       monkeypatch):
    """The acceptance scenario: a 5-step LeNet CPU run under
    BIGDL_TPU_TRACE produces per-rank Perfetto-loadable JSON whose
    trace_report breakdown shows data/step/checkpoint spans and a
    data_wait_fraction in [0, 1]."""
    from bigdl_tpu.models import LeNet5
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv("BIGDL_TPU_TRACE", str(trace_dir))
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(28, 28, 1)).astype(np.float32),
                      np.int32(i % 10)) for i in range(5 * 64)]
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(64, drop_last=True))
    opt = (Optimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_epoch(1))
           .set_checkpoint(str(tmp_path / "ckpt"),
                           Trigger.several_iteration(2)))
    opt.optimize()
    # the optimizer owned the tracer and closed (flushed) it
    assert telemetry.get_active() is None
    files = glob.glob(str(trace_dir / "trace.*.json"))
    assert len(files) == 1
    merged = merge_traces(str(trace_dir))
    bd = phase_breakdown(merged)
    for phase in ("data", "step", "checkpoint"):
        assert bd["phases"][phase]["count"] >= 1, bd["phases"]
    assert bd["phases"]["step"]["count"] == 5
    assert 0.0 <= bd["data_wait_fraction"] <= 1.0
    # per-step counter track: what the host observed, under names that
    # claim nothing about the device (a pipelined model adds its
    # schedule's pipe_bubble_fraction)
    ctr = [e for e in merged["traceEvents"]
           if e["ph"] == "C" and e["name"] == "train"]
    assert len(ctr) == 5
    assert set(ctr[0]["args"]) == {"data_wait_s", "step_s",
                                   "records_per_sec",
                                   "prefetch_queue_depth", "ran_ahead"}
    # the prefetch worker produced on its own named thread track
    spans = [e for e in merged["traceEvents"]
             if e["ph"] == "X" and e["name"] == "prefetch.item"]
    step = next(e for e in merged["traceEvents"]
                if e["ph"] == "X" and e["name"] == "step")
    assert spans and all(s["tid"] != step["tid"] for s in spans)
    # checkpoint IO spans from file_io under the optimizer's checkpoint
    assert bd["phases"]["ckpt.write"]["count"] >= 2
    # the report renders
    text = format_report(bd, merged)
    assert "data_wait_fraction" in text and "step" in text


def test_flush_on_crash_preserves_chaos_marker(tmp_path, monkeypatch):
    """A run that dies mid-epoch still leaves a loadable trace whose
    last events include the injected fault marker (chaos instant)."""
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv("BIGDL_TPU_TRACE", str(trace_dir))
    with chaos.scoped("data.batch=fail@2"):
        opt = _linear_opt()  # no checkpoint path: the failure re-raises
        with pytest.raises(chaos.ChaosFault):
            opt.optimize()
    merged = merge_traces(str(trace_dir))
    names = [e["name"] for e in merged["traceEvents"] if e["ph"] == "i"]
    assert "chaos:data.batch" in names
    bd = phase_breakdown(merged)
    assert bd["phases"].get("data", {}).get("count", 0) >= 1
    assert bd["instants"]["chaos:data.batch"] == 1


def test_evaluator_and_predictor_spans(tmp_path):
    from bigdl_tpu.optim import Evaluator, Predictor, Top1Accuracy
    tr = Tracer(str(tmp_path), rank=0)
    telemetry.set_active(tr)
    model = nn.Sequential().add(nn.Linear(6, 2)).add(nn.LogSoftMax())
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(32)]
    Evaluator(model).test(DataSet.array(samples), [Top1Accuracy()],
                          batch_size=16)
    Predictor(model, batch_size=16).predict(DataSet.array(samples))
    tr.close()
    blob = _load_trace(tr.path)
    names = {e["name"] for e in blob["traceEvents"] if e["ph"] == "X"}
    assert {"evaluate", "eval.batch", "predict",
            "predict.batch"} <= names


# ---------------------------------------------------------------------------
# supervisor integration: trace tail + flush-on-stall
# ---------------------------------------------------------------------------

def test_crash_report_embeds_trace_tail_and_flushes(tmp_path):
    tr = Tracer(str(tmp_path / "trace"), rank=0, flush_every=0)
    telemetry.set_active(tr)
    with tr.span("step", neval=7):
        pass
    sup = Supervisor({"step": 1.0}, report_dir=str(tmp_path))
    path = sup._write_report("step", 2.0, 1.0, {}, "test stall")
    rep = json.loads(file_io.get_filesystem(path).read_bytes(path))
    tail_names = [e["name"] for e in rep["trace_tail"]]
    assert "step" in tail_names
    # flush-on-crash: the trace file exists WITHOUT close() ever running,
    # and carries the supervisor's stall marker
    blob = _load_trace(tr.path)
    names = [e["name"] for e in blob["traceEvents"]]
    assert "stall" in names
    tr.close()


def test_crash_report_without_tracer_has_no_tail(tmp_path):
    sup = Supervisor({"step": 1.0}, report_dir=str(tmp_path))
    rep = sup.crash_report("step", 2.0, 1.0, {})
    assert "trace_tail" not in rep


# ---------------------------------------------------------------------------
# multi-rank merge + phase breakdown + straggler detection
# ---------------------------------------------------------------------------

def _write_rank_trace(dir_, rank, step_s, steps=4):
    tr = Tracer(str(dir_), rank=rank, flush_every=0)
    for i in range(steps):
        tr.complete("data", 0.002, neval=i)
        tr.complete("step", step_s, neval=i)
    tr.flush()


def test_merge_and_straggler_rank_detection(tmp_path):
    _write_rank_trace(tmp_path, 0, step_s=0.010)
    _write_rank_trace(tmp_path, 1, step_s=0.100)  # the slow host
    merged = merge_traces(str(tmp_path))
    assert merged["otherData"]["ranks"] == [0, 1]
    assert {e["pid"] for e in merged["traceEvents"]
            if e["ph"] == "X"} == {0, 1}
    # time-sorted with metadata first
    ts = [e["ts"] for e in merged["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    bd = phase_breakdown(merged)
    assert bd["phases"]["step"]["count"] == 8
    assert set(bd["ranks"]) == {"0", "1"}
    assert bd["ranks"]["1"]["step_mean_s"] == pytest.approx(0.1, rel=0.01)
    stragglers = bd["straggler_ranks"]
    assert [s["rank"] for s in stragglers] == [1]
    assert stragglers[0]["x_median"] == pytest.approx(10.0, rel=0.05)
    assert "STRAGGLER rank 1" in format_report(bd, merged)


def test_merge_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        merge_traces(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        merge_traces(str(tmp_path / "missing"))


def test_trace_report_cli(tmp_path):
    _write_rank_trace(tmp_path, 0, step_s=0.004)
    merged_out = tmp_path / "merged.json"
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "trace_report.py"),
         str(tmp_path), "--json", "--out", str(merged_out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    assert res.returncode == 0, res.stderr
    bd = json.loads(res.stdout)
    assert bd["phases"]["step"]["count"] == 4
    assert merged_out.exists()
    # empty dir -> non-zero exit
    empty = tmp_path / "empty"
    empty.mkdir()
    res2 = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "trace_report.py"), str(empty)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    assert res2.returncode != 0


def test_train_track_promotes_ran_ahead_to_the_training_line(tmp_path):
    """A synthetic `train` track of two epochs of four steps: the report's
    `train:` line gives the steps and the share of them that were called
    while the step before was in flight; a trace without the series (an
    older program's) has no such line."""
    tr = Tracer(str(tmp_path), rank=0, flush_every=0)
    for i in range(8):
        tr.complete("step", 0.004, neval=i + 1)
        tr.counter("train", step_s=0.004, ran_ahead=float(i % 4 != 0))
    tr.close()
    bd = phase_breakdown(merge_traces(str(tmp_path)))
    assert bd["train"] == {"steps": 8, "ran_ahead": 0.75}
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "trace_report.py"), str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    assert res.returncode == 0, res.stderr
    assert "train: steps=8  ran_ahead=0.75" in res.stdout.splitlines()
    older = phase_breakdown({"traceEvents": [
        {"ph": "C", "name": "train", "ts": 1.0, "args": {"step_s": 0.004}}]})
    assert older["train"] == {}
    assert "train:" not in telemetry.format_report(older)


# ---------------------------------------------------------------------------
# Metrics snapshot/summary + the epoch-done log line
# ---------------------------------------------------------------------------

def test_metrics_snapshot_and_summary():
    m = Metrics()
    m.add("get batch time average", 0.2)
    m.add("get batch time average", 0.4)
    m.set("dropped iterations", 3.0)
    snap = m.snapshot()
    assert snap["get batch time average"] == {
        "mean": pytest.approx(0.3), "count": 2,
        "total": pytest.approx(0.6)}
    assert snap["dropped iterations"]["count"] == 1
    s = m.summary()
    assert "get batch time average" in s
    assert "mean 0.3" in s and "count 2" in s and "total 0.6" in s


def test_epoch_done_line_prints_metrics_summary(caplog):
    caplog.set_level(logging.INFO, logger="bigdl_tpu")
    opt = _linear_opt()
    opt.optimize()
    done = [r.message for r in caplog.records
            if "done:" in r.message and "Epoch" in r.message]
    assert done, "no epoch-done log line"
    assert "get batch time average" in done[-1]
    assert "mean" in done[-1] and "count" in done[-1]


def test_train_summary_writes_all_three_reference_scalars(tmp_path):
    """Reference parity (TrainSummary.scala tags): Loss + LearningRate +
    Throughput land for every logged iteration."""
    from bigdl_tpu.visualization import TrainSummary
    ts = TrainSummary(str(tmp_path), "job")
    opt = _linear_opt().set_train_summary(ts).set_log_interval(1)
    opt.optimize()
    loss = ts.read_scalar("Loss")
    assert len(loss) >= 2
    assert len(ts.read_scalar("LearningRate")) == len(loss)
    thr = ts.read_scalar("Throughput")
    assert len(thr) == len(loss)
    assert all(v > 0 for _, v, _ in thr)
    ts.close()
