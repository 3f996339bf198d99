"""The reduction from a profiler trace to busy time, idle share and time
per operation: on a hand-made trace with a known answer, and on a small
trace recorded on a TPU v5e (rows as ``trace_reduce.read_xplane`` gives
them, kept beside this file)."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def _rows(events, plane=DEV, line=trace_reduce.OP_LINE):
    return [[plane, line, name, float(s), float(d)] for name, s, d in events]


def test_hand_made_trace_has_the_known_answer():
    # 0..1000 ns: a(0-100) gap b(200-300) ... ; no trim
    rows = _rows([("a", 0, 100), ("b", 200, 100), ("a", 400, 100),
                  ("c", 900, 100)])
    out = trace_reduce.reduce_rows(rows, trim=0.0)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["idle_share"] == pytest.approx(0.6)
    assert dict(map(tuple, out["ops"])) == pytest.approx(
        {"a": 200e-9, "b": 100e-9, "c": 100e-9})
    assert out["ops"][0][0] == "a"


def test_overlapping_events_count_once_and_children_take_self_time():
    # a `while` of 600 ns holds two body operations of 200 ns each; another
    # line's event overlaps the second half
    rows = _rows([("while", 0, 600), ("body", 100, 200), ("body", 350, 200),
                  ("tail", 800, 200)])
    out = trace_reduce.reduce_rows(rows, trim=0.0)
    assert out["busy_s"] == pytest.approx(800e-9)       # not 1200
    ops = dict(map(tuple, out["ops"]))
    assert ops["while"] == pytest.approx(200e-9)        # 600 - 2 x 200
    assert ops["body"] == pytest.approx(400e-9)
    assert sum(ops.values()) == pytest.approx(out["busy_s"])


def test_trim_cuts_the_profilers_own_start_and_stop():
    # busy 10 % of the time except a long stall at the start
    events = [("op", 0, 10)] + [("op", 1000 + 100 * i, 50) for i in range(90)]
    out = trace_reduce.reduce_rows(_rows(events), trim=0.1)
    assert out["idle_share"] == pytest.approx(0.5, abs=0.02)


def test_lines_that_are_not_operations_and_host_planes_are_ignored():
    rows = (_rows([("op", 0, 100), ("op", 900, 100)])
            + _rows([("jit_step", 0, 1000)], line="XLA Modules")
            + _rows([("1", 0, 1000)], line="Steps")
            + _rows([("python", 0, 1000)], plane="/host:CPU", line="python"))
    out = trace_reduce.reduce_rows(rows, trim=0.0)
    assert out["busy_s"] == pytest.approx(200e-9)
    assert trace_reduce.reduce_rows(
        _rows([("python", 0, 10)], plane="/host:CPU")) is None


def test_several_devices_are_averaged():
    rows = (_rows([("op", 0, 100), ("op", 900, 100)])
            + _rows([("op", 0, 500), ("op", 900, 100)],
                    plane="/device:TPU:1"))
    out = trace_reduce.reduce_rows(rows, trim=0.0)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(400e-9)
    assert dict(map(tuple, out["ops"]))["op"] == pytest.approx(400e-9)


def test_recorded_v5e_trace():
    """Rows recorded on a TPU v5e in PR 24: two steps of resnet50.train and
    the gap between them.  The expected busy time was worked out another
    way, by marking a grid of 100 ns cells, when the rows were recorded."""
    path = os.path.join(HERE, "recorded_trace_rows.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    out = trace_reduce.reduce_rows(rec["rows"])
    assert out["devices"] == rec["expect"]["devices"]
    assert out["window_s"] == pytest.approx(rec["expect"]["window_s"])
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s_grid"],
                                          rel=1e-3)
    # a step of 108 ms every 139 ms: the device idles about 15 % of this cut
    assert 0.10 < out["idle_share"] < 0.20
    assert sum(s for _n, s in out["ops"]) == pytest.approx(out["busy_s"],
                                                           rel=1e-6)
    assert sum(s for _n, s in out["kinds"]) == pytest.approx(out["busy_s"],
                                                             rel=1e-6)
    assert out["ops"][0][0] == rec["expect"]["top_op"]
    # the lines that hold whole programs and steps were not counted: alone
    # they would make the device busy for the whole window
    assert any(r[1] == "XLA Modules" for r in rec["rows"])
    # whole programs, untrimmed: the train step ran twice, 108.4 ms each
    name, runs, seconds = out["modules"][0]
    assert (name, runs) == ("jit_step", 2)
    assert seconds == pytest.approx(0.216752732)


def test_step_device_ms_is_read_from_the_trace_alone():
    """The reader takes the program that took most of the traced time and
    gives the mean length of its runs; it wants nothing but the trace."""
    from benchmark import harness
    with gzip.open(os.path.join(HERE, "recorded_trace_rows.json.gz"),
                   "rt") as f:
        trace = trace_reduce.reduce_rows(json.load(f)["rows"])
    d = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark",
                     "layer_metrics")
    step = harness.load_module(os.path.join(d, "step_device_ms.train.py"),
                               "m_step_device_ms")
    assert step.read({"trace": trace}) == pytest.approx(108.376366)
    assert step.read({"trace": dict(trace, modules=[])}) is None
    mfu = harness.load_module(os.path.join(d, "step_mfu_pct.train.py"),
                              "m_step_mfu_pct")
    # 1e12 operations in 108.4 ms on a chip of 197e12 a second
    got = mfu.read({"trace": trace, "device": {"kind": "TPU v5 lite"},
                    "flops_per_record": 1e12 / 256, "batch": 256, "n_dev": 1})
    assert got == pytest.approx(100 * 1e12 / 0.108376366 / 197e12)


def test_modules_are_averaged_over_devices():
    rows = (_rows([("jit_step(1)", 0, 100), ("jit_step(1)", 200, 300),
                   ("jit_other(2)", 600, 10)], line="XLA Modules")
            + _rows([("jit_step(1)", 0, 200)], plane="/device:TPU:1",
                    line="XLA Modules")
            + _rows([("op", 0, 100)]) + _rows([("op", 0, 100)],
                                              plane="/device:TPU:1"))
    out = trace_reduce.reduce_rows(rows, trim=0.0)
    assert out["modules"] == [["jit_step", 1.5, pytest.approx(300e-9)],
                              ["jit_other", 0.5, pytest.approx(5e-9)]]


def test_read_xplane_reads_a_profile_written_here(tmp_path):
    """The reader on a real file: a CPU profile has no device plane, so the
    rows are empty and the reduction says so."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    rows = trace_reduce.read_xplane(path)
    assert trace_reduce.reduce_rows(rows) is None or rows
