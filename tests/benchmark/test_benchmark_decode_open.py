"""The open-loop decode cell (ISSUE 39), which waits under
``benchmark/pending/`` for a ``benchmark`` PR to admit it:
``gpt2m.decode.open`` rehearsed through ``benchmark/run.py`` from the merged
copy that ``pending/apply.py`` writes, the entries it would add to
``BENCHMARK.json`` against the contract's limits, the arrival schedule's two
properties, and the five readers of the decode engine's spans, each on a
hand-written span list, on the spans a rehearsal really left, and on
``facts`` without ``spans``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
PENDING = os.path.join(REPO, "benchmark", "pending")
CELL = "gpt2m.decode.open"
APPLY = harness.load_module(os.path.join(PENDING, "apply.py"),
                            "t_pending_apply")
BENCH = APPLY.merged(CELL)      # BENCHMARK.json as it would be
READERS = ("queue_wait_p95_ms.decode", "ttft_p95_ms.decode",
           "token_gap_p95_ms.decode", "tick_host_ms.decode",
           "no_work_pct.decode")


def _env():
    """One CPU device, no compile cache shared with other runs."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    env["BIGDL_TPU_XLA_CACHE"] = "0"
    return env


def _reader(name):
    return harness.load_module(
        os.path.join(PENDING, "layer_metrics", name + ".py"),
        "open_reader_" + name.replace(".", "_"))


@pytest.fixture(scope="module")
def merged(tmp_path_factory):
    """A copy of the benchmark with the pending cell merged in: the
    arguments that make ``benchmark/run.py`` run from it."""
    out = tmp_path_factory.mktemp("merged")
    APPLY.apply(str(out), CELL)
    return ["--bench-dir", str(out / "benchmark"),
            "--benchmark-json", str(out / "BENCHMARK.json")]


def _driver():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "decode_open.py"),
        "t_decode_open")


def _traffic():
    return json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                       "decode_open_r70.json")))


# ---------------------------------------------------------------------------
# the cell, rehearsed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_runs_the_open_loop_end_to_end(trace, merged):
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "2", "--trace", str(trace), "--rehearse"] + merged,
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    checks = {c["name"]: c for c in map(json.loads, lines)
              if c.get("obs") == "check"}
    assert line["correct"] is True, checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(checks) == {"logit_gap", "wrong_row_lengths",
                           "compiles_in_window", "client_threads_left",
                           "late_submits", "rejected"}
    assert checks["rejected"]["limit"] == 0
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[kind]
            if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == want
    if trace:
        assert set(READERS) <= want
    else:
        assert want == {"decode_tokens_per_s", "request_p95_ms", "setup_s"}
    assert all(m["value"] == "not measured" for m in line["metrics"].values())
    # arrivals at the file's rate, whatever the engine answered: 4 a second
    # over two seconds, the schedule's rounds of 6
    window = next(json.loads(ln) for ln in lines if '"obs": "window"' in ln)
    assert 6 <= window["submitted"] <= 10


_PROBE = """
import json, sys, time
t0 = time.perf_counter()
sys.path.insert(0, {repo!r})
from benchmark import harness
cell = harness.Cell({cell!r}, bench_dir={bench_dir!r},
                    benchmark_json={benchmark_json!r})
run = harness.Run(cell, 2147483777, 2.0, True, True, t0)
harness.device_phase(run)
out = cell.driver_mod.run(run)
facts = out["facts"]
readers = cell.layer_readers()
spans = facts["spans"]
print(json.dumps({{"values": {{n: readers[n].read(facts) for n in {names!r}}},
                  "no_spans": {{n: readers[n].read(dict(facts, spans=None))
                               for n in {names!r}}},
                  "window": facts["window"], "correct": run.correct,
                  "names": sorted({{e["name"] for e in spans}}),
                  "flows": sum(1 for e in spans if e["ph"] in "stf"),
                  "requests": sum(1 for e in spans
                                  if e["name"] == "serve.request")}}))
"""


def test_the_readers_read_what_a_rehearsal_really_left(merged):
    """The driver's ``facts`` as the program of this tree fills them: every
    reader finds its spans under the names and arguments it looks for."""
    p = subprocess.run(
        [sys.executable, "-c", _PROBE.format(
            repo=REPO, cell=CELL, names=list(READERS), bench_dir=merged[1],
            benchmark_json=merged[3])],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert got["correct"] is True
    v = got["values"]
    assert all(isinstance(v[n], float) for n in READERS), v
    assert 0 <= v["queue_wait_p95_ms.decode"] <= v["ttft_p95_ms.decode"]
    assert v["token_gap_p95_ms.decode"] > 0 and v["tick_host_ms.decode"] > 0
    # four requests a second against a tiny model: the engine mostly sleeps
    assert 20.0 < v["no_work_pct.decode"] <= 100.0
    assert all(x is None for x in got["no_spans"].values())
    lo, hi = got["window"]
    assert 1.9e6 < hi - lo < 2.6e6
    assert {"decode.tick", "decode.step", "decode.admit", "decode.call",
            "decode.fetch", "decode.idle", "serve.request",
            "benchmark.window_open", "benchmark.window_close"} \
        <= set(got["names"])
    assert got["flows"] == 4 * got["requests"]      # no event a token


def test_the_pending_entries_keep_the_contracts_limits():
    """What a ``benchmark`` PR would paste: only additions, at the end of
    their lists, and every new metric has its reader, unit and layer."""
    now = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert CELL not in [w["name"] for w in now["workloads"]]
    assert BENCH["workloads"][:-1] == now["workloads"]
    entry = BENCH["workloads"][-1]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, "gpt2_medium", "decode_open_r70", 1)
    assert len(entry["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert [m["name"] for m in BENCH["end_to_end"]
            if CELL in m.get("workloads", [])] \
        == ["decode_tokens_per_s", "request_p95_ms"]
    old = {m["name"]: m for m in now["end_to_end"] + now["per_layer"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in old:    # nothing changed but the cell appended
            was = old[m["name"]]
            assert {k: v for k, v in m.items() if k != "workloads"} \
                == {k: v for k, v in was.items() if k != "workloads"}
            assert m.get("workloads", [])[:len(was.get("workloads", []))] \
                == was.get("workloads", [])
    new = BENCH["per_layer"][len(now["per_layer"]):]
    assert [m["name"] for m in new] == list(READERS)
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        mod = _reader(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["moves"] in e2e and CELL in e2e[m["moves"]]["workloads"]
        assert m["source"] == "program_span" and m["workloads"] == [CELL]
        assert m["better"] in ("lower", "higher")
        assert mod.read({}) is None


# ---------------------------------------------------------------------------
# the arrival schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2147483659])
def test_every_round_of_arrivals_takes_the_same_time_and_gaps(seed):
    drv, tr = _driver(), _traffic()
    k = tr["grid"][0] * tr["grid"][1]
    rate = tr["arrival_rate_per_s"]
    assert k == 48 and rate > 0
    gaps = drv.arrival_gaps(tr, seed, 5 * k + 7)
    assert len(gaps) == 5 * k + 7 and (gaps > 0).all()
    rounds = gaps[:5 * k].reshape(5, k)
    # each round of 48 arrivals takes 48 / rate seconds ...
    assert np.allclose(rounds.sum(axis=1), k / rate, rtol=1e-12)
    # ... and holds every gap once: the exponential's quantiles, rescaled
    want = -np.log(1 - (np.arange(k) + 0.5) / k)
    want *= (k / rate) / want.sum()
    for row in rounds:
        assert np.allclose(np.sort(row), want, rtol=1e-12)
    assert not np.array_equal(rounds[0], rounds[1])     # an order of its own
    # bursts: the mean is 1 / rate, the shortest gap a hundredth of it
    assert want.min() < 0.011 / rate and want.max() > 4 / rate
    # two seeds: the same multiset, another order; one seed: the same list
    other = drv.arrival_gaps(tr, seed + 1, 5 * k)
    assert np.allclose(np.sort(other), np.sort(gaps[:5 * k]), rtol=1e-12)
    assert not np.array_equal(other, gaps[:5 * k])
    assert np.array_equal(drv.arrival_gaps(tr, seed, 5 * k), gaps[:5 * k])
    # and not the lengths' order: the two grids are drawn apart
    order = np.argsort(np.argsort(rounds[0]))
    lengths = np.random.default_rng(seed).permutation(k)
    assert not np.array_equal(order, lengths)


def test_the_traffic_file_holds_what_the_driver_reads():
    tr = _traffic()
    assert tr["driver"] == "decode_open"
    assert (tr["slots"], tr["page"], tr["max_len"], tr["queue_limit"]) \
        == (64, 512, 512, 256)
    closed = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                         "decode_closed_c48.json")))
    for key in ("prompt_len", "output_len", "grid", "sample_requests",
                "trace_seconds"):
        assert tr[key] == closed[key]
    assert tr["ramp_seconds"] == 6 and tr["tail_wait_seconds"] == 60
    assert tr["late_submit_ms"] == 5
    assert isinstance(tr["arrival_rate_per_s"], float)
    assert set(tr["rehearse"]) <= set(tr)


# ---------------------------------------------------------------------------
# the readers, on hand-written spans
# ---------------------------------------------------------------------------

def _x(name, ts_ms, dur_ms, tid=7, **args):
    ev = {"name": name, "cat": "serve", "ph": "X", "ts": ts_ms * 1e3,
          "dur": dur_ms * 1e3, "pid": 0, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def _engine_spans():
    """Ten ticks 10 ms apart from t = 100 ms, each 8 ms long with a step of
    5 ms (call 1, fetch 3.5); the ticks at 130 and 160 ms start 6 ms late
    and admit first (call 0.5, fetch 1.5); the engine sleeps 50 ms before
    the first tick and 20 ms from 200; a tick at 300 ms lies outside the
    window (90 to 250 ms)."""
    out = [_x("decode.idle", 45.0, 50.0)]
    for i in range(10):
        late = 6.0 if i in (3, 6) else 0.0
        t0 = 100.0 + 10.0 * i + late
        out.append(_x("decode.tick", t0, 8.0 + (2.0 if late else 0.0),
                      active=0 if i == 0 else 4 + i, admitted=int(bool(late))))
        at = t0 + 0.2
        if late:
            out.append(_x("decode.admit", at, 2.2, prompt_len=9, bucket=16,
                          slot=1))
            out.append(_x("decode.call", at, 0.5, program="decode_prefill"))
            out.append(_x("decode.fetch", at + 0.5, 1.5,
                          program="decode_prefill", bytes=4))
            at += 2.2
        out.append(_x("decode.step", at, 5.0, active=5 + i))
        out.append(_x("decode.call", at, 1.0, program="decode_step"))
        out.append(_x("decode.fetch", at + 1.0, 3.5, program="decode_step",
                      bytes=256))
        out.append(_x("decode.sample", at + 5.0, 0.3, active=5 + i))
        # the track is no span, and another thread's fetch is nobody's child
        out.append({"name": "serve.decode", "ph": "C", "ts": (t0 + 7.9) * 1e3,
                    "pid": 0, "tid": 0, "args": {"fill": 0.5}})
        out.append(_x("decode.fetch", t0 + 1.0, 100.0, tid=8,
                      program="decode_step", bytes=256))
    out.append(_x("decode.idle", 200.0, 20.0))
    out.append(_x("decode.idle", 240.0, 30.0))        # 10 ms of it inside
    out.append(_x("decode.tick", 300.0, 500.0, active=9, admitted=0))
    out.append(_x("decode.step", 300.2, 499.0, active=9))
    out.append(_x("decode.fetch", 301.0, 498.0, program="decode_step",
                  bytes=256))
    # requests: the span starts at the submission; 20 inside the window,
    # one before it and one after with far larger stamps
    for i in range(20):
        out.append(_x("serve.request", 100.0 + 5 * i, 60.0, tid=9,
                      status="ok", queue_wait_ms=float(i), ttft_ms=10.0 + i,
                      prompt_len=9, tokens=5))
    out.append(_x("serve.request", 50.0, 60.0, tid=9, status="ok",
                  queue_wait_ms=900.0, ttft_ms=950.0, prompt_len=9, tokens=5))
    out.append(_x("serve.request", 260.0, 60.0, tid=9, status="ok",
                  queue_wait_ms=900.0, ttft_ms=950.0, prompt_len=9, tokens=5))
    # a one-shot request has neither stamp
    out.append(_x("serve.request", 120.0, 3.0, tid=9, status="ok"))
    return out


FACTS = {"spans": _engine_spans(), "window": (90e3, 250e3)}


@pytest.mark.parametrize("name,want", [
    # 20 requests, waits 0..19 ms: the 95th percentile by interpolation
    ("queue_wait_p95_ms.decode", 18.05),
    ("ttft_p95_ms.decode", 28.05),
    # nine periods: 10 ms but 16 into a late tick and 4 out of it; by the
    # slots that waited (5..13) the heaviest twentieth lies in the 16 ms
    ("token_gap_p95_ms.decode", 16.0),
    # a tick less its fetches: 8 - 3.5, and 10 - 5 in the two that admit
    ("tick_host_ms.decode", 4.5),
    # asleep 95-100 (the first's end), 200-220 and 240-250 of 160 ms
    ("no_work_pct.decode", 100.0 * 35.0 / 160.0),
])
def test_span_readers_on_hand_written_spans(name, want):
    reader = _reader(name)
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry["workloads"] == [CELL] and entry["source"] == "program_span"
    assert reader.read(FACTS) == pytest.approx(want)
    # facts without spans, without a window, or with neither: nothing
    assert reader.read(dict(FACTS, spans=None)) is None
    assert reader.read(dict(FACTS, spans=[])) is None
    assert reader.read(dict(FACTS, window=None)) is None
    assert reader.read(dict(FACTS, window=(None, None))) is None
    assert reader.read({}) is None


def test_the_token_gap_is_weighted_by_the_slots_that_waited():
    reader = _reader("token_gap_p95_ms.decode")
    spans = _engine_spans()
    # with one slot carried into the late ticks and 40 into the others the
    # late periods are under a twentieth of the weight
    for e in spans:
        if e["name"] == "decode.tick":
            e["args"]["active"] = 1 if e["args"]["admitted"] else 40
    assert reader.read(dict(FACTS, spans=spans)) == pytest.approx(10.0)
    # a tick that held no step (every slot failed) ends no period: with all
    # the weight on the tick at 150 ms, its period is 10 ms, and 14 from the
    # tick at 136 once the one at 140 has no step
    spans = _engine_spans()
    for e in spans:
        if e["name"] == "decode.tick" and e["ts"] == 150e3:
            e["args"]["active"] = 10000
    assert reader.read(dict(FACTS, spans=spans)) == pytest.approx(10.0)
    spans = [e for e in spans
             if not (e["name"] == "decode.step" and 139 < e["ts"] / 1e3 < 141)]
    assert reader.read(dict(FACTS, spans=spans)) == pytest.approx(14.0)
    # nobody waited: no gap
    for e in spans:
        if e["name"] == "decode.tick":
            e["args"]["active"] = 0
    assert reader.read(dict(FACTS, spans=spans)) is None


def test_on_a_parents_spans_the_host_time_is_left_out():
    """The program before this PR: ticks, steps and admissions, no
    ``decode.fetch``, no ``decode.idle``.  The whole tick is not read as the
    host's; the engine is read as never asleep; the requests' stamps and the
    ticks' periods are there as before."""
    old = [e for e in _engine_spans()
           if e["name"] not in ("decode.fetch", "decode.call", "decode.idle")]
    facts = dict(FACTS, spans=old)
    assert _reader("tick_host_ms.decode").read(facts) is None
    assert _reader("no_work_pct.decode").read(facts) == 0.0
    assert _reader("token_gap_p95_ms.decode").read(facts) \
        == pytest.approx(16.0)
    assert _reader("queue_wait_p95_ms.decode").read(facts) \
        == pytest.approx(18.05)
