"""Compile cards + perf gate (bigdl_tpu/utils/hlostats.py — ISSUE 11).

Covers: HLO/StableHLO text analysis units (op histogram, convert
direction pairs, alias counting on the nested-brace header), the
matmul-route card showing 0 convolutions in the compiled train step, the
wire card's up-cast count bounded by the BUCKET count (not the leaf
count), the fused-update card reporting the expected buffer count +
donation aliases, card round-trip through ``memory://``, disabled-mode
inertness, the forward (serve/eval) choke point, the trace_report
counter-track section + ``--diff`` CLI, the aot quarantine log carrying
the fingerprint, and the perf gate's check logic + full CLI pass against
the committed PERF_BASELINE.json.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.utils import aot, hlostats, telemetry
from bigdl_tpu.utils.engine import Engine

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_hlostats():
    hlostats.reset()
    aot.reset()
    yield
    hlostats.reset()
    aot.reset()


def _build_lenet_step(batch_size=16):
    """The real compiled train step on device 0; fresh Optimizer so env
    knobs re-bake."""
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    Engine.reset()
    Engine.init(devices=[jax.devices()[0]])
    mesh = Engine.mesh()
    model = LeNet5(10)
    model.build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=nn.ClassNLLCriterion(),
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.01))
    step, param_sh, _ = opt._build_step(mesh)
    rng = np.random.default_rng(0)
    inp = jnp.asarray(rng.normal(size=(batch_size, 28, 28, 1)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 10, size=batch_size), jnp.int32)
    params = jax.device_put(model.params, param_sh)
    args = (params, model.state, opt.optim_method.init_state(params),
            inp, tgt, jnp.float32(0.01), jax.random.key(1))
    return step, args, opt


def _step_once(step, args):
    out = step(*args)
    jax.block_until_ready(out[3])
    return out


# ----------------------------------------------------------------------
# text-analysis units (no backend)
# ----------------------------------------------------------------------

def test_op_histogram_hlo_text():
    txt = """HloModule jit_f, is_scheduled=true
%fused (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %convert.1 = bf16[8,8]{1,0} convert(f32[8,8]{1,0} %p0)
  %convert.2 = f32[8,8]{1,0} convert(bf16[8,8]{1,0} %convert.1)
  ROOT %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %convert.2, f32[8,8]{1,0} %convert.2)
}
ENTRY %main (a: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %a = f32[8,8]{1,0} parameter(0)
  %conv = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)
  %ar = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %conv)
  %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(f32[8,8]{1,0} %ar, f32[8,8]{1,0} %a)
}
"""
    hist = hlostats.op_histogram(txt)
    assert hist["convert"] == 2
    assert hist["dot"] == 1
    assert hist["convolution"] == 1
    assert hist["all-reduce"] == 1
    assert "parameter" not in hist
    pairs = hlostats.convert_pairs(txt)
    assert pairs == {"bf16<-f32": 1, "f32<-bf16": 1}
    assert hlostats.collective_count(hist) == 1


def test_op_histogram_stablehlo_text():
    txt = """module @jit_f {
  func.func public @main(%arg0: tensor<8x8xf32>) -> tensor<128xf32> {
    %0 = stablehlo.convert %arg0 : (tensor<8x8xf32>) -> tensor<8x8xbf16>
    %1 = stablehlo.reshape %0 : (tensor<8x8xbf16>) -> tensor<64xbf16>
    %2 = stablehlo.concatenate %1, %1, dim = 0 : (tensor<64xbf16>, tensor<64xbf16>) -> tensor<128xbf16>
    %3 = stablehlo.convert %2 : (tensor<128xbf16>) -> tensor<128xf32>
    return %3 : tensor<128xf32>
  }
}
"""
    hist = hlostats.op_histogram(txt)
    assert hist["convert"] == 2
    assert hist["concatenate"] == 1
    pairs = hlostats.convert_pairs(txt)
    # the dim-prefixed dtype must parse as bf16, never "xbf16"
    assert pairs == {"bf16<-f32": 1, "f32<-bf16": 1}


def test_alias_count_nested_braces():
    hdr = ("HloModule jit_step, is_scheduled=true, input_output_alias="
           "{ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, "
           "entry_computation_layout={(f32[8]{0})->f32[8]{0}}\n%body...")
    assert hlostats.alias_count(hdr) == 2
    assert hlostats.alias_count("HloModule jit_f, is_scheduled=true\n") == 0


def test_collective_count_async_pairs_count_once():
    hist = {"all-reduce-start": 2, "all-reduce-done": 2, "all-gather": 1,
            "dot": 4}
    assert hlostats.collective_count(hist) == 3


# ----------------------------------------------------------------------
# the three structural cards (ISSUE 11 test checklist)
# ----------------------------------------------------------------------

def test_matmul_route_card_has_zero_convolutions(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "1")
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
    jax.clear_caches()
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    card = hlostats.last_card("optim.step")
    assert card is not None, "no compile card captured for the train step"
    assert card["convolutions"] == 0
    assert card["stablehlo_ops"].get("convolution", 0) == 0
    assert card["total_ops"] > 0
    assert card["cost"]["flops"] > 0
    # the pad route, for contrast, keeps its 5 conv programs
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "pad")
    jax.clear_caches()
    hlostats.reset()
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    assert hlostats.last_card("optim.step")["convolutions"] > 0


def test_wire_card_upcasts_bounded_by_bucket_count(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "1")
    monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "4")
    jax.clear_caches()
    step, args, opt = _build_lenet_step()
    _step_once(step, args)
    card = hlostats.last_card("optim.step")
    extra = card["extra"]
    assert extra["wire_leaves"] == 8      # LeNet: 4 layers x (W, b)
    assert extra["wire_buckets"] == 1     # all leaves fit one 4MB bucket
    upcasts = card["stablehlo_convert_pairs"]["f32<-bf16"]
    # THE wire invariant: up-casts per BUCKET, not per leaf
    assert upcasts == extra["wire_buckets"]
    assert upcasts < extra["wire_leaves"]
    # per-leaf wire (bucketing off) pays one up-cast per gradient leaf
    monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "0")
    jax.clear_caches()
    hlostats.reset()
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    card = hlostats.last_card("optim.step")
    assert card["extra"]["wire_buckets"] == 0
    assert card["stablehlo_convert_pairs"]["f32<-bf16"] == 8


def test_fused_card_buffer_count_and_donation(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "1")
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
    jax.clear_caches()
    step, args, opt = _build_lenet_step()
    _step_once(step, args)
    card = hlostats.last_card("optim.step")
    # LeNet params are all-f32: one dtype-homogeneous fused buffer
    assert card["extra"]["fused_buffers"] == 1
    assert card["donation"] is True
    assert card["input_output_aliases"] > 0
    # NO_DONATE compiles a step with zero aliases — the card proves it
    monkeypatch.setenv("BIGDL_TPU_NO_DONATE", "1")
    jax.clear_caches()
    hlostats.reset()
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    card = hlostats.last_card("optim.step")
    assert card["donation"] is False
    assert card["input_output_aliases"] == 0


def test_forward_card_from_sharded_forward(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "1")
    from bigdl_tpu.optim import Predictor
    model = nn.Sequential().add(nn.Linear(6, 4)).add(nn.ReLU())
    model.build(jax.random.key(0))
    out = Predictor(model).predict(
        np.random.default_rng(0).normal(size=(8, 6)).astype(np.float32))
    assert out.shape == (8, 4)
    card = hlostats.last_card("forward")
    assert card is not None
    assert card["total_ops"] > 0
    # the forward key_fields ARE fingerprinted even with the cache off:
    # the card records the key the executable would cache under
    assert card["aot_key"]
    assert hlostats.ledger().get("forward") == 1


# ----------------------------------------------------------------------
# emission: artifacts, ledger, telemetry, inertness
# ----------------------------------------------------------------------

def test_card_roundtrip_memory_scheme():
    card = hlostats.compile_card(None, None, label="unit.test",
                                 key="abc123", extra={"wire_buckets": 2})
    path = hlostats.write_card(card, "memory://cards_rt")
    assert path.endswith(".json")
    got = hlostats.read_cards("memory://cards_rt")
    assert got == [card]


def test_capture_writes_artifact_to_knob_dir(monkeypatch, tmp_path):
    d = str(tmp_path / "cards")
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", d)
    jax.clear_caches()
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    got = hlostats.read_cards(d)
    assert len(got) == 1 and got[0]["label"] == "optim.step"
    assert got[0] == hlostats.last_card("optim.step")
    assert hlostats.stats()["writes"] == 1


def test_cards_dir_beside_trace_dir(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_TRACE", "memory://tr_cards")
    monkeypatch.delenv("BIGDL_TPU_COMPILE_CARDS", raising=False)
    assert hlostats.enabled()
    assert hlostats.cards_dir() == "memory://tr_cards/cards"
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "0")
    assert not hlostats.enabled()


def test_disabled_mode_is_inert(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_COMPILE_CARDS", raising=False)
    monkeypatch.delenv("BIGDL_TPU_TRACE", raising=False)
    step, args, _ = _build_lenet_step()
    _step_once(step, args)
    assert hlostats.capture(None, None, label="x") is None
    assert hlostats.stats() == {"cards": 0, "writes": 0, "errors": 0,
                                "dropped": 0}
    assert hlostats.cards() == []


def test_card_instant_and_counter_in_trace(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_TRACE", "memory://tr_card_ev")
    tr = telemetry.Tracer("memory://tr_card_ev", rank=0)
    telemetry.set_active(tr)
    try:
        jax.clear_caches()
        step, args, _ = _build_lenet_step()
        _step_once(step, args)
    finally:
        tr.close()
    merged = telemetry.merge_traces("memory://tr_card_ev")
    bd = telemetry.phase_breakdown(merged)
    assert bd["instants"].get("compile.card", 0) >= 1
    assert "compile.total_ops" in bd["counters"]
    assert bd["counters"]["compile.total_ops"]["last"] > 0


# ----------------------------------------------------------------------
# trace_report: counter-track section, aot section, --diff
# ----------------------------------------------------------------------

def _fake_trace(dir_, step_ms=(5.0, 7.0), counters=(), rank=0):
    t = [0.0]

    def clock():
        return t[0]

    tr = telemetry.Tracer(dir_, rank=rank, clock=clock,
                          wall_clock=lambda: 1000.0)
    for ms in step_ms:
        with tr.span("step"):
            t[0] += ms / 1e3
    for track, values in counters:
        tr.counter(track, **values)
    tr.close()


def _run_cli(argv):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "trace_report.py"), *argv],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc


def test_trace_report_counter_track_cli(tmp_path):
    d = str(tmp_path / "tr")
    _fake_trace(d, counters=[
        ("zz", {"late": 3.0}), ("aa", {"early": 1.0}),
        ("aot", {"hits": 2, "misses": 1, "stores": 1, "lowers": 1,
                 "compiles": 1})])
    proc = _run_cli([d])
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.splitlines()
    rows = [ln.split()[0] for ln in lines
            if ln.startswith(("aa.", "aot.", "zz."))]
    # deterministic: sorted series order, every run
    assert rows == sorted(rows) and "aa.early" in rows and "zz.late" in rows
    # the aot counter track surfaces as its own ledger section
    aot_line = [ln for ln in lines if ln.startswith("aot ledger:")]
    assert aot_line and "hits=2" in aot_line[0] \
        and "compiles=1" in aot_line[0]
    # --json carries the parsed ledger too
    blob = json.loads(_run_cli([d, "--json"]).stdout)
    assert blob["aot"] == {"hits": 2, "misses": 1, "stores": 1,
                           "lowers": 1, "compiles": 1}


def test_trace_report_empty_dir_names_path(tmp_path):
    d = str(tmp_path / "empty")
    os.makedirs(d)
    proc = _run_cli([d])
    assert proc.returncode == 2
    assert d in proc.stderr  # the message names the offending input path


def test_trace_report_diff_cli(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _fake_trace(a, step_ms=(5.0, 5.0),
                counters=[("train", {"records_per_sec": 0.30})])
    _fake_trace(b, step_ms=(10.0, 10.0),
                counters=[("train", {"records_per_sec": 0.15})])
    proc = _run_cli([a, "--diff", b])
    assert proc.returncode == 0, proc.stderr
    assert "B/A" in proc.stdout and "train.records_per_sec" in proc.stdout
    blob = json.loads(_run_cli([a, "--diff", b, "--json"]).stdout)
    assert blob["phases"]["step"]["total_ratio"] == pytest.approx(2.0,
                                                                  rel=0.05)
    assert blob["counters"]["train.records_per_sec"]["last"] == [0.3, 0.15]
    assert blob["counters"]["train.records_per_sec"]["delta"] == pytest.approx(
        -0.15)


def test_diff_breakdowns_only_in_one_run():
    a = {"phases": {"step": {"count": 1, "total_s": 1.0, "p50_ms": 1.0}},
         "counters": {}, "data_wait_fraction": 0.1}
    b = {"phases": {}, "counters": {"aot.hits": {"count": 1, "mean": 1,
                                                 "max": 1, "last": 1}},
         "data_wait_fraction": 0.2}
    d = telemetry.diff_breakdowns(a, b)
    assert d["phases"]["step"] == {"only": "A"}
    assert d["counters"]["aot.hits"] == {"only": "B"}
    assert "only in run A" in telemetry.format_diff(d)


# ----------------------------------------------------------------------
# aot satellites: quarantine fingerprint in the log
# ----------------------------------------------------------------------

def test_quarantine_log_names_fingerprint(tmp_path, caplog):
    import logging
    d = str(tmp_path / "aotq")
    cache = aot.AOTCache(d)
    key = "deadbeef" * 8
    with open(os.path.join(d, key + ".aotx"), "wb") as f:
        f.write(b"not a framed entry")
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        assert cache.load(key) is None
    msgs = [r.getMessage() for r in caplog.records
            if "quarantining" in r.getMessage()]
    assert msgs and key in msgs[0] and "fingerprint" in msgs[0]
    assert aot.stats()["corrupt"] == 1


# ----------------------------------------------------------------------
# the perf gate
# ----------------------------------------------------------------------

def _gate_mod():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(_REPO_ROOT, "tools", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_gate_check_logic():
    gate = _gate_mod()
    baseline = {"metrics": {
        "conv_ops": {"value": 0, "match": "exact"},
        "ratio": {"value": 1.25, "match": "max"},
        "over": {"value": 0.5, "match": "max"},
        "floor": {"value": 2, "match": "min"},
        "unmeasured": {"value": 1, "match": "exact"}}}
    measured = {"conv_ops": 5, "ratio": 1.0, "over": 0.51, "floor": 3,
                "extra_new": 7}
    rows, regressions = gate.check(measured, baseline)
    # a count drifts, a ratio passes its bound, a kind the gate does not
    # know (a floor is a rate's kind: there is none) and a missing reading
    assert regressions == ["conv_ops", "floor", "over", "unmeasured"]
    by_name = {r[0]: r[3] for r in rows}
    assert by_name["conv_ops"].startswith("REGRESSED")
    assert by_name["ratio"] == "OK"
    assert by_name["over"] == "REGRESSED (<= 0.5)"
    assert "unknown match kind" in by_name["floor"]
    assert by_name["extra_new"].startswith("NEW")
    assert by_name["unmeasured"].startswith("MISSING")


def _baseline_rows():
    path = os.path.join(_REPO_ROOT, "PERF_BASELINE.json")
    assert os.path.exists(path), "PERF_BASELINE.json must be committed"
    blob = json.load(open(path))
    assert blob["format"] == "bigdl_tpu-perf-baseline-v1"
    return blob["metrics"]


def test_perf_gate_baseline_committed_and_wellformed():
    m = _baseline_rows()
    assert m["lenet_matmul.conv_ops"] == {"value": 0, "match": "exact"}
    assert m["wire.upcasts"]["value"] == m["wire.buckets"]["value"]
    assert m["wire.buckets"]["value"] < m["wire.leaves"]["value"]
    assert m["fused.buffers"]["value"] == 1
    assert set(_gate_mod().DEFAULT_RATIO_BOUNDS) == {
        n for n, row in m.items() if row["match"] == "max"}


def test_perf_gate_rows_are_counts():
    """The gate runs on the CPU, so no row is a time, a rate or a ratio of
    times: a row is an exact count, or a ratio of bytes or of schedule
    slots bounded from above, and none carries a slack of its own."""
    time_like = re.compile(r"(_s|_ms|_us|_seconds|per_s|_over_cold|"
                           r"_over_full|step_ratio|traced_ratio)$")
    for name, row in _baseline_rows().items():
        assert set(row) <= {"value", "match", "note"}, name
        assert not time_like.search(name), name
        if row["match"] == "max":
            assert name.endswith(("bubble_fraction", "bytes_ratio")), name
        else:
            assert row["match"] == "exact", name


def test_perf_gate_cli_passes_on_clean_head():
    """The acceptance run: the gate against the committed baseline must
    exit 0 with every metric OK (the pad-forced regression demo is
    exercised by test_perf_gate_check_logic)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BIGDL_TPU_")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools", "perf_gate.py"),
         "--platform", "cpu", "--batch-size", "32"],
        capture_output=True, text=True, timeout=420,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    blob = json.loads(proc.stdout.splitlines()[-1])
    assert blob["ok"] is True and blob["regressions"] == []
    assert blob["measured"]["lenet_matmul.conv_ops"] == 0
