"""Regression tests for bench.py's stall watchdog (the lost-RPC guard).

A compile or a device call that never returns would block the benching
process forever (docs/benchmarking.md "Stall watchdog").  These tests run bench.py's watchdog machinery in a
subprocess with an artificial stall and assert the driver-facing
contract: exactly ONE JSON line always lands on stdout — partial results
(exit 0, `stall` field) when at least one config completed, a
bench_error naming the stage (exit 1, carrying earlier per-config
errors) when none did.
"""

import pytest

# sleep-driven watchdog integration: slow lane
pytestmark = pytest.mark.slow
import json
import subprocess
import sys
import textwrap


def _run(body, timeout=90):
    import os
    code = ("import time, sys, argparse\n"
            "sys.argv = ['bench.py']\n"
            "import bench\n" + textwrap.dedent(body))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, cwd=repo, env=env)


def _json_lines(out):
    return [json.loads(l) for l in out.splitlines() if l.strip().startswith("{")]


def test_stall_with_no_results_emits_bench_error_with_prior_errors():
    r = _run("""
        bench._STALL_STATE['errors']['resnet50'] = 'OOM: earlier failure'
        bench._beat('put:lenet')
        bench._start_watchdog(1.0, 2.0)
        time.sleep(60)
    """)
    lines = _json_lines(r.stdout)
    assert r.returncode == 1 and len(lines) == 1
    out = lines[0]
    assert out["metric"] == "bench_error"
    assert out["stage"] == "stall:put:lenet"
    assert "OOM: earlier failure" in out["error"]


def test_stall_with_results_emits_partial_artifact_exit_zero():
    r = _run("""
        bench._STALL_STATE['results']['lenet'] = {
            'name': 'lenet', 'images_per_sec': 100.0, 'mode': 'train',
            'mfu': None, 'model_flops_per_step': 1.0}
        class D: device_kind = 'cpu'
        bench._STALL_STATE['meta'] = dict(
            args=argparse.Namespace(no_scaling=True, budget_seconds=1500.0,
                                    configs=['lenet', 'resnet50_bf16', 'lstm']),
            table_peak=None, measured_peak=None, peak=None, devices=[D()],
            t_start=0.0)
        bench._beat('compile:resnet50_bf16')
        bench._start_watchdog(1.0, 2.0)
        time.sleep(60)
    """)
    lines = _json_lines(r.stdout)
    assert r.returncode == 0 and len(lines) == 1
    out = lines[0]
    assert out["configs"]["lenet"]["images_per_sec"] == 100.0
    assert out["stall"]["stage"] == "compile:resnet50_bf16"
    # hung config excluded; untouched configs recorded, not silently lost
    assert out["stall"]["configs_not_attempted"] == ["lstm"]


def test_main_thread_claim_wins_and_watchdog_stays_silent():
    """A stale heartbeat must not produce a second JSON line once the main
    thread has claimed the emit (the late-resolving-RPC race)."""
    r = _run("""
        import threading
        bench._STALL_STATE['results']['lenet'] = {
            'name': 'lenet', 'images_per_sec': 100.0, 'mode': 'train',
            'mfu': None, 'model_flops_per_step': 1.0}
        class D: device_kind = 'cpu'
        meta = dict(
            args=argparse.Namespace(no_scaling=True, budget_seconds=1500.0,
                                    configs=['lenet']),
            table_peak=None, measured_peak=None, peak=None, devices=[D()],
            t_start=0.0)
        bench._STALL_STATE['meta'] = meta
        bench._beat('put:lenet')
        assert bench._claim_emit()
        bench._start_watchdog(0.5, 0.5)
        # the watchdog loop ticks every 10s regardless of the limits, so
        # sleeping 12s guarantees exactly one tick observes the stale beat;
        # do not shorten below 10s or the race stops being exercised
        time.sleep(12)
        bench._assemble_and_print(results=bench._STALL_STATE['results'],
                                  errors={}, skipped=[], **meta)
    """)
    lines = _json_lines(r.stdout)
    assert r.returncode == 0 and len(lines) == 1
    assert "stall" not in lines[0]


def test_healthy_fast_run_unaffected_by_watchdog():
    """End-to-end: the real lenet config on CPU with tight-but-ample limits
    completes normally and emits one line with no stall field."""
    import os
    repo = __import__("pathlib").Path(__file__).resolve().parent.parent
    env = {**os.environ}
    r = subprocess.run(
        [sys.executable, "bench.py", "--configs", "lenet", "--platform",
         "cpu", "--no-scaling"],
        capture_output=True, text=True, timeout=420, cwd=repo, env=env)
    lines = _json_lines(r.stdout)
    assert r.returncode == 0 and len(lines) == 1, r.stderr[-500:]
    out = lines[0]
    assert out["metric"] == "lenet_train_images_per_sec_per_chip"
    assert "stall" not in out


def test_flash_attention_bench_record(monkeypatch):
    """The flash_attention op bench produces a well-formed record with the
    pallas-vs-reference comparison fields (VERDICT r3 #6)."""
    monkeypatch.setenv("BIGDL_TPU_BENCH_FLASH_SHAPE", "1,2,128,32")
    import bench

    rec = bench._bench_flash("flash_attention",
                             bench.CONFIGS["flash_attention"], None)
    assert rec["mode"] == "op" and rec["shape"] == [1, 2, 128, 32]
    assert rec["reference_dt_seconds"] > 0
    assert rec["speedup_vs_reference"] > 0
    assert rec["model_flops_per_step"] == 3.5 * 4 * 1 * 2 * 128 * 128 * 32 / 2
