"""The harness end to end at the tests' tiny sizes, on the CPU: each driver
through ``--rehearse``, the refusal to measure without a TPU, BENCHMARK.json
against the contract's limits, and a cell, a configuration, a traffic mix and
a per-layer metric added as files and entries alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env():
    """One CPU device, no compile cache shared with other runs."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    env["BIGDL_TPU_XLA_CACHE"] = "0"
    return env


def _run(args, timeout=600):
    p = subprocess.run([sys.executable, RUN] + args, cwd=REPO, env=_env(),
                       capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


@pytest.mark.parametrize("workload,trace", [
    ("resnet50.train", 0), ("gpt2m.decode", 0), ("gpt2m.decode", 1),
    ("gpt2m.train", 1)])
def test_rehearse_runs_the_driver_end_to_end(workload, trace):
    p, lines = _run(["--workload", workload, "--seed", "2147483659",
                     "--seconds", "2", "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, [ln for ln in lines if '"check"' in ln]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[kind]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == want
    # a rehearsal measures nothing: no number under a device metric's name
    assert all(m["value"] == "not measured" for m in line["metrics"].values())
    # every number compared is printed beside its limit
    checks = [json.loads(ln) for ln in lines if '"obs": "check"' in ln]
    assert checks and all({"name", "value", "limit", "ok"} <= set(c)
                          for c in checks)


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    p, lines = _run(["--workload", "gpt2m.decode", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(ln.startswith('{"correct"') for ln in lines)


def test_benchmark_json_keeps_the_contracts_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        names.append(c["name"])
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in names
        names += [w["name"], w["traffic"]]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    readers = {fn[:-3] for fn in os.listdir(
        os.path.join(REPO, "benchmark", "layer_metrics"))
        if fn.endswith(".py")}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["name"] in readers
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
        assert all(w in cells for w in m.get("workloads", []))
    assert all(NAME.match(n) for n in names), names
    for w in cells:   # setup_s, one more end-to-end and one per-layer metric
        assert sum(w in m.get("workloads", [w])
                   for m in BENCH["end_to_end"]) >= 2
        assert any(w in m["workloads"] for m in BENCH["per_layer"])


def test_layer_metric_files_say_what_benchmark_json_says():
    from benchmark import harness
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    d = os.path.join(REPO, "benchmark", "layer_metrics")
    for fn in os.listdir(d):
        if fn.endswith(".py"):
            mod = harness.load_module(os.path.join(d, fn), "m_" + fn[:-3]
                                      .replace(".", "_"))
            assert mod.NAME == fn[:-3]
            entry = by_name[mod.NAME]
            assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
                entry["unit"], entry["layer"], entry["moves"])
            assert mod.read({}) is None   # nothing to read: nothing reported


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric dropped into a
    copy of benchmark/ are found by name and run; no file that was there is
    edited."""
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.load(open(bench / "configs" / "gpt2_medium.json"))
    cfg.update(cfg.pop("rehearse"))
    cfg["name"] = "gpt2_pocket"
    (bench / "configs" / "gpt2_pocket.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "gpt2_medium.py",
                bench / "configs" / "gpt2_pocket.py")
    tr = json.load(open(bench / "traffic" / "decode_closed_c48.json"))
    tr.update(tr.pop("rehearse"))
    tr["clients"] = 2
    (bench / "traffic" / "decode_closed_c2.json").write_text(json.dumps(tr))
    (bench / "layer_metrics" / "tokens_per_step.decode.py").write_text(
        'NAME = "tokens_per_step.decode"\nUNIT = "tokens"\n'
        'LAYER = "decode engine"\nMOVES = "decode_tokens_per_s"\n\n\n'
        'def read(facts):\n    c = facts.get("counts")\n'
        '    return c and c["tokens_out"] / max(c["decode_steps"], 1)\n')
    b = json.loads(json.dumps(BENCH))
    b["configs"].append({"name": "gpt2_pocket", "source": "test",
                         "file": "benchmark/configs/gpt2_pocket.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "pocket.decode", "config": "gpt2_pocket",
                           "traffic": "decode_closed_c2", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "gpt2m.decode" in m.get("workloads", []):
            m["workloads"].append("pocket.decode")
    b["per_layer"].append({"name": "tokens_per_step.decode", "unit": "tokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "decode engine",
                           "moves": "decode_tokens_per_s",
                           "workloads": ["pocket.decode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    p, lines = _run(["--workload", "pocket.decode", "--seed", "5",
                     "--seconds", "2", "--trace", "1", "--rehearse",
                     "--bench-dir", str(bench),
                     "--benchmark-json", str(tmp_path / "BENCHMARK.json")])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True
    assert "tokens_per_step.decode" in line["metrics"]
    assert "slot_fill_pct.decode" in line["metrics"]
    assert all(p.read_bytes() == data for p, data in before.items())


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_every_seed_offers_a_window_the_same_requests(seed):
    """All clients draw from one list: round after round of the whole grid,
    so any run of consecutive submissions as long as the grid holds every
    cell once, whatever the seed; only the order and the tokens differ."""
    from benchmark import harness
    drv = harness.load_module(os.path.join(REPO, "benchmark", "drivers",
                                           "decode_closed.py"), "t_decode")
    tr = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                     "decode_closed_c48.json")))
    tr["rounds"] = 3
    cfg = {"vocab_size": 50257}
    reqs = drv.make_requests(cfg, tr, seed)
    n = tr["grid"][0] * tr["grid"][1]
    assert len(reqs) == 3 * n
    cells = [(len(p), o) for p, o in reqs]
    grid = sorted(cells[:n])
    assert len(set(grid)) == n
    assert sorted(cells[n:2 * n]) == grid and sorted(cells[2 * n:]) == grid
    assert cells[:n] != cells[n:2 * n]            # each round in its own order
    other = drv.make_requests(cfg, tr, seed + 1)
    assert sorted((len(p), o) for p, o in other[:n]) == grid
    assert [(len(p), o) for p, o in other] != cells
    again = drv.make_requests(cfg, tr, seed)
    assert all((a[0] == b[0]).all() and a[1] == b[1]
               for a, b in zip(reqs, again))
    # the longest request fits the cache, and the queue holds the callers
    assert max(p + o for p, o in grid) <= tr["max_len"]
    assert tr["clients"] - tr["slots"] <= tr["queue_limit"]
