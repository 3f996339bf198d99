"""The configuration ``mellum2_12b_share4`` and its cell ``mellum2.decode``
(ISSUE 48), at the tests' tiny sizes on the CPU: the cell runs end to end
through the harness, the configuration file keeps every published number and
cuts the four it says, the plain reference agrees with the program at the
logits, the program's tree is the one written out here and takes the
reference's weights, the counts by hand match the tree and the declared
state, the new reader reads a recorded fact, the served routing is followed,
and the control and five planted faults (the window left out, the window off
by one, YaRN's blend left out, YaRN's factor left out, the ring's rows taken
``mod (window - 1)``) read ``correct`` false."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "mellum2.decode"
CONFIG = "mellum2_12b_share4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_experts", "num_attention_heads", "num_key_value_heads",
           "vocab_size"]
HELD = 3_040_674_048

#: the program's flattened parameter paths and shapes at the rehearse size,
#: as ``test_benchmark_tree_guard.py`` writes its three: ``program_weights``
#: lays the seeded weights on by flatten order, so a renamed, added or
#: reordered leaf has to move the reference's tree with it and say so here.
#: Slot 1 (``Float32``) and the last (``LogSoftMax``) hold no leaf; a block
#: is two slots, attention and experts, each a residual around (norm, layer).
_BLOCK = [("[{a}][0][0][0]['weight']", (64,)),
          ("[{a}][0][0][1]['wk']", (64, 16)),
          ("[{a}][0][0][1]['wo']", (32, 64)),
          ("[{a}][0][0][1]['wq']", (64, 32)),
          ("[{a}][0][0][1]['wv']", (64, 16)),
          ("[{e}][0][0][0]['weight']", (64,)),
          ("[{e}][0][0][1]['gate']", (64, 16)),
          ("[{e}][0][0][1]['w_down']", (4, 32, 64)),
          ("[{e}][0][0][1]['w_gate']", (4, 64, 32)),
          ("[{e}][0][0][1]['w_up']", (4, 64, 32))]
TREE = ([("[0]['weight']", (211, 64))]
        + [(path.format(a=2 + 2 * n, e=3 + 2 * n), shape)
           for n in range(6) for path, shape in _BLOCK]
        + [("[14]['weight']", (64,)), ("[15]['weight']", (211, 64))])


@pytest.fixture
def fresh_policy():
    from bigdl_tpu.common import get_policy, set_policy
    prior = get_policy()
    yield
    set_policy(prior)


def _cell():
    return harness.Cell(CELL)


def _tiny():
    cell = _cell()
    cfg = dict(cell.cfg)
    cfg.update(cell.cfg["rehearse"])
    return cell.cfg_mod, cfg


def _env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", BIGDL_TPU_XLA_CACHE="0")
    return env


def _last_line(capsys, trace=0):
    from benchmark import run as bench_run
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483777",
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse"])
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    checks = {c["name"]: c for c in map(json.loads, out)
              if c.get("obs") == "check"}
    return rc, json.loads(out[-1]), checks


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_end_to_end(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True, [ln for ln in lines if '"check"' in ln]
    assert line["attempted"] > 0 and line["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[kind]
            if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == want
    assert ("kv_read_roofline_pct.decode" in want) == bool(trace)
    assert all(m["value"] == "not measured" for m in line["metrics"].values())
    # the window's counters carry the engine's new one
    window = next(json.loads(ln) for ln in lines if '"obs": "window"' in ln)
    assert window["slot_positions"] > window["decode_steps"] > 0


def test_the_entries_are_added_and_the_older_cells_stand():
    """One configuration, one cell on one chip, on the seven lists ISSUE 48
    names and on the new metric's; every entry is looked up by name, so the
    next cell appended after this one leaves the test standing."""
    config = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert config == dict(
        config, reduced=REDUCED,
        file="benchmark/configs/mellum2_12b_share4.json",
        source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct"
               "/blob/main/config.json")
    assert "num_hidden_layers" not in config["reduced"]
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.count(CELL) == 1
    older = names[:names.index(CELL)]
    assert older[-5:] == ["gpt2m.train", "dsv2.decode", "nemo3.decode",
                          "qwen3n.decode", "jamba2.decode"]
    entry = BENCH["workloads"][names.index(CELL)]
    assert entry == dict(entry, config=CONFIG,
                         traffic="decode_closed_c288_code", chips=1)
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m["workloads"]
             for k in ("end_to_end", "per_layer") for m in BENCH[k]
             if "workloads" in m}
    assert {n for n, w in lists.items() if CELL in w} == {
        "decode_tokens_per_s", "request_p95_ms", "slot_fill_pct.decode",
        "device_ms_per_token.decode", "device_idle_pct.decode",
        "prefill_share_pct.decode", "step_hbm_roofline_pct.decode",
        "kv_read_roofline_pct.decode"}
    assert all(w.count(CELL) <= 1 for w in lists.values())
    # the older cells come before this one on every list it is on, and the
    # lists it is not on hold what they held
    for name, w in lists.items():
        if CELL in w:
            assert [c for c in w[:w.index(CELL)]] == \
                [c for c in w if c in older]
    assert lists["kv_read_roofline_pct.decode"][0] == CELL
    assert lists["ssm_state_roofline_pct.decode"][:2] == ["nemo3.decode",
                                                          "jamba2.decode"]
    assert lists["train_records_per_s"][:2] == ["resnet50.train",
                                                "gpt2m.train"]
    metric = {m["name"]: m for m in BENCH["per_layer"]}[
        "kv_read_roofline_pct.decode"]
    assert metric == dict(metric, unit="%", better="higher",
                          source="device_trace", layer="model step",
                          moves="decode_tokens_per_s")


def test_configuration_file_keeps_every_published_number():
    cfg = _cell().cfg
    entry = {c["name"]: c for c in BENCH["configs"]}[cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    for key in ("source", "deployment", "assumed", "held", "published"):
        assert cfg[key], key
    for key in ("weights", "initializer_range", "norm_weights", "qk_norm",
                "window", "rope", "mtp", "residual_stream", "router_precision",
                "ties", "compared_positions"):
        assert cfg["assumed"][key], key
    assert cfg["published"] == {"num_experts": 64, "num_attention_heads": 32,
                                "num_key_value_heads": 4,
                                "vocab_size": 98304}
    assert (cfg["num_experts"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"]) == (16, 8, 1,
                                                               24576)
    assert cfg["held"]["router_outputs"] == 64
    assert (cfg["param_dtype"], cfg["compute_dtype"]) == ("bfloat16",
                                                          "bfloat16")
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 28
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 7
    tr = _cell().traffic
    assert tr["driver"] == "decode_closed_routed_positions"
    assert (tr["clients"], tr["slots"], tr["page"], tr["max_len"],
            tr["queue_limit"], tr["rounds"]) == (288, 192, 5120, 5120, 384,
                                                 192)
    assert (tr["prompt_len"], tr["output_len"], tr["grid"]) == (
        [1024, 4096], [128, 1024], [8, 6])
    assert (tr["sample_requests"], tr["trace_seconds"],
            tr["tail_wait_seconds"]) == (6, 2.0, 180)
    from benchmark.drivers import decode_closed
    plens = decode_closed._log_grid(*tr["prompt_len"], tr["grid"][0])
    olens = decode_closed._log_grid(*tr["output_len"], tr["grid"][1])
    assert plens.tolist() == [1117, 1328, 1579, 1878, 2233, 2656, 3158, 3756]
    assert olens.tolist() == [152, 215, 304, 431, 609, 861]
    assert plens[-1] + olens[-1] == 4617 <= tr["max_len"]
    assert {decode_closed._bucket(int(p)) for p in plens} == {2048, 4096}
    # every prompt wraps its ring
    assert plens.min() > cfg["sliding_window"] == 1024
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg[key] != value and cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_the_programs_tree_takes_the_references_weights(fresh_policy):
    """At the published sizes, by shape alone (nothing is allocated): the
    reference's tree flattens in the program's order, and the counts by hand
    are the parameters that are there: ISSUE 48's 3,040,674,048, 6.08 GB."""
    import jax
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    shapes, _ = jax.eval_shape(model.init, jax.random.key(0))
    want = jax.eval_shape(lambda k: cm.init_params(cfg, k),
                          jax.random.key(0))
    assert [(s.shape, s.dtype) for s in jax.tree.leaves(shapes)] == \
        [(s.shape, s.dtype) for s in jax.tree.leaves(want)]
    n = cm.param_counts(cfg)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n["once"] + n["routed"] + n["embedding"] == total == HELD
    # by hand, ISSUE 48's arithmetic
    attention = 2 * 2304 * 1024 + 2 * 2304 * 128
    experts = 16 * 3 * 2304 * 896
    assert (attention, experts) == (5_308_416, 99_090_432)
    assert 3 * 2304 * 896 == 6_193_152
    assert n["block"] == attention + 4608 + 147_456 + experts == 104_550_912
    assert total == 28 * 104_550_912 + 2304 + 2 * 56_623_104
    assert n["routed"] == 28 * experts
    assert n["embedding"] == 24576 * 2304 == 56_623_104
    assert total * 2 == pytest.approx(6.08e9, rel=1e-3)
    # the whole model, from the published counts
    whole = 28 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 4608 + 147_456
                  + 64 * 6_193_152) + 2304 + 2 * 98304 * 2304
    assert whole == pytest.approx(12_149.9e6, rel=1e-5)


def test_parameter_paths_and_shapes_are_the_ones_written_out(fresh_policy):
    import jax
    cm, cfg = _tiny()
    cm.set_policy(cfg)
    shapes, _ = jax.eval_shape(cm.build_model(cfg).init, jax.random.key(0))
    got = [(jax.tree_util.keystr(p), tuple(s.shape))
           for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert got == TREE
    want = jax.eval_shape(lambda k: cm.init_params(cfg, k), jax.random.key(0))
    assert [tuple(s.shape) for s in jax.tree.leaves(want)] == \
        [s for _p, s in TREE]


def test_bytes_against_a_count_by_hand_and_the_declared_leaves(fresh_policy):
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    state = cm.state_bytes_per_row(cfg)
    assert state == {"ring": 21 * 2 * 1024 * 128 * 2, "position": 7 * 2 * 128 * 2}
    assert state == {"ring": 11_010_048, "position": 3_584}
    n = cm.param_counts(cfg)
    # no token: every weight outside the experts once and no expert
    assert cm.decode_step_min_bytes(cfg, 0) == 2 * n["once"]
    # 192 tokens choose 8 of 64 each: every held expert is all but surely
    # chosen, 5.97 GB (ISSUE 48), and the embedding is left out
    full = cm.decode_step_min_bytes(cfg, 192)
    assert full == pytest.approx(2 * (HELD - n["embedding"]), rel=1e-6)
    assert full == pytest.approx(5.97e9, rel=2e-3)
    # keys and values: 21 rings whole and 7 x 512 B a position a slot
    assert cm.kv_read_min_bytes(cfg, 0, 3000) == 0
    assert cm.kv_read_min_bytes(cfg, 192, 0) == 192 * 11_010_048 \
        == pytest.approx(2.11e9, rel=2e-3)
    assert cm.kv_read_min_bytes(cfg, 192, 2800) == \
        192 * (11_010_048 + 7 * 512 * 2800)
    assert cm.kv_read_min_bytes(cfg, 1, 1) == 11_010_048 + 3_584
    # what the engine declares is what the benchmark counts
    import jax.numpy as jnp
    from bigdl_tpu.models import decode as kv
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    total, fixed = kv.state_bytes_per_row(model, 5120, jnp.bfloat16)
    avals = kv.cache_avals(model, 192, 5120, jnp.bfloat16)
    assert fixed == state["ring"]
    assert total - fixed == state["position"] * 5120
    assert 192 * total == pytest.approx(5.64e9, rel=2e-3)
    assert sum(a["k"].shape == (192, 1024, 128) for a in avals) == 21
    assert sum(a["k"].shape == (192, 5120, 128) for a in avals) == 7


def _facts(trace_counts, modules):
    cell = _cell()
    return {"cfg": cell.cfg, "traffic": cell.traffic,
            "device": {"kind": "TPU v5 lite"}, "trace_counts": trace_counts,
            "trace": {"busy_s": 2.0, "modules": modules, "ops": []}}


def test_the_kv_reader_on_a_recorded_fact():
    reader = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "kv_read_roofline_pct.decode.py"), "reader_kv_m")
    counts = {"prefill_steps": 10, "decode_steps": 50, "tokens_out": 9510,
              "seqs_done": 9, "seqs_failed": 0,
              "slot_positions": 9500 * 2800}
    modules = [["jit_decode_step", 50.0, 1.5],
               ["jit_decode_prefill", 10.0, 0.5]]
    # 190 tokens a step at a mean of 2,800 positions, 30 ms a step
    want = 100 * 190 * (11_010_048 + 3_584 * 2800) / (0.030 * 819e9)
    assert reader.read(_facts(counts, modules)) == pytest.approx(want)
    assert 0 < want < 100
    # beside the weights' share of the same step the two stay under 100 %
    hbm = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "step_hbm_roofline_pct.decode.py"), "reader_hbm_m")
    assert want + hbm.read(_facts(counts, modules)) < 100
    # an engine without the counter (a parent commit's), a window without a
    # step, a trace without the program: nothing, and no error
    old = {k: v for k, v in counts.items() if k != "slot_positions"}
    assert reader.read(_facts(old, modules)) is None
    assert reader.read(_facts(dict(counts, decode_steps=0), modules)) is None
    assert reader.read(_facts(counts, modules[1:])) is None
    assert reader.read(_facts(None, modules)) is None
    # a configuration without the function (another cell's)
    other = _facts(counts, modules)
    other["cfg"] = harness.Cell("qwen3n.decode").cfg
    assert reader.read(other) is None


def test_the_drivers_counters_carry_the_engines_new_one():
    """``decode_closed_routed_positions`` is ``decode_closed_routed`` whose
    ``_counts`` also keeps ``slot_positions`` where the engine has it."""
    mod = harness.load_module(
        os.path.join(REPO, "benchmark", "drivers",
                     "decode_closed_routed_positions.py"), "driver_pos_m")

    class Engine:
        def __init__(self, **more):
            self.more = more

        def stats(self):
            return dict(prefill_steps=1, decode_steps=2, tokens_out=3,
                        seqs_done=4, seqs_failed=5, other=6, **self.more)

    five = {"prefill_steps": 1, "decode_steps": 2, "tokens_out": 3,
            "seqs_done": 4, "seqs_failed": 5}
    assert mod.routed.base._counts(Engine()) == five
    assert mod.routed.base._counts(Engine(slot_positions=7)) == dict(
        five, slot_positions=7)
    assert mod.run is mod.routed.run and mod.control is mod.routed.control
    assert mod.routed.base.Client is mod.routed.Client


def test_reference_agrees_with_the_program_at_rehearse_sizes(fresh_policy):
    import jax
    import jax.numpy as jnp
    cm, cfg = _tiny()
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 27)).astype(np.int32))
    got, _ = model.apply(params, state, toks)
    p0 = cm.init_params(cfg, jax.random.key(5))
    from benchmark.reference import mellum2_12b_share4 as ref
    plain = jax.jit(lambda p, t, prec: jax.nn.log_softmax(
        ref.logits(cfg, p, t, prec)), static_argnums=2)
    want = plain(p0, toks, "f32")
    assert float(jnp.abs(got - want).max()) < 2e-4
    low = plain(p0, toks, "fp8")
    assert float(jnp.abs(low - want).max()) > 2e-3


def test_served_routing_is_followed_and_held_to_the_references_router(
        fresh_policy):
    """``routed_logits_fn`` with the routing the engine returned: in float32
    the served choices are the reference's own, forcing them changes
    nothing, and choices that are no router's read near 1."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serve import DecodeEngine
    cm, cfg = _tiny()
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    key = jax.random.key(5)
    params, state = harness.program_weights(cm, cfg, model, key)
    model.attach(params, state)
    prompts = [np.random.default_rng(n).integers(1, 211, n).astype(np.int32)
               for n in (5, 13, 9)]
    with DecodeEngine(model, slots=2, page=32, max_len=32) as eng:
        reqs = [eng.submit(p, 6) for p in prompts]
        rows = [h.result(120.0) for h in reqs]
    layers, k, width = 6, 3, 24
    toks = np.zeros((3, width), np.int32)
    served = np.full((3, layers, width, k), -1, np.int32)
    for i, (r, h) in enumerate(zip(rows, reqs)):
        assert h.routing.shape == (layers, len(r) - 1, k)
        toks[i, :len(r)] = r
        served[i, :, :len(r) - 1] = h.routing
    p0 = cm.init_params(cfg, key)
    from benchmark.reference import mellum2_12b_share4 as ref
    own = np.asarray(ref.logits(cfg, p0, jnp.asarray(toks)))
    f32 = jax.jit(cm.routed_logits_fn(cfg))
    got, made, disagree = map(np.asarray, f32(p0, toks, served))
    assert not disagree.any()
    np.testing.assert_allclose(got, own, atol=1e-5)
    given = served[..., 0] >= 0
    np.testing.assert_array_equal(np.sort(made[given], -1),
                                  np.sort(served[given], -1))
    wrong = np.where(served >= 0, np.arange(3), -1).astype(np.int32)
    assert (np.asarray(f32(p0, toks, wrong)[2]) > 0.8).all()


def test_the_control_is_read_under_its_own_choices():
    """``benchmark/control.py`` through the cell's driver: the sound run's two
    numbers inside their limits, the control's gap (its own choices forced
    into the float32 reference, as the served ones are) outside."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control.py"),
         "--workload", CELL, "--seeds", "11,12", "--seconds", "1",
         "--rehearse"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.splitlines()[-1])
    lim = _cell().cfg["rehearse"]["limits"]["decode"]
    assert last["sound_largest"]["logit_gap"] <= lim["logit_gap"]
    assert last["sound_largest"]["routing_disagree"] \
        <= lim["routing_disagree"]
    assert last["control_smallest"]["fp8:logit_gap"] > lim["logit_gap"]


def test_the_sound_path_is_correct(capsys, fresh_policy):
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, checks


# ------------------------------------------------------- planted faults


def _window_left_out(monkeypatch):
    """A sliding layer's prompt reads every earlier key."""
    from bigdl_tpu.nn import RotaryAttention, WindowAttention

    def band(self, q, k, v, dtype, length=None):
        wide = copy.copy(self)
        wide.window = None
        return RotaryAttention._band(wide, q, k, v, dtype, length)

    monkeypatch.setattr(WindowAttention, "_band", band)


def _window_off_by_one(monkeypatch):
    """A query sees ``window - 1`` keys: the band of a prompt is one
    narrower, and a step does not read the oldest row of its ring."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import RotaryAttention, WindowAttention
    held = WindowAttention._ring_position

    def band(self, q, k, v, dtype, length=None):
        narrow = copy.copy(self)
        narrow.window = self.window - 1
        return RotaryAttention._band(narrow, q, k, v, dtype, length)

    def ring_position(self, newest):
        at = held(self, newest)
        return jnp.where(at == newest[..., None] - self.window + 1, -1, at)

    monkeypatch.setattr(WindowAttention, "_band", band)
    monkeypatch.setattr(WindowAttention, "_ring_position", ring_position)


def _yarn_blend_left_out(monkeypatch):
    """The full layers turn by the plain frequencies."""
    from bigdl_tpu.nn import window_attention
    sound = window_attention.rope_inv_freq
    monkeypatch.setattr(window_attention, "rope_inv_freq",
                        lambda dim, base, scaling=None: sound(dim, base))


def _yarn_factor_left_out(monkeypatch):
    """cos and sin of the full layers go without ``attention_factor``."""
    from bigdl_tpu.models import mellum
    sound = mellum._rope
    monkeypatch.setattr(
        mellum, "_rope", lambda group: {
            k: v for k, v in sound(group).items() if k != "attention_factor"})


def _ring_rows_mod_one_less(monkeypatch):
    """Position ``p`` is kept at ring row ``p mod (window - 1)``: a step
    overwrites a row its window still needs, and the last row of the ring
    keeps what the prefill left there."""
    from bigdl_tpu.nn import WindowAttention
    monkeypatch.setattr(WindowAttention, "_ring_row",
                        lambda self, pos: pos % (self.window - 1))


PLANTERS = {"window_left_out": _window_left_out,
            "window_off_by_one": _window_off_by_one,
            "yarn_blend": _yarn_blend_left_out,
            "yarn_factor": _yarn_factor_left_out,
            "ring_rows": _ring_rows_mod_one_less}


@pytest.mark.parametrize("plant", sorted(PLANTERS))
def test_a_planted_fault_is_not_correct(plant, monkeypatch, capsys,
                                        fresh_policy):
    PLANTERS[plant](monkeypatch)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True


def test_the_lowering_tool_writes_this_cells_programs_too(tmp_path):
    """``tools/lower_cells.py`` writes the step and the prefill of one prompt
    and of two of this cell as of the older ones, the same text twice."""
    tool = os.path.join(REPO, "tools", "lower_cells.py")
    for out in ("a", "b"):
        p = subprocess.run(
            [sys.executable, tool, str(tmp_path / out), CELL],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
    names = sorted(os.listdir(tmp_path / "a"))
    assert names == [f"{CELL}.{k}.txt"
                     for k in ("prefill1", "prefill2", "step")]
    for n in names:
        a, b = ((tmp_path / d / n).read_text() for d in ("a", "b"))
        assert a == b and "func.func public @main" in a
