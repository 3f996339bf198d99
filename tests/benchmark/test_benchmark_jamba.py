"""The configuration ``jamba2_3b`` and its cell ``jamba2.decode`` (ISSUE 44),
at the tests' tiny sizes on the CPU: the cell runs end to end through the
harness, the configuration file keeps every published number and cuts none,
the plain reference agrees with the program at the logits, the program's tree
is the one written out here and takes the reference's weights (the table one
leaf), the counts by hand match the tree and the declared state, the readers
read recorded facts, and the control and five planted faults (the three inner
norms left out, ``b_dt`` left out, the convolution's tail taken from a
prompt's pads, the ``D x`` term left out, the state not carried from prefill
to step) read ``correct`` false."""

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
CELL = "jamba2.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WHOLE = 3_029_337_472

#: the program's flattened parameter paths and shapes at the rehearse size,
#: as ``test_benchmark_tree_guard.py`` writes its three: ``program_weights``
#: lays the seeded weights on by flatten order, so a renamed, added or
#: reordered leaf has to move the reference's tree with it and say so here.
#: Slot 1 (``Float32``) and the last two (the head, which reads ``[0]``, and
#: ``LogSoftMax``) hold no leaf.
TREE = [
    ("[0]['weight']", (211, 64)),
    ("[2][0][0][0]['weight']", (64,)),
    ("[2][0][0][1]['A_log']", (8, 128)),
    ("[2][0][0][1]['B_norm']", (8,)),
    ("[2][0][0][1]['C_norm']", (8,)),
    ("[2][0][0][1]['D']", (128,)),
    ("[2][0][0][1]['conv_bias']", (128,)),
    ("[2][0][0][1]['conv_weight']", (4, 128)),
    ("[2][0][0][1]['dt_bias']", (128,)),
    ("[2][0][0][1]['dt_norm']", (8,)),
    ("[2][0][0][1]['dt_proj']", (8, 128)),
    ("[2][0][0][1]['in_proj']", (64, 256)),
    ("[2][0][0][1]['out_proj']", (128, 64)),
    ("[2][0][0][1]['x_proj']", (128, 24)),
    ("[3][0][0][0]['weight']", (64,)),
    ("[3][0][0][1][0][0][0]['weight']", (128, 64)),
    ("[3][0][0][1][0][1]['weight']", (128, 64)),
    ("[3][0][0][1][2]['weight']", (64, 128)),
    ("[4][0][0][0]['weight']", (64,)),
    ("[4][0][0][1]['wk']", (64, 16)),
    ("[4][0][0][1]['wo']", (64, 64)),
    ("[4][0][0][1]['wq']", (64, 64)),
    ("[4][0][0][1]['wv']", (64, 16)),
    ("[5][0][0][0]['weight']", (64,)),
    ("[5][0][0][1][0][0][0]['weight']", (128, 64)),
    ("[5][0][0][1][0][1]['weight']", (128, 64)),
    ("[5][0][0][1][2]['weight']", (64, 128)),
    ("[6][0][0][0]['weight']", (64,)),
    ("[6][0][0][1]['A_log']", (8, 128)),
    ("[6][0][0][1]['B_norm']", (8,)),
    ("[6][0][0][1]['C_norm']", (8,)),
    ("[6][0][0][1]['D']", (128,)),
    ("[6][0][0][1]['conv_bias']", (128,)),
    ("[6][0][0][1]['conv_weight']", (4, 128)),
    ("[6][0][0][1]['dt_bias']", (128,)),
    ("[6][0][0][1]['dt_norm']", (8,)),
    ("[6][0][0][1]['dt_proj']", (8, 128)),
    ("[6][0][0][1]['in_proj']", (64, 256)),
    ("[6][0][0][1]['out_proj']", (128, 64)),
    ("[6][0][0][1]['x_proj']", (128, 24)),
    ("[7][0][0][0]['weight']", (64,)),
    ("[7][0][0][1][0][0][0]['weight']", (128, 64)),
    ("[7][0][0][1][0][1]['weight']", (128, 64)),
    ("[7][0][0][1][2]['weight']", (64, 128)),
    ("[8][0][0][0]['weight']", (64,)),
    ("[8][0][0][1]['A_log']", (8, 128)),
    ("[8][0][0][1]['B_norm']", (8,)),
    ("[8][0][0][1]['C_norm']", (8,)),
    ("[8][0][0][1]['D']", (128,)),
    ("[8][0][0][1]['conv_bias']", (128,)),
    ("[8][0][0][1]['conv_weight']", (4, 128)),
    ("[8][0][0][1]['dt_bias']", (128,)),
    ("[8][0][0][1]['dt_norm']", (8,)),
    ("[8][0][0][1]['dt_proj']", (8, 128)),
    ("[8][0][0][1]['in_proj']", (64, 256)),
    ("[8][0][0][1]['out_proj']", (128, 64)),
    ("[8][0][0][1]['x_proj']", (128, 24)),
    ("[9][0][0][0]['weight']", (64,)),
    ("[9][0][0][1][0][0][0]['weight']", (128, 64)),
    ("[9][0][0][1][0][1]['weight']", (128, 64)),
    ("[9][0][0][1][2]['weight']", (64, 128)),
    ("[10]['weight']", (64,)),
]


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
    assert ("step_hbm_roofline_pct.decode" in want) == bool(trace)
    assert all(m["value"] == "not measured" for m in line["metrics"].values())


OLDER_CELLS = ["resnet50.train", "gpt2m.decode", "gpt2m.train", "dsv2.decode",
               "nemo3.decode", "qwen3n.decode"]


def test_the_entries_are_added_and_the_older_cells_stand():
    """One configuration, one cell on one chip, on the lists ISSUE 44 names;
    the cells that were there before it keep their order and their lists.
    Every entry is looked up by name, so that the next cell appended after
    this one leaves the test standing."""
    config = {c["name"]: c for c in BENCH["configs"]}["jamba2_3b"]
    assert config == dict(config, reduced=[],
                          file="benchmark/configs/jamba2_3b.json")
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.count(CELL) == 1
    assert names[:names.index(CELL)] == OLDER_CELLS
    entry = BENCH["workloads"][names.index(CELL)]
    assert entry == dict(entry, config="jamba2_3b",
                         traffic="decode_closed_c480_reason", chips=1)
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m["workloads"]
             for k in ("end_to_end", "per_layer") for m in BENCH[k]
             if "workloads" in m}
    assert {n for n, w in lists.items() if CELL in w} == {
        "decode_tokens_per_s", "request_p95_ms", "slot_fill_pct.decode",
        "device_ms_per_token.decode", "device_idle_pct.decode",
        "prefill_share_pct.decode", "step_hbm_roofline_pct.decode",
        "ssm_state_roofline_pct.decode"}
    assert all(w.count(CELL) <= 1 for w in lists.values())
    older = {n: [c for c in w if c in OLDER_CELLS] for n, w in lists.items()}
    assert older["ssm_state_roofline_pct.decode"] == ["nemo3.decode"]
    assert older["step_hbm_roofline_pct.decode"] == [
        "dsv2.decode", "nemo3.decode", "qwen3n.decode"]
    for name in ("decode_tokens_per_s", "request_p95_ms",
                 "slot_fill_pct.decode", "device_ms_per_token.decode",
                 "device_idle_pct.decode", "prefill_share_pct.decode"):
        assert older[name] == ["gpt2m.decode", "dsv2.decode", "nemo3.decode",
                               "qwen3n.decode"]
    for name in ("train_records_per_s", "step_mfu_pct.train"):
        assert older[name] == ["resnet50.train", "gpt2m.train"]
    # the older cells come before this one on every list it is on
    assert all(w.index(CELL) == len(older[n])
               for n, w in lists.items() if CELL in w)


def test_configuration_file_keeps_every_published_number_and_cuts_none():
    cfg = _cell().cfg
    entry = {c["name"]: c for c in BENCH["configs"]}[cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == []
    for key in ("source", "deployment", "assumed"):
        assert cfg[key], key
    for key in ("weights", "initializer_range", "mamba_init", "layer_order",
                "num_experts", "head_dim", "A_log_layout",
                "use_mamba_kernels, num_logits_to_keep"):
        assert cfg["assumed"][key], key
    assert (cfg["param_dtype"], cfg["compute_dtype"]) == ("bfloat16",
                                                          "bfloat16")
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "AI21-Jamba2-3B")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    tr = _cell().traffic
    assert tr["driver"] == "decode_closed"
    assert (tr["clients"], tr["slots"], tr["page"], tr["max_len"],
            tr["queue_limit"], tr["rounds"]) == (480, 384, 1024, 1024, 512,
                                                 192)
    assert (tr["sample_requests"], tr["trace_seconds"],
            tr["tail_wait_seconds"]) == (6, 2.0, 180)
    from benchmark.drivers import decode_closed
    plens = decode_closed._log_grid(*tr["prompt_len"], tr["grid"][0])
    olens = decode_closed._log_grid(*tr["output_len"], tr["grid"][1])
    assert plens.tolist() == [53, 66, 81, 100, 123, 152, 187, 231]
    assert olens.tolist() == [149, 200, 270, 364, 491, 661]
    assert plens[-1] + olens[-1] == 892 <= tr["max_len"]
    assert {decode_closed._bucket(int(p)) for p in plens} == {64, 128, 256}


def test_the_programs_tree_takes_the_references_weights(fresh_policy):
    """At the published sizes, by shape alone (nothing is allocated): the
    reference's tree flattens in the program's order, the table is one leaf,
    and the counts by hand are the parameters that are there: ISSUE 44's
    3,029,337,472, 6.06 GB."""
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
    assert sum(s.shape == (65536, 2560)
               for s in jax.tree.leaves(shapes)) == 1
    n = cm.param_counts(cfg)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n["once"] == total == WHOLE
    # by hand, ISSUE 44's table
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560 + 160 + 16 + 16)
    mlp, attn = 3 * 2560 * 8192, 2 * 2560 * 2560 + 2 * 2560 * 128
    assert (n["mamba_mixer"], n["mlp"], n["attention_mixer"]) == \
        (mamba, mlp, attn) == (41_241_792, 62_914_560, 13_762_560)
    assert (n["mamba_layer"], n["attention_layer"]) == (104_161_472,
                                                        76_682_240)
    assert total == 26 * 104_161_472 + 2 * 76_682_240 + 65536 * 2560 + 2560
    assert total * 2 == pytest.approx(6.06e9, rel=1e-3)


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


def test_bytes_against_a_count_by_hand_and_the_declared_leaves():
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    state = cm.state_bytes_per_row(cfg)
    assert state == {"ssm": 26 * 16 * 5120 * 4, "conv": 26 * 3 * 5120 * 2}
    assert sum(state.values()) == 9_318_400
    # no token: every weight once and no row's state
    assert cm.decode_step_min_bytes(cfg, 0) == 2 * WHOLE
    want = 2 * WHOLE + 2 * 384 * 9_318_400
    assert cm.decode_step_min_bytes(cfg, 384) == want
    assert want == pytest.approx(13.2e9, rel=0.01)
    assert 2 * 384 * state["ssm"] / want == pytest.approx(0.50, abs=0.01)
    assert cm.ssm_update_min_bytes(cfg, 384) == 2 * 384 * state["ssm"] \
        == pytest.approx(6.54e9, rel=0.01)
    assert cm.ssm_leaf_shape(cfg, 384) == "f32[384,16,5120]"
    # what the engine declares is what the benchmark counts
    import jax.numpy as jnp
    from bigdl_tpu.common import get_policy, set_policy
    from bigdl_tpu.models import decode as kv
    prior = get_policy()
    try:
        cm.set_policy(cfg)
        model = cm.build_model(cfg)
        total, fixed = kv.state_bytes_per_row(model, 1024, jnp.bfloat16)
        avals = kv.cache_avals(model, 384, 1024, jnp.bfloat16)
    finally:
        set_policy(prior)
    assert fixed == sum(state.values())
    assert total - fixed == 2 * 2 * 128 * 2 * 1024
    leaves = [a for c in avals for n, a in c.items() if n == "ssm"]
    assert len(leaves) == 26
    assert all(f"f32[{','.join(map(str, a.shape))}]"
               == cm.ssm_leaf_shape(cfg, 384) for a in leaves)


def _facts(ops, modules):
    cell = _cell()
    return {"cfg": cell.cfg, "traffic": cell.traffic,
            "device": {"kind": "TPU v5 lite"},
            "trace": {"busy_s": 2.0, "modules": modules, "ops": ops}}


def test_the_state_reader_finds_this_leaf():
    """The accepted reader finds the selective state's update by the
    leaf's shape, as it finds Mamba-2's: the compiled step has one fusion a
    layer whose result holds the leaf beside the layer's ``y``."""
    reader = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "ssm_state_roofline_pct.decode.py"), "reader_ssm_j")
    leaf = "f32[384,16,5120]{2,1,0:T(8,128)}"
    update = (f"%multiply_reduce_fusion.1 = (f32[384,5120]{{1,0:T(8,128)}}, "
              f"{leaf}) fusion(%a, %b, %c), kind=kLoop, "
              f"calls=%fused_computation.7")
    reads = f"%fusion.9 = f32[384,5120]{{1,0}} fusion({leaf} %p)"
    facts = _facts([[update, 0.5], [reads, 0.06]],
                   [["jit_decode_step", 50.0, 1.2],
                    ["jit_decode_prefill", 10.0, 0.8]])
    # 0.5 s of 2.0 busy, 2.0 s of programs, 50 steps: 10 ms a step
    want = 100 * 2 * 384 * 26 * 16 * 5120 * 4 / (0.010 * 819e9)
    assert reader.read(facts) == pytest.approx(want)
    assert 0 < want < 100


def test_reference_agrees_with_the_program_at_rehearse_sizes(fresh_policy):
    import jax
    import jax.numpy as jnp
    cm, cfg = _tiny()
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 211, (2, 19)).astype(np.int32))
    got, _ = model.apply(params, state, toks)
    p0 = cm.init_params(cfg, jax.random.key(5))
    want = jax.nn.log_softmax(cm.logits_fn(cfg)(p0, toks), axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-4)
    low = jax.nn.log_softmax(cm.logits_fn(cfg, "fp8")(p0, toks), axis=-1)
    assert float(jnp.abs(low - want).max()) > 0.05


def test_the_control_is_outside_the_limit():
    """`benchmark/control.py` through the cell's driver: the sound run's gap
    inside its limit, the fp8 control's outside."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control.py"),
         "--workload", CELL, "--seeds", "11,12", "--seconds", "1",
         "--rehearse"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.splitlines()[-1])
    lim = _cell().cfg["rehearse"]["limits"]["decode"]
    assert last["sound_largest"]["logit_gap"] <= lim["logit_gap"]
    assert last["control_smallest"]["fp8:logit_gap"] > lim["logit_gap"]


def test_the_sound_path_is_correct(capsys, fresh_policy):
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, checks


# ------------------------------------------------------- planted faults


def _inner_norms_left_out(monkeypatch):
    """``dt``, ``B`` and ``C`` go on as the projection gives them."""
    from bigdl_tpu.nn import MambaMixer
    monkeypatch.setattr(MambaMixer, "_norm",
                        lambda self, params, name, v: v)


def _dt_bias_left_out(monkeypatch):
    """``Delta = softplus(dt W_dt)``, without ``b_dt``."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import MambaMixer
    sound = MambaMixer._selective
    monkeypatch.setattr(
        MambaMixer, "_selective",
        lambda self, params, x: sound(
            self, dict(params, dt_bias=jnp.zeros_like(params["dt_bias"])),
            x))


def _tail_from_the_pads(monkeypatch):
    """The convolution keeps the bucket's last inputs, pads or not."""
    from bigdl_tpu.nn import mamba
    sound = mamba.conv_tail
    monkeypatch.setattr(mamba, "conv_tail",
                        lambda x, length, taps: sound(x, x.shape[1], taps))


def _d_term_left_out(monkeypatch):
    """``y = sum_n h C`` without ``D x``."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import MambaMixer
    sound = MambaMixer._decay

    def decay(self, params):
        A, D = sound(self, params)
        return A, jnp.zeros_like(D)

    monkeypatch.setattr(MambaMixer, "_decay", decay)


def _state_not_carried(monkeypatch):
    """The prefill leaves the slot's recurrent state as it found it."""
    from bigdl_tpu.nn import MambaMixer
    sound = MambaMixer.decode_prefill

    def prefill(self, params, x, cache, slot, length):
        y, new = sound(self, params, x, cache, slot, length)
        return y, dict(new, ssm=cache["ssm"])

    monkeypatch.setattr(MambaMixer, "decode_prefill", prefill)


PLANTERS = {"inner_norms": _inner_norms_left_out,
            "dt_bias": _dt_bias_left_out,
            "conv_tail": _tail_from_the_pads,
            "d_term": _d_term_left_out,
            "state_carry": _state_not_carried}


@pytest.mark.parametrize("plant", sorted(PLANTERS))
def test_a_planted_fault_is_not_correct(plant, monkeypatch, capsys,
                                        fresh_policy):
    PLANTERS[plant](monkeypatch)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True


def test_the_lowering_tool_writes_each_program_of_a_cell(tmp_path):
    """``tools/lower_cells.py``, the guard of the older cells (their lowered
    programs compared with the parent's before a chip run): the step and the
    prefill of one prompt and of two, one file each, the same text twice."""
    tool = os.path.join(REPO, "tools", "lower_cells.py")
    for out in ("a", "b"):
        p = subprocess.run(
            [sys.executable, tool, str(tmp_path / out), CELL, "gpt2m.decode"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
    names = sorted(os.listdir(tmp_path / "a"))
    assert names == [f"{c}.{k}.txt" for c in ("gpt2m.decode", CELL)
                     for k in ("prefill1", "prefill2", "step")]
    for n in names:
        a, b = ((tmp_path / d / n).read_text() for d in ("a", "b"))
        assert a == b and "func.func public @main" in a
