"""The configuration ``qwen3_next_share4`` and its cell ``qwen3n.decode``
(ISSUE 41), at the tests' tiny sizes on the CPU: the cell runs end to end
through the harness, the configuration file keeps every published number, the
plain reference agrees with the program at the logits, the program's tree is
the one written out here and takes the reference's weights, the counts by
hand match the tree and the declared state, the readers read recorded facts,
and the control and three planted faults (the delta rule's decay left out,
``w`` for ``1 + w`` in the query and key norms, the shared expert's gate left
out) read ``correct`` false."""

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
CELL = "qwen3n.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_experts", "linear_num_value_heads",
           "linear_num_key_heads", "num_attention_heads",
           "num_key_value_heads", "vocab_size"}

#: the program's flattened parameter paths and shapes at the rehearse size,
#: as ``test_benchmark_tree_guard.py`` writes its three: ``program_weights``
#: lays the seeded weights on by flatten order, so a renamed, added or
#: reordered leaf has to move the reference's tree with it and say so here
TREE = [
    ("[0]['weight']", (211, 64)),
    ("[2][0][0][0]['weight']", (64,)),
    ("[2][0][0][1]['A_log']", (4,)),
    ("[2][0][0][1]['conv_weight']", (4, 96)),
    ("[2][0][0][1]['dt_bias']", (4,)),
    ("[2][0][0][1]['in_ba']", (64, 8)),
    ("[2][0][0][1]['in_qkvz']", (64, 160)),
    ("[2][0][0][1]['norm']", (16,)),
    ("[2][0][0][1]['out_proj']", (64, 64)),
    ("[3][0][0][0]['weight']", (64,)),
    ("[3][0][0][1]['gate']", (64, 16)),
    ("[3][0][0][1]['shared_down']", (32, 64)),
    ("[3][0][0][1]['shared_gate']", (64, 32)),
    ("[3][0][0][1]['shared_score']", (64, 1)),
    ("[3][0][0][1]['shared_up']", (64, 32)),
    ("[3][0][0][1]['w_down']", (4, 32, 64)),
    ("[3][0][0][1]['w_gate']", (4, 64, 32)),
    ("[3][0][0][1]['w_up']", (4, 64, 32)),
    ("[4][0][0][0]['weight']", (64,)),
    ("[4][0][0][1]['A_log']", (4,)),
    ("[4][0][0][1]['conv_weight']", (4, 96)),
    ("[4][0][0][1]['dt_bias']", (4,)),
    ("[4][0][0][1]['in_ba']", (64, 8)),
    ("[4][0][0][1]['in_qkvz']", (64, 160)),
    ("[4][0][0][1]['norm']", (16,)),
    ("[4][0][0][1]['out_proj']", (64, 64)),
    ("[5][0][0][0]['weight']", (64,)),
    ("[5][0][0][1]['gate']", (64, 16)),
    ("[5][0][0][1]['shared_down']", (32, 64)),
    ("[5][0][0][1]['shared_gate']", (64, 32)),
    ("[5][0][0][1]['shared_score']", (64, 1)),
    ("[5][0][0][1]['shared_up']", (64, 32)),
    ("[5][0][0][1]['w_down']", (4, 32, 64)),
    ("[5][0][0][1]['w_gate']", (4, 64, 32)),
    ("[5][0][0][1]['w_up']", (4, 64, 32)),
    ("[6][0][0][0]['weight']", (64,)),
    ("[6][0][0][1]['A_log']", (4,)),
    ("[6][0][0][1]['conv_weight']", (4, 96)),
    ("[6][0][0][1]['dt_bias']", (4,)),
    ("[6][0][0][1]['in_ba']", (64, 8)),
    ("[6][0][0][1]['in_qkvz']", (64, 160)),
    ("[6][0][0][1]['norm']", (16,)),
    ("[6][0][0][1]['out_proj']", (64, 64)),
    ("[7][0][0][0]['weight']", (64,)),
    ("[7][0][0][1]['gate']", (64, 16)),
    ("[7][0][0][1]['shared_down']", (32, 64)),
    ("[7][0][0][1]['shared_gate']", (64, 32)),
    ("[7][0][0][1]['shared_score']", (64, 1)),
    ("[7][0][0][1]['shared_up']", (64, 32)),
    ("[7][0][0][1]['w_down']", (4, 32, 64)),
    ("[7][0][0][1]['w_gate']", (4, 64, 32)),
    ("[7][0][0][1]['w_up']", (4, 64, 32)),
    ("[8][0][0][0]['weight']", (64,)),
    ("[8][0][0][1]['k_norm']", (16,)),
    ("[8][0][0][1]['q_norm']", (16,)),
    ("[8][0][0][1]['wk']", (64, 16)),
    ("[8][0][0][1]['wo']", (32, 64)),
    ("[8][0][0][1]['wq']", (64, 64)),
    ("[8][0][0][1]['wv']", (64, 16)),
    ("[9][0][0][0]['weight']", (64,)),
    ("[9][0][0][1]['gate']", (64, 16)),
    ("[9][0][0][1]['shared_down']", (32, 64)),
    ("[9][0][0][1]['shared_gate']", (64, 32)),
    ("[9][0][0][1]['shared_score']", (64, 1)),
    ("[9][0][0][1]['shared_up']", (64, 32)),
    ("[9][0][0][1]['w_down']", (4, 32, 64)),
    ("[9][0][0][1]['w_gate']", (4, 64, 32)),
    ("[9][0][0][1]['w_up']", (4, 64, 32)),
    ("[10][0][0][0]['weight']", (64,)),
    ("[10][0][0][1]['A_log']", (4,)),
    ("[10][0][0][1]['conv_weight']", (4, 96)),
    ("[10][0][0][1]['dt_bias']", (4,)),
    ("[10][0][0][1]['in_ba']", (64, 8)),
    ("[10][0][0][1]['in_qkvz']", (64, 160)),
    ("[10][0][0][1]['norm']", (16,)),
    ("[10][0][0][1]['out_proj']", (64, 64)),
    ("[11][0][0][0]['weight']", (64,)),
    ("[11][0][0][1]['gate']", (64, 16)),
    ("[11][0][0][1]['shared_down']", (32, 64)),
    ("[11][0][0][1]['shared_gate']", (64, 32)),
    ("[11][0][0][1]['shared_score']", (64, 1)),
    ("[11][0][0][1]['shared_up']", (64, 32)),
    ("[11][0][0][1]['w_down']", (4, 32, 64)),
    ("[11][0][0][1]['w_gate']", (4, 64, 32)),
    ("[11][0][0][1]['w_up']", (4, 64, 32)),
    ("[12][0][0][0]['weight']", (64,)),
    ("[12][0][0][1]['A_log']", (4,)),
    ("[12][0][0][1]['conv_weight']", (4, 96)),
    ("[12][0][0][1]['dt_bias']", (4,)),
    ("[12][0][0][1]['in_ba']", (64, 8)),
    ("[12][0][0][1]['in_qkvz']", (64, 160)),
    ("[12][0][0][1]['norm']", (16,)),
    ("[12][0][0][1]['out_proj']", (64, 64)),
    ("[13][0][0][0]['weight']", (64,)),
    ("[13][0][0][1]['gate']", (64, 16)),
    ("[13][0][0][1]['shared_down']", (32, 64)),
    ("[13][0][0][1]['shared_gate']", (64, 32)),
    ("[13][0][0][1]['shared_score']", (64, 1)),
    ("[13][0][0][1]['shared_up']", (64, 32)),
    ("[13][0][0][1]['w_down']", (4, 32, 64)),
    ("[13][0][0][1]['w_gate']", (4, 64, 32)),
    ("[13][0][0][1]['w_up']", (4, 64, 32)),
    ("[14]['weight']", (64,)),
    ("[15]['weight']", (211, 64))
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
         "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
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
    # the two the trace cannot read honestly here (PERF.md Open questions)
    assert "ssm_state_roofline_pct.decode" not in want
    assert "gdn_chunk_roofline_pct.decode" not in want
    assert all(m["value"] == "not measured" for m in line["metrics"].values())


def test_the_entries_are_added_and_nothing_else_is_touched():
    """One configuration, one cell on one chip, its name at the end of
    seven of the eight lists ISSUE 41 names.  Left out after the chip's
    reading: `ssm_state_roofline_pct.decode` (it read 106 %: the compiler
    stages the state in its second memory space, and the accepted reader
    times the update's pass alone) and the new
    `gdn_chunk_roofline_pct.decode` (the trace's operations carry no
    `op_name`, so no reader finds the chunked form): PERF.md Open
    questions."""
    assert BENCH["configs"][-1]["name"] == "qwen3_next_share4"
    assert BENCH["workloads"][-1] == dict(
        BENCH["workloads"][-1], name=CELL, config="qwen3_next_share4",
        traffic="decode_closed_c288_chat", chips=1)
    lists = {m["name"]: m["workloads"]
             for k in ("end_to_end", "per_layer") for m in BENCH[k]
             if CELL in m.get("workloads", ())}
    assert set(lists) == {
        "decode_tokens_per_s", "request_p95_ms", "slot_fill_pct.decode",
        "device_ms_per_token.decode", "device_idle_pct.decode",
        "prefill_share_pct.decode", "step_hbm_roofline_pct.decode"}
    assert all(w[-1] == CELL for w in lists.values())
    assert BENCH["per_layer"][-1]["name"] == "ssm_state_roofline_pct.decode"


def test_configuration_file_keeps_every_published_number():
    """Every number of the catalog's row is in the file under its own key;
    those that differ are the cut, listed in ``reduced`` with the published
    values beside them; no width is among them."""
    cfg = _cell().cfg
    entry = {c["name"]: c for c in BENCH["configs"]}[cfg["name"]]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == REDUCED
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in REDUCED)
    for key in ("source", "published", "held", "deployment", "assumed"):
        assert cfg[key], key
    assert "MTP" in cfg["assumed"]["mtp"] or "multi-token" in \
        cfg["assumed"]["mtp"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the cut: a quarter of every layer, three whole periods of four
    assert cfg["num_hidden_layers"] == 3 * cfg["full_attention_interval"]
    for key in REDUCED - {"num_hidden_layers", "num_key_value_heads"}:
        assert 4 * cfg[key] == cfg["published"][key], key
    assert 2 * cfg["num_key_value_heads"] == \
        cfg["published"]["num_key_value_heads"]
    assert cfg["held"]["router_outputs"] == 512
    tr = _cell().traffic
    assert tr["prompt_len"][1] + tr["output_len"][1] <= 1664
    assert (tr["clients"], tr["slots"], tr["page"], tr["max_len"],
            tr["queue_limit"], tr["rounds"]) == (288, 192, 1536, 1536, 384,
                                                 192)
    from benchmark.drivers import decode_closed
    plens = decode_closed._log_grid(*tr["prompt_len"], tr["grid"][0])
    olens = decode_closed._log_grid(*tr["output_len"], tr["grid"][1])
    assert (plens[0], plens[-1], olens[0], olens[-1]) == (146, 899, 78, 528)
    assert plens[-1] + olens[-1] <= tr["max_len"]


def test_the_programs_tree_takes_the_references_weights(fresh_policy):
    """At the real sizes, by shape alone (nothing is allocated): the
    reference's tree flattens in the program's order, and the counts by hand
    are the parameters that are there: ISSUE 41's 5,135.7 M, 10.27 GB."""
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
    assert n["once"] + n["routed"] + n["embedding"] == total == 5_135_717_136
    # by hand, a block of each kind (mixer, experts, two norms)
    experts = (128 * 3 * 2048 * 512 + 2048 * 512 + 3 * 2048 * 512 + 2048
               + 2 * 2048)
    linear = (2048 * 3072 + 2048 * 16 + 4 * 2048 + 8 + 8 + 128
              + 1024 * 2048)
    full = 2048 * 2048 + 2 * 2048 * 256 + 1024 * 2048 + 2 * 256
    assert (n["linear_block"], n["full_block"]) == (linear + experts,
                                                    full + experts)
    assert total == 9 * (linear + experts) + 3 * (full + experts) + 2048 \
        + 2 * 37984 * 2048
    assert total * 2 == pytest.approx(10.27e9, rel=1e-3)


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


def test_bytes_and_operations_against_a_count_by_hand():
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    n = cm.param_counts(cfg)
    state = cm.state_bytes_per_row(cfg)
    assert state == {"ssm": 9 * 8 * 128 * 128 * 4, "conv": 9 * 3 * 2048 * 2}
    assert sum(state.values()) == pytest.approx(4.83e6, rel=1e-3)
    # no token: nothing of the experts and no row's state
    assert cm.decode_step_min_bytes(cfg, 0) == 2 * n["once"]
    touched = 1 - (1 - 10 / 512) ** 192
    want = 2 * (n["once"] + n["routed"] * touched) \
        + 2 * 192 * sum(state.values())
    assert cm.decode_step_min_bytes(cfg, 192) == pytest.approx(want)
    assert want == pytest.approx(11.75e9, rel=0.01)
    assert cm.ssm_update_min_bytes(cfg, 192) == 2 * 192 * state["ssm"] \
        == pytest.approx(1.81e9, rel=0.01)
    assert cm.ssm_leaf_shape(cfg, 192) == "f32[192,8,128,128]"
    # the chunked form of a 1,024 bucket: 16 chunks x 8 heads x 9 layers
    macs = (2 * 64 * 64 * 128 + 64 * 64 * 256 / 2 + 3 * 64 * 128 * 128
            + 64 * 64 * 128)
    assert cm.gdn_chunk_flops(cfg, 1024) == 2 * macs * 16 * 8 * 9
    assert cm.gdn_chunk_flops(cfg, 1000) == cm.gdn_chunk_flops(cfg, 1024)
    assert cm.gdn_chunk_min_bytes(cfg, 1024) == 9 * (
        1024 * 8 * (384 * 2 + 8 + 128 * 4) + 8 * 128 * 128 * 4)
    # what the engine declares is what the benchmark counts
    import jax.numpy as jnp
    from bigdl_tpu.common import get_policy, set_policy
    from bigdl_tpu.models import decode as kv
    prior = get_policy()
    try:
        cm.set_policy(cfg)
        total, fixed = kv.state_bytes_per_row(cm.build_model(cfg), 1536,
                                              jnp.bfloat16)
    finally:
        set_policy(prior)
    assert fixed == sum(state.values())
    assert total - fixed == 3 * 2 * 256 * 2 * 1536


def test_the_state_reader_would_find_this_leaf():
    """The accepted reader finds the delta rule's update by the leaf's
    shape, as it finds Mamba's; the cell is not on its list because on the
    chip the update's pass reads a state the compiler staged in its second
    memory space, and the share came out above 100 % (PERF.md)."""
    reader = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "ssm_state_roofline_pct.decode.py"), "reader_ssm_q")
    cell = _cell()
    leaf = "f32[192,8,128,128]{3,2,1,0:T(8,128)}"
    update = (f"%multiply_add_fusion.2 = {leaf} fusion(%c, %a, %b), "
              f"kind=kLoop, calls=%fused_computation.61")
    reads = f"%fusion.9 = f32[192,8,128]{{2,1,0}} fusion({leaf} %p)"
    facts = {"cfg": cell.cfg, "traffic": cell.traffic,
             "device": {"kind": "TPU v5 lite"},
             "trace": {"busy_s": 2.0,
                       "modules": [["jit_decode_step", 50.0, 1.0],
                                   ["jit_decode_prefill", 40.0, 1.0]],
                       "ops": [[update, 0.12], [reads, 0.06]]}}
    # 0.12 s of 2.0 busy, 2.0 s of programs, 50 steps: 2.4 ms a step
    want = 100 * 2 * 192 * 9 * 8 * 128 * 128 * 4 / (0.0024 * 819e9)
    assert reader.read(facts) == pytest.approx(want)


def test_reference_agrees_with_the_program_at_rehearse_sizes(fresh_policy):
    import jax
    import jax.numpy as jnp
    cm, cfg = _tiny()
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 20)).astype(np.int32))
    got, _ = model.apply(params, state, toks)
    p0 = cm.init_params(cfg, jax.random.key(5))
    from benchmark.reference import qwen3_next_share4 as ref
    want = jax.nn.log_softmax(ref.logits(cfg, p0, toks))
    assert float(jnp.abs(got - want).max()) < 2e-4
    low = jax.nn.log_softmax(ref.logits(cfg, p0, toks, "fp8"))
    assert float(jnp.abs(low - want).max()) > 2e-3


def test_served_routing_is_followed_and_held_to_the_references_router(
        fresh_policy):
    """`routed_logits_fn` with the routing the engine returned: in float32
    the served choices are the reference's own, forcing them changes
    nothing, and choices that are no router's read 1."""
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
    # every block keeps state, so only the last block's experts see the
    # prompt's last position alone
    assert (served[1, :5, :18] >= 0).all()
    assert (served[1, 5, :12] < 0).all() and (served[1, 5, 12:18] >= 0).all()
    p0 = cm.init_params(cfg, key)
    from benchmark.reference import qwen3_next_share4 as ref
    own = np.asarray(ref.logits(cfg, p0, jnp.asarray(toks)))
    f32 = jax.jit(cm.routed_logits_fn(cfg))
    got, made, disagree = map(np.asarray, f32(p0, toks, served))
    assert not disagree.any()
    np.testing.assert_allclose(got, own, atol=1e-5)
    given = served[..., 0] >= 0
    np.testing.assert_array_equal(np.sort(made[given], -1),
                                  np.sort(served[given], -1))
    # choices that are no router's: held experts 0, 1, 2 at every position
    wrong = np.where(served >= 0, np.arange(3), -1).astype(np.int32)
    assert (np.asarray(f32(p0, toks, wrong)[2]) > 0.8).all()
    _low, theirs, _d = jax.jit(cm.routed_logits_fn(cfg, "fp8"))(
        p0, toks, np.full_like(served, -1))
    assert (np.sort(np.asarray(theirs)[given], -1)
            != np.sort(served[given], -1)).any()


def test_the_control_is_read_under_its_own_choices():
    """`benchmark/control.py` through the cell's driver: the sound run's two
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


def _decay_left_out(monkeypatch):
    """The delta rule without its gate: ``S' = S`` for ``exp(g) S``."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import GatedDeltaNet
    sound = GatedDeltaNet._project

    def project(self, params, u):
        qkv, z, beta, g = sound(self, params, u)
        return qkv, z, beta, jnp.zeros_like(g)

    monkeypatch.setattr(GatedDeltaNet, "_project", project)


def _w_for_one_plus_w(monkeypatch):
    """The query and key norms multiply by ``w``, not by ``1 + w``."""
    from bigdl_tpu.nn import attention
    sound = attention.rms_norm
    monkeypatch.setattr(
        attention, "rms_norm",
        lambda x, weight, eps, plus_one=False: sound(x, weight, eps))


def _shared_gate_left_out(monkeypatch):
    """The shared expert weighs 1, not ``sigmoid(x w_sg)``."""
    from bigdl_tpu.parallel.expert import GatedMoE
    sound = GatedMoE._forward

    def forward(self, params, x, live=None):
        had, self.shared_gate = self.shared_gate, False
        try:
            return sound(self, params, x, live)
        finally:
            self.shared_gate = had

    monkeypatch.setattr(GatedMoE, "_forward", forward)


@pytest.mark.parametrize("plant", [_decay_left_out, _w_for_one_plus_w,
                                   _shared_gate_left_out],
                         ids=["decay", "qk_norm", "shared_gate"])
def test_a_planted_fault_is_not_correct(plant, monkeypatch, capsys,
                                        fresh_policy):
    plant(monkeypatch)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True
