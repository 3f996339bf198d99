"""The configuration ``nemotron3_nano_share2`` and its cell ``nemo3.decode``
(ISSUE 32), at the tests' tiny sizes on the CPU: the cell runs end to end
through the harness, the configuration file keeps every published number, the
plain reference agrees with the program, the bytes a decode step and its
recurrent-state update cannot avoid match a count by hand, the new reader
reads a recorded fact, and the timed path broken underneath (an expert zeroed,
the state not carried, the convolution's window taken from the pads) reads
``correct`` false."""

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
CELL = "nemo3.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture
def fresh_policy():
    from bigdl_tpu.common import get_policy, set_policy
    prior = get_policy()
    yield
    set_policy(prior)


def _cell():
    return harness.Cell(CELL)


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
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", BIGDL_TPU_XLA_CACHE="0")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True, [ln for ln in lines if '"check"' in ln]
    assert line["attempted"] > 0 and line["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[kind]
            if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == want
    assert ("ssm_state_roofline_pct.decode" in want) == bool(trace)
    assert ("step_hbm_roofline_pct.decode" in want) == bool(trace)
    assert all(m["value"] == "not measured" for m in line["metrics"].values())


def test_configuration_file_keeps_every_published_number():
    """Every number of the catalog's row is in the file under its own key;
    those that differ are the cut, listed in ``reduced`` with the published
    values beside them; no width is among them."""
    cfg = _cell().cfg
    entry = {c["name"]: c for c in BENCH["configs"]}[cfg["name"]]
    reduced = {"num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "mamba_num_heads", "n_groups",
               "num_attention_heads", "num_key_value_heads", "vocab_size"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the cut: two whole periods of the leading MEMEM*E, half of each layer
    assert cfg["hybrid_override_pattern"] == "MEMEM*EMEMEM*E" == \
        row["config"]["hybrid_override_pattern"][:14]
    assert cfg["num_hidden_layers"] == len(cfg["hybrid_override_pattern"])
    for key in ("n_routed_experts", "mamba_num_heads", "n_groups",
                "num_attention_heads", "num_key_value_heads", "vocab_size"):
        assert 2 * cfg[key] == cfg["published"][key], key
    assert cfg["held"]["router_outputs"] == 128
    tr = _cell().traffic
    assert tr["prompt_len"][1] + tr["output_len"][1] <= tr["max_len"]
    assert (tr["clients"], tr["slots"], tr["page"], tr["queue_limit"]) == \
        (192, 128, 1536, 256)


def test_the_programs_tree_takes_the_references_weights(fresh_policy):
    """At the real sizes, by shape alone (nothing is allocated): the
    reference's tree flattens in the program's order, and the counts by hand
    are the parameters that are there: ISSUE 32's 4,445 M, 8.89 GB."""
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
    assert n["once"] + n["routed"] + n["embedding"] == total == 4_445_280_960
    # by hand, a layer of each kind (with its norm)
    mamba = (2688 * 5152 + 4 * 3072 + 3072 + 3 * 32 + 2048 + 2048 * 2688
             + 2688)
    attn = 2 * 2688 * 2048 + 2 * 2688 * 128 + 2688
    moe = (64 * 2 * 2688 * 1856 + 2688 * 128 + 128 + 2 * 2688 * 3712
           + 2688)
    assert (n["mamba_layer"], n["attention_layer"], n["expert_layer"]) == \
        (mamba, attn, moe)
    assert total == 6 * mamba + 2 * attn + 6 * moe + 2688 \
        + 2 * 65536 * 2688


def test_bytes_a_step_cannot_avoid_against_a_count_by_hand():
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    n = cm.param_counts(cfg)
    state = cm.state_bytes_per_row(cfg)
    assert state == {"ssm": 6 * 32 * 64 * 128 * 4, "conv": 6 * 3 * 3072 * 2}
    assert sum(state.values()) == pytest.approx(6.40e6, rel=1e-3)
    # no token: nothing of the experts and no row's state
    assert cm.decode_step_min_bytes(cfg, 0) == 2 * n["once"]
    touched = 1 - (1 - 6 / 128) ** 128
    want = 2 * (n["once"] + n["routed"] * touched) \
        + 2 * 128 * sum(state.values())
    assert cm.decode_step_min_bytes(cfg, 128) == pytest.approx(want)
    assert want == pytest.approx(10.16e9, rel=0.01)        # ISSUE 32: 10.1
    assert cm.ssm_update_min_bytes(cfg, 128) == 2 * 128 * state["ssm"] \
        == pytest.approx(1.61e9, rel=0.01)
    assert cm.ssm_leaf_shape(cfg, 128) == "f32[128,32,64,128]"
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
    assert total - fixed == 2 * 2 * 128 * 2 * 1536


def test_the_state_reader_on_a_recorded_fact():
    reader = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "ssm_state_roofline_pct.decode.py"), "reader_ssm")
    cell = _cell()
    leaf = "f32[128,32,64,128]{3,2,1,0:T(8,128)}"
    update = (f"%multiply_reduce_fusion.5 = (f32[128,32,64]{{2,1,0:T(8,128)"
              f"S(1)}}, {leaf}) fusion(%a, %b), kind=kLoop, calls=%c")
    # a prefill's one row written in place, as the compiled prefill has it:
    # no part of a step, left out
    write = (f"%constant_dynamic-update-slice_fusion.2 = {leaf} fusion(%x, "
             f"%y, %i), kind=kLoop, calls=%fused_computation.852, metadata="
             f"{{op_name=\"jit(decode_prefill)/dynamic_update_slice\"}}")
    reads_only = f"%fusion.9 = f32[128,32,64]{{2,1,0}} fusion({leaf} %p)"
    facts = {"cfg": cell.cfg, "traffic": cell.traffic,
             "device": {"kind": "TPU v5 lite"},
             "trace": {"busy_s": 1.2,
                       "modules": [["jit_decode_step", 40.0, 1.0],
                                   ["jit_decode_prefill", 30.0, 0.5]],
                       "ops": [[update, 0.08], [write, 0.03],
                               [reads_only, 0.5],
                               ["%ragged-dot.1 = f32[768,1856]{1,0} "
                                "custom-call(%q)", 0.3]]}}
    # 0.08 s of 1.2 busy, 1.5 s of programs, 40 steps: 2.5 ms a step
    want = 100 * 2 * 128 * 6 * 32 * 64 * 128 * 4 / (0.0025 * 819e9)
    assert reader.read(facts) == pytest.approx(want)
    assert 75 < want < 82
    # a kernel of the agreed name is found by it, whatever its shapes
    named = dict(facts, trace=dict(facts["trace"], ops=[
        ["%ssm_step.2 = (f32[8]{0}) custom-call(%s), custom_call_target="
         "\"tpu_custom_call\"", 0.08]]))
    assert reader.read(named) == pytest.approx(want)
    # a parent without the leaf or the program, another configuration, no
    # trace: nothing, and no error
    for broken in ({"trace": dict(facts["trace"], ops=[[reads_only, 1.0]])},
                   {"trace": dict(facts["trace"],
                                  modules=[["jit_step", 3.0, 1.0]])},
                   {"cfg": {"name": "gpt2_medium"}},
                   {"cfg": {"name": "deepseek_v2_share4"}},
                   {"trace": None}):
        assert reader.read(dict(facts, **broken)) is None
    assert reader.read({}) is None


def test_reference_agrees_with_the_program_at_rehearse_sizes(fresh_policy):
    import jax
    import jax.numpy as jnp
    cell = _cell()
    cm = cell.cfg_mod
    cfg = dict(cell.cfg)
    cfg.update(cell.cfg["rehearse"])
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 20)).astype(np.int32))
    got, _ = model.apply(params, state, toks)
    p0 = cm.init_params(cfg, jax.random.key(5))
    from benchmark.reference import nemotron3_nano_share2 as ref
    want = jax.nn.log_softmax(ref.logits(cfg, p0, toks))
    assert float(jnp.abs(got - want).max()) < 2e-4
    low = jax.nn.log_softmax(ref.logits(cfg, p0, toks, "fp8"))
    assert float(jnp.abs(low - want).max()) > 2e-3


def test_served_routing_is_followed_and_held_to_the_references_router(
        fresh_policy):
    """The engine returns a finished request's expert choices with it;
    `routed_logits_fn` gives the reference those choices and says at what
    share of a layer's positions they are not its own router's.  Choices
    that are the reference's own change nothing; a near tie decided the
    other way is followed (the logits move) and counted; choices that are
    no router's read 1.  The control's own choices come back for the driver
    to force in their turn."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serve import DecodeEngine
    cell = _cell()
    cm = cell.cfg_mod
    cfg = dict(cell.cfg)
    cfg.update(cell.cfg["rehearse"])
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
    layers, k, width = 2, 3, 24
    toks = np.zeros((3, width), np.int32)
    served = np.full((3, layers, width, k), -1, np.int32)
    for i, (r, h) in enumerate(zip(rows, reqs)):
        assert h.routing.shape == (layers, len(r) - 1, k)
        toks[i, :len(r)] = r
        served[i, :, :len(r) - 1] = h.routing
    # pattern MEM*E: the first expert layer sees the whole prompt, the one
    # past the last layer that keeps state its last position alone
    assert (served[1, 0, :18] >= 0).all() and (served[1, 0, 18:] < 0).all()
    assert (served[1, 1, :12] < 0).all() and (served[1, 1, 12:18] >= 0).all()
    p0 = cm.init_params(cfg, key)
    from benchmark.reference import nemotron3_nano_share2 as ref
    own = np.asarray(ref.logits(cfg, p0, jnp.asarray(toks)))
    f32 = jax.jit(cm.routed_logits_fn(cfg))
    got, made, disagree = map(np.asarray, f32(p0, toks, served))
    # float32 both: the served choices are the reference's own
    assert not disagree.any()
    np.testing.assert_allclose(got, own, atol=1e-5)
    given = served[..., 0] >= 0
    np.testing.assert_array_equal(np.sort(made[given], -1),
                                  np.sort(served[given], -1))
    none = np.full_like(served, -1)
    free, made0, dis0 = map(np.asarray, f32(p0, toks, none))
    np.testing.assert_allclose(free, own, atol=1e-5)
    assert not dis0.any() and (made0 >= 0).all()
    np.testing.assert_array_equal(np.sort(made0[given], -1),
                                  np.sort(served[given], -1))
    # a held choice decided the other way is followed: the logits there
    # move, and the position counts against its layer
    t0 = len(prompts[0])
    at = t0 + 2
    swapped = served.copy()
    held = swapped[0, 0, at][swapped[0, 0, at] < 8]
    swapped[0, 0, at, list(swapped[0, 0, at]).index(held[0])] = next(
        e for e in range(8) if e not in swapped[0, 0, at])
    moved, _m, dis = map(np.asarray, f32(p0, toks, swapped))
    assert np.abs(moved[0, at] - got[0, at]).max() > 1e-3
    np.testing.assert_allclose(moved[0, :at], got[0, :at], atol=1e-5)
    np.testing.assert_allclose(moved[1:], got[1:], atol=1e-5)
    assert dis[0, 0] == pytest.approx(1 / given[0, 0].sum())
    # (the next expert layer's own choice at that position may follow)
    assert not dis[1:].any() and dis[0, 1] <= 1 / given[0, 1].sum() + 1e-6
    # choices that are not this router's
    wrong = np.where(served >= 0, 15 - np.arange(3), -1).astype(np.int32)
    # (experts 13-15 are held elsewhere: a position agrees where the
    # reference chooses none of the held ones either)
    assert (np.asarray(f32(p0, toks, wrong)[2]) > 0.5).all()
    # the control makes its own choices, and hands them on
    _low, theirs, _d = jax.jit(cm.routed_logits_fn(cfg, "fp8"))(
        p0, toks, none)
    assert (np.sort(np.asarray(theirs)[given], -1)
            != np.sort(served[given], -1)).any()


def test_the_control_is_read_under_its_own_choices():
    """`benchmark/control.py` through the cell's driver: the sound run's two
    numbers inside their limits, the control's gap (its own choices forced
    into the float32 reference, as the served ones are) outside."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", BIGDL_TPU_XLA_CACHE="0")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control.py"),
         "--workload", CELL, "--seeds", "11,12", "--seconds", "1",
         "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.splitlines()[-1])
    lim = _cell().cfg["rehearse"]["limits"]["decode"]
    assert last["sound_largest"]["logit_gap"] <= lim["logit_gap"]
    assert last["sound_largest"]["routing_disagree"] \
        <= lim["routing_disagree"]
    assert last["control_smallest"]["fp8:logit_gap"] > lim["logit_gap"]


def test_a_decided_choice_of_held_experts_survives_every_small_change():
    """``held_choice_decided`` against what it promises, on random router
    logits at the published shape (128 experts, 6 a token, a selection bias,
    experts 0..63 held): where it says decided by ``width``, no change of
    the logits by under ``width / 2`` each changes which held experts are
    chosen."""
    import jax.numpy as jnp
    from benchmark.reference import nemotron3_nano_share2 as ref
    z = dict(k=6, held=(0, 64))
    r = np.random.default_rng(3)
    logit = r.normal(0, 1.04, (3000, 128)).astype(np.float32)
    p = {"select_bias": jnp.asarray(r.normal(0, 0.02, 128), jnp.float32)}
    width = 0.1

    def held_choice(lg):
        key = 1 / (1 + np.exp(-lg)) + np.asarray(p["select_bias"])
        hot = np.zeros(lg.shape, bool)
        np.put_along_axis(hot, np.argsort(-key, axis=1)[:, :6], True, axis=1)
        return hot[:, :64]

    decided = np.asarray(ref.held_choice_decided(z, p, jnp.asarray(logit),
                                                 width))
    assert 0.3 < decided.mean() < 0.9
    base = held_choice(logit)
    changed = np.zeros(len(logit), bool)
    for _ in range(20):
        move = r.choice([-1.0, 1.0], logit.shape) * (0.4999 * width)
        changed |= (held_choice(logit + move.astype(np.float32))
                    != base).any(axis=1)
    assert not (changed & decided).any()
    assert (changed & ~decided).sum() > 100
    # a near tie between two experts held elsewhere does not undo it; one
    # that a held expert is part of does
    p0 = {"select_bias": jnp.zeros(128)}
    one = np.full((1, 128), -9.0, np.float32)
    one[0, [0, 1, 2]] = 5.0, 4.0, 3.0
    one[0, [70, 71, 72, 73]] = 2.5, 2.0, 1.5, 1.49
    assert bool(ref.held_choice_decided(z, p0, jnp.asarray(one), width)[0])
    one[0, 3] = 1.52
    assert not bool(ref.held_choice_decided(z, p0, jnp.asarray(one),
                                            width)[0])


def test_the_sound_path_is_correct(capsys, fresh_policy):
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, checks


def test_one_experts_weights_zeroed_is_not_correct(monkeypatch, capsys,
                                                   fresh_policy):
    """A fault in the weights the timed engine serves from: every held
    expert's down projection of the first expert layer zeroed in the
    program's tree, the reference's left whole."""
    import jax
    sound = harness.program_weights

    def zeroed(cm, cfg, model, key):
        params, state = sound(cm, cfg, model, key)
        leaves, tree = jax.tree.flatten(params)
        hit = [i for i, x in enumerate(leaves) if x.ndim == 3]
        assert len(hit) == 2 * 2        # w_down, w_up of two expert layers
        leaves[hit[0]] = leaves[hit[0]] * 0
        return jax.tree.unflatten(tree, leaves), state

    monkeypatch.setattr(harness, "program_weights", zeroed)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True


def test_a_state_that_is_not_carried_is_not_correct(monkeypatch, capsys,
                                                    fresh_policy):
    """A fault in the state: a step computes its new recurrent state and
    throws it away, and the next one starts from nothing."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import Mamba2Mixer
    sound = Mamba2Mixer.decode_step

    def not_carried(self, params, x, cache, pos):
        y, new = sound(self, params, x, cache, pos)
        return y, dict(new, ssm=jnp.zeros_like(new["ssm"]))

    monkeypatch.setattr(Mamba2Mixer, "decode_step", not_carried)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False


def test_a_convolution_window_from_the_pads_is_not_correct(
        monkeypatch, capsys, fresh_policy):
    """A fault in what a prefill owes a fixed leaf: the convolution's window
    taken from the end of the padded bucket, not from the prompt's last real
    positions."""
    import jax
    from bigdl_tpu.nn import Mamba2Mixer
    sound = Mamba2Mixer.decode_prefill

    def from_the_pads(self, params, x, cache, slot, length):
        y, new = sound(self, params, x, cache, slot, length)
        _y, wrong = sound(self, params, x, cache, slot, x.shape[1])
        return y, dict(new, conv=wrong["conv"])

    monkeypatch.setattr(Mamba2Mixer, "decode_prefill", from_the_pads)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
