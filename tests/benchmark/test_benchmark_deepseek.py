"""The configuration ``deepseek_v2_share4`` and its cell ``dsv2.decode``
(ISSUE 27), at the tests' tiny sizes on the CPU: the cell runs end to end
through the harness, the plain reference agrees with the program, the
bytes a decode step cannot avoid match a count by hand, the new reader reads
a recorded fact, and the timed path broken underneath reads ``correct``
false."""

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
CELL = "dsv2.decode"


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
    assert ("step_hbm_roofline_pct.decode" in want) == bool(trace)
    assert all(m["value"] == "not measured" for m in line["metrics"].values())


def test_configuration_file_keeps_every_published_number():
    """Every number of the catalog's row is in the file under its own key;
    the four that differ are the cut, listed in ``reduced`` with the
    published counts beside them; no width is among them."""
    cfg = _cell().cfg
    published = {
        "first_k_dense_replace": 1, "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
        "n_shared_experts": 2, "num_attention_heads": 128,
        "num_experts_per_tok": 6, "num_hidden_layers": 60,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "topk_group": 3, "v_head_dim": 128,
        "vocab_size": 102400}
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == sorted(
        next(c for c in BENCH["configs"]
             if c["name"] == "deepseek_v2_share4")["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["num_attention_heads"], cfg["vocab_size"]) == (5, 40, 32,
                                                               25600)
    assert cfg["held"]["router_outputs"] == 160
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    tr = _cell().traffic
    assert tr["prompt_len"][1] + tr["output_len"][1] <= tr["max_len"]


def test_the_programs_tree_takes_the_references_weights(fresh_policy):
    """At the real sizes, by shape alone (nothing is allocated): the
    reference's tree flattens in the program's order, and the counts by
    hand are the parameters that are there."""
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
    assert n["once"] + n["routed"] + n["embedding"] == total
    assert total == pytest.approx(4.645e9, rel=1e-3)       # ISSUE 27's table


def test_decode_step_min_bytes_against_a_count_by_hand():
    cell = _cell()
    cm, cfg = cell.cfg_mod, cell.cfg
    # attention a layer, 32 of 128 heads: q_a, q_b, kv_a, kv_b, o, 2 norms
    attn = (5120 * 1536 + 1536 * 32 * 192 + 5120 * 576 + 512 * 32 * 256
            + 32 * 128 * 5120 + 1536 + 512)
    shared = 3 * 5120 * 2 * 1536
    router = 5120 * 160
    dense = 3 * 5120 * 12288
    once = (5 * (attn + 2 * 5120) + dense + 4 * (shared + router)
            + 5120 + 25600 * 5120)               # final norm, head
    routed = 4 * 40 * 3 * 5120 * 1536
    assert cm.param_counts(cfg) == {"once": once, "routed": routed,
                                    "embedding": 25600 * 5120}
    # no token: nothing of the experts; many: all of them
    assert cm.decode_step_min_bytes(cfg, 0) == 2 * once
    assert cm.decode_step_min_bytes(cfg, 1e6) == \
        pytest.approx(2 * (once + routed))
    touched = 1 - (1 - 6 / 160) ** 64
    assert cm.decode_step_min_bytes(cfg, 64) == \
        pytest.approx(2 * (once + routed * touched))
    assert cm.decode_step_min_bytes(cfg, 64) == pytest.approx(8.36e9,
                                                              rel=0.01)


def test_the_roofline_reader_on_a_recorded_fact():
    reader = harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics",
                     "step_hbm_roofline_pct.decode.py"), "reader_roofline")
    cell = _cell()
    facts = {"cfg": cell.cfg, "device": {"kind": "TPU v5 lite"},
             "trace": {"modules": [["jit_decode_step", 50.0, 0.75],
                                   ["jit_decode_prefill", 20.0, 1.0]]},
             "trace_counts": {"decode_steps": 50, "prefill_steps": 20,
                              "tokens_out": 50 * 64 + 20, "seconds": 2.0}}
    # 64 tokens a step, 15 ms a run: the bytes over 15 ms x 819 GB/s
    want = 100 * cell.cfg_mod.decode_step_min_bytes(cell.cfg, 64) \
        / (0.015 * 819e9)
    assert reader.read(facts) == pytest.approx(want)
    assert 60 < want < 75
    # a parent without the program's name, a run without a step, another
    # configuration: nothing, and no error
    for broken in ({"trace": {"modules": [["jit_step", 3.0, 1.0]]}},
                   {"trace_counts": dict(facts["trace_counts"],
                                         decode_steps=0)},
                   {"cfg": {"name": "gpt2_medium"}}):
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
    toks = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 20)).astype(np.int32)
    got, _ = model.apply(params, state, jnp.asarray(toks))
    p0 = cm.init_params(cfg, jax.random.key(5))
    from benchmark.reference import deepseek_v2_share4 as ref
    want = jax.nn.log_softmax(ref.logits(cfg, p0, jnp.asarray(toks)))
    # float32 both: sums in another order (test_deepseek.py has the reason)
    assert float(jnp.abs(got - want).max()) < 2e-4
    low = jax.nn.log_softmax(cm.logits_fn(cfg, "fp8")(p0, jnp.asarray(toks)))
    assert float(jnp.abs(low - want).max()) > 2e-3
    # what the driver compares with: the same logits where the reference
    # decides the choice among the held experts by the margin in every
    # layer, a flat row elsewhere
    toks = jnp.asarray(toks)
    _out, seen = ref.logits(cfg, p0, toks, widths=(0.001, 0.05, 0.4))
    held = dict(cfg, limits={"decode": {"routing_margin": 0.05,
                                        "decided_share_min": 0.08}})
    got = np.asarray(jax.jit(cm.logits_fn(held))(p0, toks))
    decided = np.asarray(seen["decided"][:, :, 1].all(axis=1))
    assert 0 < decided.sum() < decided.size     # at 0.05 some near tie
    np.testing.assert_array_equal(got[decided], np.asarray(_out)[decided])
    assert not got[~decided].any()
    # a wider margin decides fewer positions
    assert seen["decided"][:, :, 0].all(axis=1).mean() > decided.mean()
    # too few decided positions to trust the mask: every position is held
    few = np.asarray(seen["decided"][:, :, 2].all(axis=1)).mean()
    assert few < 0.5
    held = dict(cfg, limits={"decode": {"routing_margin": 0.4,
                                        "decided_share_min": 0.5}})
    np.testing.assert_array_equal(
        np.asarray(jax.jit(cm.logits_fn(held))(p0, toks)), np.asarray(_out))


def test_a_decided_choice_of_held_experts_survives_every_small_change():
    """``held_choice_decided`` against what it promises, on random router
    logits at the published shape (160 experts, 8 groups, 3 kept, 6 a
    token, experts 0..39 held): where it says decided by ``width``, no
    change of the logits by under ``width / 2`` each changes which held
    experts are chosen; a near tie between two experts held elsewhere does
    not undo it, one that a held expert is part of does."""
    import jax.numpy as jnp
    from benchmark.reference import deepseek_v2_share4 as ref
    z = dict(n_group=8, topk_group=3, k=6, held=(0, 40), scale=1.0)
    r = np.random.default_rng(3)
    logit = r.normal(0, 1.43, (4000, 160)).astype(np.float32)
    width = 0.1

    def held_choice(lg):
        s = jnp.asarray(lg)
        _g, allowed = ref._kept_groups(z, s)
        order = jnp.argsort(-jnp.where(allowed, s, -jnp.inf), axis=-1)
        hot = np.zeros(lg.shape, bool)
        np.put_along_axis(hot, np.asarray(order[:, :6]), True, axis=1)
        return hot[:, :40]

    decided = np.asarray(ref.held_choice_decided(z, jnp.asarray(logit),
                                                 width))
    assert 0.5 < decided.mean() < 0.8        # 0.64 by the arithmetic
    base = held_choice(logit)
    changed = np.zeros(len(logit), bool)
    for _ in range(20):
        # the worst a change under width / 2 can do: every logit at one end
        # or the other
        move = r.choice([-1.0, 1.0], logit.shape) * (0.4999 * width)
        changed |= (held_choice(logit + move.astype(np.float32))
                    != base).any(axis=1)
    assert not (changed & decided).any()
    assert (changed & ~decided).sum() > 100
    # one token: groups 0, 2 and 3 kept by far; held experts 0 and 1 chosen
    # by far; the last chosen and the first left out are both of group 2
    one = np.full((1, 160), -9.0, np.float32)
    one[0, [0, 1]] = 5.0, 4.0
    one[0, [40, 41, 42, 43]] = 3.0, 2.5, 1.5, 1.49
    one[0, 60] = 3.5
    assert bool(ref.held_choice_decided(z, jnp.asarray(one), width)[0])
    one[0, 2] = 1.52                 # a held expert in that near tie
    assert not bool(ref.held_choice_decided(z, jnp.asarray(one), width)[0])


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
        assert len(hit) == 3 * 2       # w_down, w_gate, w_up of two layers
        leaves[hit[0]] = leaves[hit[0]] * 0
        return jax.tree.unflatten(tree, leaves), state

    monkeypatch.setattr(harness, "program_weights", zeroed)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True


def test_an_unrotated_rotary_key_in_the_cache_is_not_correct(
        monkeypatch, capsys, fresh_policy):
    """A fault in the state: the cache's rotary key written as it comes out
    of the projection, unrotated, in the prefill and in the step alike."""
    import jax.numpy as jnp
    from bigdl_tpu.nn import LatentAttention
    sound = LatentAttention._project

    def unrotated_key(self, params, x, pos):
        q_nope, q_rope, c_kv, _ = sound(self, params, x, pos)
        # turned by position 0 is not turned at all
        k_raw = sound(self, params, x, jnp.zeros_like(pos))[3]
        return q_nope, q_rope, c_kv, k_raw

    monkeypatch.setattr(LatentAttention, "_project", unrotated_key)
    rc, line, checks = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
