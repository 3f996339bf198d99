"""``JambaLM`` (``models/jamba.py``) at a small size on the CPU against the
plain reference's full forward (``benchmark/reference/jamba2_3b.py``) on
seeded weights: the model, ``cached_generate`` and ``DecodeEngine`` (prefill
then decode through the cache; logits compared, not tokens), the order of
the layers from the two attention keys, the one table that is embedding and
head, and multi-query attention at 20 heads on 1 through the code
``nemo3.decode`` runs."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import jamba2_3b as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.models import JambaLM, cached_generate
from bigdl_tpu.models.jamba import jamba_layer_kinds
from bigdl_tpu.nn import (Linear, LookupTable, MambaMixer,
                          MultiHeadAttention, TiedSequential)
from bigdl_tpu.serve import DecodeEngine

from test_mamba1 import small_cfg

TOL = 2e-4


@pytest.fixture(autouse=True)
def _float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        yield
    set_policy(prior)


def build(cfg):
    z = ref.sizes(cfg)
    return JambaLM(z["vocab"], z["hidden"], z["layers"], z["period"],
                   z["offset"], z["heads"], z["kv_heads"], z["mlp"],
                   cfg["mamba_expand"], z["state"], z["rank"], z["taps"],
                   cfg["tie_word_embeddings"], z["eps"])


def seeded(cfg, seed=3):
    """(model, its params laid out from the reference's seeded tree, state,
    the reference's tree)."""
    model = build(cfg)
    shapes, _ = jax.eval_shape(model.init, jax.random.key(0))
    p0 = ref.init_params(cfg, jax.random.key(seed))
    leaves = jax.tree.leaves(p0)
    assert [a.shape for a in leaves] == \
        [s.shape for s in jax.tree.leaves(shapes)]
    params = jax.tree.unflatten(jax.tree.structure(shapes), leaves)
    _, state = model.init(jax.random.key(0))
    return model, params, state, p0


def _mixers(model):
    from bigdl_tpu.models.decode import _stateful_modules
    return [m for m, _spec in _stateful_modules(model)]


# ------------------------------------------------------ the layers' order


def test_the_layer_order_follows_from_the_two_attention_keys():
    """The published 28 layers with ``attn_layer_period`` 14 and
    ``attn_layer_offset`` 7: attention at 7 and 21, Mamba elsewhere."""
    kinds = jamba_layer_kinds(28, 14, 7)
    assert [i for i, k in enumerate(kinds) if k == "*"] == [7, 21]
    assert kinds.count("M") == 26
    model = JambaLM(64, 40, 28, 14, 7, 20, 1, 16, mamba_state=4,
                    mamba_dt_rank=2)
    mixers = _mixers(model)
    assert [i for i, m in enumerate(mixers)
            if isinstance(m, MultiHeadAttention)] == [7, 21]
    assert sum(isinstance(m, MambaMixer) for m in mixers) == 26
    attn = mixers[7]
    # 20 query heads on one key-value head, no positions, no bias
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (20, 1, 2)
    p = attn._init(jax.random.key(0))
    assert sorted(p) == ["wk", "wo", "wq", "wv"]
    assert p["wk"].shape == p["wv"].shape == (40, 2)


# ---------------------------------------------------------------- the tie


def test_one_table_is_embedding_and_head():
    """One leaf: the head's slot in the tree is empty, and a changed row of
    the table moves both that token's embedding and that row's logit."""
    cfg = small_cfg()
    model, params, state, p0 = seeded(cfg)
    assert isinstance(model, TiedSequential)
    table, head = model.modules[0], model.modules[-2]
    assert isinstance(table, LookupTable) and isinstance(head, Linear)
    assert params[len(model.modules) - 2] == {}
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    tables = [a for a in jax.tree.leaves(params) if a.shape == (v, d)]
    assert len(tables) == 1
    toks = jnp.array([[5, 17, 9, 17]])
    before, _ = model.apply(params, state, toks)
    moved = list(params)
    moved[0] = {"weight": params[0]["weight"].at[100].add(0.5)}
    after, _ = model.apply(moved, state, toks)
    # token 100 is not in the input: only the head sees the row, and only
    # logit 100 moves (before the log-softmax's shared shift)
    raw = lambda lp: lp - lp[..., :1]
    delta = raw(after) - raw(before)
    assert float(jnp.abs(delta[..., 100]).min()) > 1e-3
    np.testing.assert_allclose(jnp.delete(delta, 100, axis=-1), 0, atol=1e-5)
    # token 17 is in the input: its row moves the embedding, so every
    # later position's logits move
    moved[0] = {"weight": params[0]["weight"].at[17].add(0.5)}
    after, _ = model.apply(moved, state, toks)
    assert float(jnp.abs(raw(after) - raw(before))[0, 1:, :16].max()) > 1e-3
    # position 0 saw token 5 alone: there only the head's row 17 moved
    np.testing.assert_allclose(
        jnp.delete((raw(after) - raw(before))[0, 0], 17), 0, atol=1e-5)


def test_a_gradient_through_either_use_lands_on_the_one_leaf():
    cfg = small_cfg(num_hidden_layers=1, attn_layer_offset=5)
    model, params, state, _ = seeded(cfg)
    toks = jnp.array([[5, 17, 9]])

    def loss(p):
        return model.apply(p, state, toks)[0][0, -1, 3]

    g = jax.grad(loss)(params)
    assert g[len(model.modules) - 2] == {}
    gt = g[0]["weight"]
    assert float(jnp.abs(gt[5]).max()) > 0          # through the embedding
    assert float(jnp.abs(gt[100]).max()) > 0        # through the head


def test_the_untied_model_keeps_a_head_of_its_own():
    model = build(small_cfg(tie_word_embeddings=False))
    shapes, _ = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(a.shape == (211, 32) for a in jax.tree.leaves(shapes)) == 2


# ----------------------------------------------- against the full forward


def test_model_against_the_reference_on_seeded_weights():
    cfg = small_cfg()
    model, params, state, p0 = seeded(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 21), 0, 211)
    got, _ = model.apply(params, state, toks)
    want = jax.nn.log_softmax(ref.logits(cfg, p0, toks), axis=-1)
    np.testing.assert_allclose(got, want, atol=TOL)
    # the control one precision down is far outside the tolerance
    low = jax.nn.log_softmax(ref.logits(cfg, p0, toks, "fp8"), axis=-1)
    assert float(jnp.abs(low - want).max()) > 0.05


def _gap(cfg, p0, prompt, out):
    """The widest gap by which a served token's reference logit lies under
    the reference's best, over the generated positions."""
    lg = ref.logits(cfg, p0, jnp.asarray(out)[None])[0]
    at = np.arange(len(prompt) - 1, len(out) - 1)
    return float((lg[at].max(-1) - lg[at, out[len(prompt):]]).max())


def test_cached_generate_against_the_full_forward():
    """Position by position through the cache, no prefill: every token is
    the reference's greedy one for the sequence so far."""
    cfg = small_cfg()
    model, params, state, p0 = seeded(cfg)
    model.attach(params, state)
    prompt = np.random.default_rng(8).integers(1, 211, 7).astype(np.int32)
    out = cached_generate(model, prompt, 12, 32)
    assert len(out) == 19
    assert _gap(cfg, p0, prompt, out) < 1e-3


def _served(cfg, prompts, slots=2, page=16, first_in_flight=False, **kw):
    model, params, state, p0 = seeded(cfg)
    model.attach(params, state)
    with DecodeEngine(model, slots=slots, page=page, max_len=64, **kw) as eng:
        hs = [eng.submit(*prompts[0])]
        deadline = time.monotonic() + 120.0
        while first_in_flight and eng.stats()["active"] == 0:
            assert time.monotonic() < deadline, "the first was never admitted"
            time.sleep(0.002)
        hs += [eng.submit(p, k) for p, k in prompts[1:]]
        outs = [h.result(300.0) for h in hs]
        st = eng.stats()
    return model, p0, outs, st


def _prompts(lengths, seed=50):
    return [(np.random.default_rng(seed + i).integers(1, 211, n)
             .astype(np.int32), k) for i, (n, k) in enumerate(lengths)]


def test_the_engine_against_the_full_forward():
    """Through ``DecodeEngine``: prompts of 4, 5, 11 and 19 tokens land in
    buckets of 8, 16 and 32, so the prefill computes pads, and the 16-long
    cache grows by a page under a request in flight (its selective state
    carried over bit for bit); every served token is the reference's greedy
    one for the sequence so far (logits compared, not tokens), and the
    tokens are ``cached_generate``'s."""
    cfg = small_cfg()
    prompts = _prompts([(4, 11), (5, 19), (11, 9), (19, 7)])
    model, p0, outs, st = _served(cfg, prompts, first_in_flight=True,
                                  min_step_s=0.01)
    assert st["cache_grows"] >= 1 and st["cache_len"] >= 32
    fixed = 3 * (8 * 64 * 4 + 3 * 64 * 4)       # 3 Mamba layers of 4
    assert st["state_bytes_per_slot"] == fixed
    assert st["state_bytes_fixed"] == 2 * fixed
    assert st["state_bytes_per_position"] == 2 * 8 * 4   # one kv head of 8
    assert st["prefill_positions"] >= sum(len(p) for p, _k in prompts)
    assert st["prefill_rows"] >= len(prompts)
    for (p, k), out in zip(prompts, outs):
        assert len(out) == len(p) + k
        assert _gap(cfg, p0, p, out) < 1e-3
        np.testing.assert_array_equal(out, cached_generate(model, p, k, 64))


def test_a_slots_second_occupant_gets_a_fresh_engines_tokens():
    """One slot, three requests in turn: the second and third enter a slot
    whose selective state, convolution window, keys and values the one
    before left behind; each gets bit-equal tokens to the same request in a
    fresh engine."""
    cfg = small_cfg()
    prompts = _prompts([(13, 8), (6, 10), (17, 5)], seed=70)
    _, _, outs, _ = _served(cfg, prompts, slots=1)
    for pr, out in zip(prompts, outs):
        _, _, (alone,), _ = _served(cfg, [pr], slots=1)
        np.testing.assert_array_equal(out, alone)


def test_a_group_of_prompts_enters_in_one_pass():
    """Six requests waiting at once for four slots: the engine admits
    several in one prefill call, and each still gets the tokens it gets
    alone."""
    cfg = small_cfg()
    prompts = _prompts([(9, 6), (12, 5), (10, 7), (15, 4), (11, 6), (14, 5)],
                       seed=90)
    model, p0, outs, st = _served(cfg, prompts, slots=4, page=32)
    assert st["prefill_rows"] >= 6
    for (p, k), out in zip(prompts, outs):
        assert _gap(cfg, p0, p, out) < 1e-3
        np.testing.assert_array_equal(out, cached_generate(model, p, k, 64))


def test_multi_query_attention_at_twenty_on_one_is_the_code_that_stands():
    """20 query heads of 128 on one key-value head, no positions, no bias:
    ``MultiHeadAttention`` as ``nemo3.decode`` runs it (16 on 1 there), no
    edit, against the reference's head-by-head form."""
    z = {"heads": 20, "kv_heads": 1, "head_dim": 128}
    layer = MultiHeadAttention(2560, 20, causal=True, with_bias=False,
                               num_kv_heads=1, head_dim=128)
    k = jax.random.split(jax.random.key(2), 5)
    p = {"wq": 0.02 * jax.random.normal(k[0], (2560, 2560)),
         "wk": 0.02 * jax.random.normal(k[1], (2560, 128)),
         "wv": 0.02 * jax.random.normal(k[2], (2560, 128)),
         "wo": 0.02 * jax.random.normal(k[3], (2560, 2560))}
    shapes = jax.eval_shape(layer._init, jax.random.key(0))
    assert {n: a.shape for n, a in p.items()} == \
        {n: a.shape for n, a in shapes.items()}
    x = jax.random.normal(k[4], (1, 9, 2560))
    got, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(got[0], ref.attention(z, p, x[0], "f32"),
                               atol=TOL)
