"""Decode state as the layer's own declaration (ISSUE 27): what is kept, which
axis is the length, where it lives on a mesh; one walk for the engine's two
programs; and `Module.attach()` that makes no gradient until one is read."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.models import (PositionalEmbedding, TransformerLM,
                              cached_generate, init_kv_cache)
from bigdl_tpu.models import decode as kv
from bigdl_tpu.models.deepseek import DeepSeekV2LM
from bigdl_tpu.nn.module import StateLeaf
from bigdl_tpu.parallel.layout import MeshLayout
from bigdl_tpu.serve import DecodeEngine


def _lm():
    return TransformerLM(vocab_size=64, max_len=64, d_model=32, num_heads=4,
                         num_layers=2).build(jax.random.key(0))


def _ds():
    return DeepSeekV2LM(
        vocab_size=64, hidden=32, num_layers=2, heads_held=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        dense_width=64, expert_width=16, num_experts=8, experts_per_token=2,
        n_group=2, topk_group=1, n_shared=1, routed_scaling_factor=2.0,
        experts_held=(0, 4)).build(jax.random.key(1))


def test_layers_declare_their_decode_state():
    mha = nn.MultiHeadAttention(32, 4, causal=True)
    assert mha.decode_state(3, 16) == {
        "k": StateLeaf((3, 4, 16, 8), 2, "kv_cache"),
        "v": StateLeaf((3, 4, 16, 8), 2, "kv_cache")}
    mla = nn.LatentAttention(32, 2, 16, 8, 8, 4, 8)
    assert mla.decode_state(3, 16) == {
        "c_kv": StateLeaf((3, 16, 8), 1, "latent_cache"),
        "k_rope": StateLeaf((3, 16, 4), 1, "latent_cache")}
    # needs the position, keeps nothing; a plain layer says nothing at all
    assert PositionalEmbedding(16, 32).decode_state(3, 16) == {}
    assert nn.Linear(4, 4).decode_state(3, 16) is None
    from bigdl_tpu.parallel.expert import GatedMoE
    assert GatedMoE(32, 16, 8, 2, held=(0, 4)).decode_state(3, 16) == {}
    assert [type(m).__name__ for m, _ in kv._stateful_modules(_lm())] == \
        ["MultiHeadAttention"] * 2
    assert [sorted(c) for c in init_kv_cache(_ds(), 2, 8)] == \
        [["c_kv", "k_rope"]] * 2


@pytest.mark.parametrize("model,prompts", [
    ("lm", [(5, 9), (3, 12), (17, 6)]), ("ds", [(4, 7), (9, 5)])])
def test_engine_tokens_are_bit_equal_to_cached_generate(model, prompts):
    """The merged walk against the oracle's own, for both state kinds
    (`cached_generate` steps a latent layer through its `decode_step`, all
    rows at one position; the engine prefills it in one pass and steps every
    slot at its own)."""
    m = _lm() if model == "lm" else _ds()
    rows = [np.random.default_rng(100 + n).integers(1, 64, n).astype(np.int32)
            for n, _ in prompts]
    with DecodeEngine(m, slots=2, page=8, max_len=32) as eng:
        outs = [h.result(120.0) for h in
                [eng.submit(p, k) for p, (_, k) in zip(rows, prompts)]]
        st = eng.stats()
    for p, (_, k), out in zip(rows, prompts, outs):
        np.testing.assert_array_equal(out, cached_generate(m, p, k, 32))
    assert ("expert_tokens" in st) == (model == "ds")


def test_an_expert_layer_reads_which_tokens_are_real_off_the_interface():
    """`GatedMoE` gets no word from the walk but the decode interface's own
    arguments: a prompt's `length`, a step's `pos` (negative: idle).  Pads
    and idle rows go to no expert and are counted nowhere; the real tokens'
    outputs are what the plain `apply` gives them."""
    from bigdl_tpu.parallel.expert import GatedMoE
    moe = GatedMoE(32, 16, 8, 2, n_group=2, topk_group=1, n_shared=1,
                   held=(0, 4))
    p, s = moe.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 12, 32))
    want, ns = moe.apply(p, s, x)
    assert int(ns["expert_tokens"].sum()) == 12 * 2
    y, counts = moe.decode_prefill(p, x, None, 0, 7)
    assert int(counts.sum()) == 7 * 2
    np.testing.assert_allclose(y[:, :7], want[:, :7], atol=1e-6)
    # the last real position alone, as the walk hands it on after the last
    # layer that keeps leaves
    y1, c1 = moe.decode_prefill(p, x[:, 6:7], None, 0, 7)
    assert int(c1.sum()) == 2
    np.testing.assert_allclose(y1, want[:, 6:7], atol=1e-6)
    rows = x[0][:, None]                                   # [12, 1, 32]
    pos = jnp.asarray([3, -1, 0, 5, -1, -1, 2, 9, 1, -1, 4, 7])
    y, counts = moe.decode_step(p, rows, None, pos)
    assert int(counts.sum()) == 8 * 2
    live = np.asarray(pos) >= 0
    np.testing.assert_allclose(y[live, 0], want[0][live], atol=1e-6)
    # nothing process-wide carries the mask any more
    import bigdl_tpu.parallel.expert as ep
    assert not hasattr(ep, "live_tokens") and not hasattr(kv, "live_tokens")


@pytest.mark.parametrize("make,axes", [(_lm, {"k": 2, "v": 2}),
                                       (_ds, {"c_kv": 1, "k_rope": 1})])
def test_cache_grows_along_each_leafs_own_length_axis(make, axes):
    m = make()
    eng = DecodeEngine(m, slots=2, page=8, max_len=32)
    eng._ensure_cache(6, idle=True)
    assert eng._cache_len == 8
    marked = tuple({n: a + 1 for n, a in c.items()} for c in eng._caches)
    eng._caches = marked
    per_slot = eng.cache_bytes_per_slot()
    eng._ensure_cache(20, idle=False)
    assert eng._cache_len == 32 and eng.cache_grows == 1
    assert eng.cache_bytes_per_slot() == 4 * per_slot
    for c in eng._caches:
        for n, a in c.items():
            ax = axes[n]
            assert a.shape[ax] == 32
            old, new = np.split(np.asarray(a, np.float32), [8], axis=ax)
            assert (old == 1).all() and (new == 0).all()
    # the declared size is what the arrays hold
    assert eng.cache_bytes_per_slot() == sum(
        a.nbytes for c in eng._caches for a in c.values()) // 2


def test_both_state_kinds_are_placed_on_a_mesh():
    mesh = MeshLayout(data=2, fsdp=1, tp=2).build_mesh()
    for make, specs in ((_lm, {"k": ("data", "fsdp"), "v": ("data", "fsdp")}),
                        (_ds, {"c_kv": ("data", "fsdp"),
                               "k_rope": ("data", "fsdp")})):
        caches = init_kv_cache(make(), 4, 8, jnp.float32, mesh=mesh)
        for c in caches:
            for n, a in c.items():
                spec = a.sharding.spec
                assert spec[0] == specs[n]
                # heads over tp for {k, v}; a latent has no head axis and
                # every tp share holds it whole
                assert (spec[1] if len(spec) > 1 else None) == \
                    ("tp" if n in "kv" else None)
    m = _ds()
    prompt = np.arange(1, 6, dtype=np.int32)
    want = cached_generate(m, prompt, 4, 16)
    with DecodeEngine(m, slots=2, page=16, max_len=16, mesh=mesh) as eng:
        np.testing.assert_array_equal(eng.generate(prompt, 4), want)


# (h) ---------------------------------------------------------------------


def test_attach_makes_no_gradient_until_one_is_read():
    m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Tanh())
    params, state = m.init(jax.random.key(0))
    m.attach(params, state)
    assert m._grads is None                  # serving never makes them
    m.forward(jnp.ones((2, 4)))
    assert m._grads is None
    m.zero_grad_parameters()                 # zeros not made are zeros
    assert m._grads is None
    g = m.grads                              # the first read makes them
    assert jax.tree.structure(g) == jax.tree.structure(params)
    assert all(not np.asarray(x).any() for x in jax.tree.leaves(g))
    m.backward(jnp.ones((2, 4)), jnp.ones((2, 3)))
    assert any(np.asarray(x).any() for x in jax.tree.leaves(m.grads))
    ws, gs = m.parameters()
    assert [w.shape for w in ws] == [x.shape for x in gs]
    m.attach(params, state)                  # and attach() resets them
    assert m._grads is None
    assert all(not np.asarray(x).any() for x in jax.tree.leaves(m.grads))
    m.grads = None                           # set to nothing stays nothing
    assert m.grads is None


def test_save_and_load_keep_the_gradients_lazy(tmp_path):
    m = nn.Sequential().add(nn.Linear(4, 3))
    m.build(jax.random.key(0))
    m.save(str(tmp_path / "m.bin"))
    assert m._grads is None and m._grads_due     # saving made none
    back = nn.Module.load(str(tmp_path / "m.bin"))
    assert back._grads is None
    np.testing.assert_array_equal(np.asarray(back.params[0]["weight"]),
                                  np.asarray(m.params[0]["weight"]))
    assert jax.tree.structure(back.grads) == jax.tree.structure(back.params)


def test_optimizer_still_trains_after_attach():
    from bigdl_tpu import Engine
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    Engine.init()
    r = np.random.default_rng(0)
    x = r.normal(size=(64, 4)).astype(np.float32)
    w = r.normal(size=(4, 1)).astype(np.float32)
    y = (x @ w).astype(np.float32)
    m = nn.Sequential().add(nn.Linear(4, 1))
    params, state = m.init(jax.random.key(2))
    m.attach(params, state)
    before = float(np.mean((np.asarray(m.forward(jnp.asarray(x))) - y) ** 2))
    ds = DataSet.array([Sample(a, b) for a, b in zip(x, y)]) \
        .transform(SampleToMiniBatch(16, drop_last=True))
    Optimizer(m, ds, nn.MSECriterion()).set_optim_method(SGD(0.1)) \
        .set_end_when(Trigger.max_epoch(5)).optimize()
    after = float(np.mean((np.asarray(m.forward(jnp.asarray(x))) - y) ** 2))
    assert after < 0.2 * before
