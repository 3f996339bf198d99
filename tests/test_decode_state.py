"""Decode state as the layer's own declaration (ISSUE 27): what is kept, which
axis is the length (ISSUE 32: or that there is none), where it lives on a
mesh; one walk for the engine's two programs; and `Module.attach()` that
makes no gradient until one is read."""

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


def _nemo():
    """Two Mamba layers, one attention layer (4 query heads on 1 key head),
    one expert layer: leaves of both kinds in one cache."""
    from bigdl_tpu.models.nemotron import NemotronHLM
    return NemotronHLM(
        vocab_size=64, hidden=32, pattern="ME*M", mamba_heads=4,
        mamba_head_dim=8, mamba_groups=2, ssm_state=8, conv_kernel=4,
        chunk=8, num_heads=4, num_kv_heads=1, head_dim=8, expert_width=16,
        shared_width=32, num_experts=8, experts_per_token=2,
        routed_scaling_factor=2.5).build(jax.random.key(2))


def test_a_leaf_without_a_length_is_declared_so():
    m = nn.Mamba2Mixer(32, 4, 8, 2, 8)
    spec = m.decode_state(3, 16)
    assert spec == {
        "ssm": StateLeaf((3, 4, 8, 8), None, "ssm_state", jnp.float32),
        "conv": StateLeaf((3, 3, 4 * 8 + 2 * 2 * 8), None, "latent_cache")}
    # whatever the length
    assert m.decode_state(3, 999) == spec
    gqa = nn.MultiHeadAttention(32, 4, causal=True, num_kv_heads=1,
                                head_dim=8)
    assert gqa.decode_state(3, 16)["k"] == StateLeaf((3, 16, 8), 1,
                                                     "kv_cache")
    assert [sorted(c) for c in init_kv_cache(_nemo(), 2, 8)] == \
        [["conv", "ssm"], ["k", "v"], ["conv", "ssm"]]


def test_grow_cache_carries_a_fixed_leaf_over_bit_for_bit():
    m = _nemo()
    caches = init_kv_cache(m, 2, 8, jnp.bfloat16)
    marked = tuple({n: (a + jnp.arange(a.size, dtype=jnp.float32)
                        .reshape(a.shape).astype(a.dtype) % 7 + 1)
                    for n, a in c.items()} for c in caches)
    grown = kv.grow_cache(m, marked, 32)
    for old, new in zip(marked, grown):
        for n in old:
            if n in ("ssm", "conv"):
                assert new[n].shape == old[n].shape
                assert new[n].dtype == old[n].dtype
                np.testing.assert_array_equal(np.asarray(new[n], np.float32),
                                              np.asarray(old[n], np.float32))
            else:
                assert new[n].shape == (2, 32, 8)
                np.testing.assert_array_equal(
                    np.asarray(new[n][:, :8], np.float32),
                    np.asarray(old[n], np.float32))
                assert not np.asarray(new[n][:, 8:], np.float32).any()
    assert grown[0]["ssm"].dtype == jnp.float32


@pytest.mark.parametrize("first,second", [((5, 3), (9, 14)),
                                          ((11, 4), (3, 20))])
def test_a_cache_that_grows_mid_flight_leaves_the_tokens_unchanged(first,
                                                                    second):
    """A sequence is in flight in an 8-position page when one that needs 32
    is admitted: the cache grows under the first (its keys and values along
    their length, its recurrent state carried over as it is), and both get
    the tokens they get alone in a cache that never grew."""
    m = _nemo()
    rows = [(np.random.default_rng(200 + n).integers(1, 64, n)
             .astype(np.int32), k) for n, k in (first, second)]
    (p1, k1), (p2, k2) = rows
    eng = DecodeEngine(m, slots=2, page=8, max_len=32)
    # driven by hand, so that the second arrives while the first decodes
    h1 = eng.submit(p1, k1)
    assert eng._tick()
    assert eng._cache_len == (8 if len(p1) + k1 <= 8 else 16)
    before = jax.tree.map(np.asarray, eng._caches)
    h2 = eng.submit(p2, k2)
    fixed = {(i, n) for i, c in enumerate(before) for n in c
             if n in ("ssm", "conv")}
    grown_at = eng.cache_grows
    eng._ensure_cache(len(p2) + k2, idle=False)
    assert eng.cache_grows == grown_at + 1 and eng._cache_len == 32
    for i, n in fixed:              # bit for bit through the growth
        np.testing.assert_array_equal(
            np.asarray(eng._caches[i][n], np.float32),
            np.asarray(before[i][n], np.float32))
    eng.start()
    out1, out2 = h1.result(120.0), h2.result(120.0)
    eng.stop()
    np.testing.assert_array_equal(out1, cached_generate(m, p1, k1, 32))
    np.testing.assert_array_equal(out2, cached_generate(m, p2, k2, 32))


def test_engine_counts_both_kinds_of_state():
    m = _nemo()
    with DecodeEngine(m, slots=2, page=8, max_len=32,
                      cache_dtype=jnp.bfloat16) as eng:
        eng.generate(np.arange(1, 6, dtype=np.int32), 2)
        st = eng.stats()
        held = sum(a.nbytes for c in eng._caches for a in c.values()) // 2
    fixed = 2 * (4 * 8 * 8 * 4 + 3 * 64 * 2)
    assert st["state_bytes_per_slot"] == fixed
    assert st["cache_bytes_per_slot"] == fixed + 2 * st["cache_len"] * 8 * 2
    assert st["cache_bytes_per_slot"] == held
    # a model of keys and values alone has none of fixed size
    with DecodeEngine(_lm(), slots=2, page=8, max_len=32) as eng:
        eng.generate(np.arange(1, 6, dtype=np.int32), 2)
        assert eng.stats()["state_bytes_per_slot"] == 0


@pytest.mark.parametrize("make", ["ds", "nemo", "lm"])
def test_a_finished_request_carries_the_experts_its_tokens_chose(make):
    """An expert layer reports, beside its counts, the experts each
    position's router chose; the engine's two programs return them for
    every caller, and a finished request carries its own as
    `PendingRequest.routing`, `[expert layers, positions, k]` for positions
    0 .. len(result) - 2.  A model without routed experts reports nothing
    and its requests carry None."""
    from bigdl_tpu.parallel.expert import GatedMoE
    moe = GatedMoE(32, 16, 8, 2, n_group=2, topk_group=1, held=(0, 4))
    p, _ = moe.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (3, 1, 32))
    _y, (_counts, seen) = moe.decode_step(p, x, None, jnp.zeros(3, jnp.int32))
    assert seen.shape == (3, 1, 2) and seen.dtype == jnp.int32
    np.testing.assert_array_equal(
        seen.reshape(3, 2), moe.route(p, x.reshape(3, 32))[1])
    m = {"ds": _ds, "nemo": _nemo, "lm": _lm}[make]()
    prompt = np.arange(3, 14, dtype=np.int32)
    with DecodeEngine(m, slots=2, page=16, max_len=32) as eng:
        req = eng.submit(prompt, 5)
        row = req.result(120.0)
        report = eng._step_exe(eng._cache_len)(
            eng._params, eng._state, eng._fresh_caches(eng._cache_len),
            jnp.zeros(2, jnp.int32), jnp.full((2, 2), -1, jnp.int32))[3]
    if make == "lm":
        assert report is None and req.routing is None
        return
    experts, k = 1, 2               # each of the two has one expert layer
    assert report[1].shape == (experts, 2, k)
    assert req.routing.shape == (experts, len(row) - 1, k) \
        and req.routing.dtype == np.int32
    # ds: the expert layer follows the last attention layer, so the prefill
    # ran it on the prompt's last position alone; nemo's lies before its
    # last layer that keeps state, and saw the whole prompt
    given = (req.routing >= 0).all(-1)[0]
    assert given[10:].all()
    assert given[:10].all() == (make == "nemo") and not (
        make == "ds" and given[:10].any())
    # the choices are the router's on the full forward's input to the layer
    from bigdl_tpu.nn.containers import Sequential
    seen = []

    def walk(mod, pp, ss, h):
        if isinstance(mod, Sequential):
            for mm, p2, s2 in zip(mod.modules, pp, ss):
                h = walk(mm, p2, s2, h)
            return h
        if isinstance(mod, nn.ConcatTable):
            return [walk(mm, p2, s2, h)
                    for mm, p2, s2 in zip(mod.modules, pp, ss)]
        if isinstance(mod, GatedMoE):
            seen.append(mod.route(pp, h.reshape(-1, h.shape[-1]))[1])
        return mod.apply(pp, ss, h)[0]

    walk(m, m.params, m.state, jnp.asarray(row[None, :-1]))
    want = np.asarray(seen[0])
    np.testing.assert_array_equal(
        np.sort(req.routing[0][given], -1), np.sort(want[given], -1))


@pytest.mark.parametrize("make", ["ds", "nemo"])
def test_a_request_that_stops_at_its_eos_carries_its_own_tokens_routing(make):
    """The host sees an EOS a call late (ISSUE 40): by then the next step is
    on the device with the slot's row in it.  That row is nobody's: the
    result, `tokens_out` and the routing are those of the tokens the request
    received.  The experts' counts alone take it in: the device counts every
    row it is given a position for."""
    m = {"ds": _ds, "nemo": _nemo}[make]()
    prompt = np.arange(3, 14, dtype=np.int32)
    with DecodeEngine(m, slots=2, page=16, max_len=32) as eng:
        whole = eng.submit(prompt, 8)
        full = whole.result(120.0)
    gen = [int(t) for t in full[len(prompt):]]
    k = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
    assert k + 1 < 8

    def served(**kw):
        with DecodeEngine(m, slots=2, page=16, max_len=32) as eng:
            req = eng.submit(prompt, **kw)
            row = req.result(120.0)
        return req, row, eng.stats()    # stopped: nothing waits unread

    req, row, st = served(max_tokens=8, eos_token=gen[k])
    np.testing.assert_array_equal(row, full[: len(prompt) + k + 1])
    assert st["tokens_out"] == st["tokens_device_sampled"] == k + 1
    assert req.routing.shape[1] == len(row) - 1
    np.testing.assert_array_equal(req.routing,
                                  whole.routing[:, : len(row) - 1])
    # the same tokens by a budget: one step fewer was called
    _req, same, cut = served(max_tokens=k + 1)
    np.testing.assert_array_equal(same, row)
    assert st["decode_steps"] == cut["decode_steps"] + 1 == k + 1
    chose = lambda t: t["expert_tokens"] + t["expert_tokens_elsewhere"]
    assert chose(st) == chose(cut) + 2      # one expert layer, k = 2


def _tie_head(m):
    """The head's second half of the vocabulary made a copy of its first:
    every row of the output then holds each value twice, 32 indices apart,
    its largest too."""
    at = max(i for i, p in enumerate(m.params)
             if isinstance(p, dict) and "weight" in p
             and p["weight"].shape[0] == 64)
    head = {k: jnp.concatenate([a[:32], a[:32]])
            for k, a in m.params[at].items()}
    m.params = type(m.params)(
        head if i == at else p for i, p in enumerate(m.params))
    return m


@pytest.mark.parametrize("make", ["lm", "ds", "nemo"])
def test_both_programs_choose_the_first_of_the_largest_entries(make):
    """The engine's two programs return, beside the logits, the index of
    each row's largest entry as `np.argmax` gives it on the same values: the
    first among equals.  The rule is part of the result (bfloat16
    log-probabilities tie often), so the head here makes every row tie."""
    m = _tie_head({"lm": _lm, "ds": _ds, "nemo": _nemo}[make]())
    eng = DecodeEngine(m, slots=3, page=16, max_len=32)
    toks = np.zeros(8, np.int32)
    toks[:5] = [3, 9, 4, 7, 11]
    logits, tokens, caches, _report = eng._prefill_exe(1, 8, 16)(
        eng._params, eng._state, eng._fresh_caches(16),
        jnp.asarray([5, 40, 17], jnp.int32), jnp.asarray(toks)[None],
        np.array([1], np.int32), np.array([5], np.int32))
    row = np.asarray(logits)[0]
    assert tokens.shape == (3,) and tokens.dtype == jnp.int32
    assert (row == row.max()).sum() >= 2
    # the slots' tokens come back with this slot's row set, the others' kept
    np.testing.assert_array_equal(tokens, [5, np.argmax(row), 17])
    assert int(tokens[1]) < 32
    # the step takes the vector as it is: rows 0 (idle) and 1 the device's
    # own, row 2 the token the host chose (a request that samples)
    logits, tokens, caches, _report = eng._step_exe(16)(
        eng._params, eng._state, caches, tokens,
        jnp.asarray([[-1, 5, 0], [-1, -1, 17]], jnp.int32))
    rows = np.asarray(logits)
    assert tokens.shape == (3,) and tokens.dtype == jnp.int32
    assert ((rows == rows.max(-1, keepdims=True)).sum(-1) >= 2).all()
    np.testing.assert_array_equal(tokens, np.argmax(rows, -1))
    assert (np.asarray(tokens) < 32).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_greedy_rule_is_numpys_on_the_same_values(dtype):
    """Log-probabilities near -10.8 as bfloat16 holds them, 0.0625 apart:
    many entries share the largest value, and the lowest index wins, as
    `np.argmax` on the fetched row has it."""
    from bigdl_tpu.serve.decode import _with_tokens
    r = np.random.default_rng(4)
    rows = jnp.asarray(-10.8 + 0.03 * r.standard_normal((16, 4096)), dtype)
    out, got, _caches, _report = jax.jit(_with_tokens)(rows, (), None)
    got, host = np.asarray(got), np.asarray(out)
    np.testing.assert_array_equal(host, np.asarray(rows))
    np.testing.assert_array_equal(got, np.argmax(host, -1))
    assert got.dtype == np.int32
    if dtype == jnp.bfloat16:
        assert ((host == host.max(-1, keepdims=True)).sum(-1) > 1).all()


def test_layers_declare_their_decode_state():
    mha = nn.MultiHeadAttention(32, 4, causal=True)
    assert mha.decode_state(3, 16) == {
        "k": StateLeaf((3, 16, 32), 1, "kv_cache"),
        "v": StateLeaf((3, 16, 32), 1, "kv_cache")}
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
    ("lm", [(5, 9), (3, 12), (17, 6)]), ("ds", [(4, 7), (9, 5)]),
    ("nemo", [(5, 9), (13, 6), (3, 11)])])
def test_engine_tokens_are_bit_equal_to_cached_generate(model, prompts):
    """The merged walk against the oracle's own, for both state kinds
    (`cached_generate` steps a latent layer through its `decode_step`, all
    rows at one position; the engine prefills it in one pass and steps every
    slot at its own)."""
    m = {"lm": _lm, "ds": _ds, "nemo": _nemo}[model]()
    rows = [np.random.default_rng(100 + n).integers(1, 64, n).astype(np.int32)
            for n, _ in prompts]
    with DecodeEngine(m, slots=2, page=8, max_len=32) as eng:
        outs = [h.result(120.0) for h in
                [eng.submit(p, k) for p, (_, k) in zip(rows, prompts)]]
        st = eng.stats()
    for p, (_, k), out in zip(rows, prompts, outs):
        np.testing.assert_array_equal(out, cached_generate(m, p, k, 32))
    assert ("expert_tokens" in st) == (model in ("ds", "nemo"))


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
    y, (counts, _chosen) = moe.decode_prefill(p, x, None, 0, 7)
    assert int(counts.sum()) == 7 * 2
    np.testing.assert_allclose(y[:, :7], want[:, :7], atol=1e-6)
    # the last real position alone, as the walk hands it on after the last
    # layer that keeps leaves
    y1, (c1, _chosen) = moe.decode_prefill(p, x[:, 6:7], None, 0, 7)
    assert int(c1.sum()) == 2
    np.testing.assert_allclose(y1, want[:, 6:7], atol=1e-6)
    rows = x[0][:, None]                                   # [12, 1, 32]
    pos = jnp.asarray([3, -1, 0, 5, -1, -1, 2, 9, 1, -1, 4, 7])
    y, (counts, _chosen) = moe.decode_step(p, rows, None, pos)
    assert int(counts.sum()) == 8 * 2
    live = np.asarray(pos) >= 0
    np.testing.assert_allclose(y[live, 0], want[0][live], atol=1e-6)
    # nothing process-wide carries the mask any more
    import bigdl_tpu.parallel.expert as ep
    assert not hasattr(ep, "live_tokens") and not hasattr(kv, "live_tokens")


@pytest.mark.parametrize("make,axes", [(_lm, {"k": 1, "v": 1}),
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
                # the heads' axis (the last) over tp for {k, v}; a latent
                # has no head axis and every tp share holds it whole
                assert [i for i, ax in enumerate(spec) if ax == "tp"] == \
                    ([2] if n in "kv" else [])
    m = _ds()
    prompt = np.arange(1, 6, dtype=np.int32)
    want = cached_generate(m, prompt, 4, 16)
    with DecodeEngine(m, slots=2, page=16, max_len=16, mesh=mesh) as eng:
        np.testing.assert_array_equal(eng.generate(prompt, 4), want)


# ---------------------------------------------------------------------------
# ISSUE 30: the step writes each slot's new key and value rows in place
# ---------------------------------------------------------------------------

def _mha_step_inputs(length, dtype, seed=0):
    """A tiny layer, a cache full of a previous occupant's values, and five
    rows at their own positions: one in the middle, an idle one, one at
    position 0, one at the last position, one more."""
    mha = nn.MultiHeadAttention(32, 4, causal=True)
    params, _ = mha.init(jax.random.key(seed))
    ks = jax.random.split(jax.random.key(seed + 1), 3)
    x = jax.random.normal(ks[0], (5, 1, 32))
    shape = mha.decode_state(5, length)["k"].shape
    cache = {"k": jax.random.normal(ks[1], shape).astype(dtype),
             "v": jax.random.normal(ks[2], shape).astype(dtype)}
    pos = np.array([3, -1, 0, length - 1, length // 2], np.int32)
    return mha, params, x, cache, pos


@pytest.mark.parametrize("length,dtype", [
    (16, jnp.float32), (16, jnp.bfloat16), (200, jnp.float32),
    (256, jnp.bfloat16)])
def test_decode_step_writes_each_rows_own_position(length, dtype):
    """Against a reference that writes row by row: the state differs from
    the one handed in at position pos[s] of row s, all heads, and nowhere
    else, bit for bit; the output is the oracle's (`_cached_attention`, one
    row at its scalar position), bit for bit."""
    mha, params, x, cache, pos = _mha_step_inputs(length, dtype)
    y, new = mha.decode_step(params, x, cache, jnp.asarray(pos))
    H, D = mha.num_heads, mha.head_dim
    at = np.maximum(pos, 0)                      # an idle row: position 0
    as32 = lambda a: np.array(a.astype(jnp.float32))
    for n in "kv":
        proj = as32(mha._proj(params, x, n).astype(dtype))     # [S, 1, E]
        want = as32(cache[n])
        for s in range(5):
            want[s, at[s]] = proj[s, 0]
        got = as32(new[n])
        np.testing.assert_array_equal(got, want)
        # what changed: S x H rows of D, each at its row's own position
        # (a rounded value may happen to equal the one it replaces)
        changed = (got != as32(cache[n])).reshape(5, length, H, D)
        assert 0.9 * 5 * H * D < changed.sum() <= 5 * H * D
        assert (changed.any(axis=-1).sum(axis=1) == 1).all()   # [S, H]
    for s in range(5):
        row = {n: cache[n][s:s + 1] for n in "kv"}
        want_y, want_row = kv._cached_attention(mha, params, x[s:s + 1], row,
                                                int(at[s]))
        np.testing.assert_array_equal(np.asarray(y[s:s + 1]),
                                      np.asarray(want_y))
        for n in "kv":
            np.testing.assert_array_equal(as32(new[n][s:s + 1]),
                                          as32(want_row[n]))


@pytest.mark.parametrize("length,dtype", [(16, jnp.float32),
                                          (256, jnp.bfloat16)])
def test_stale_rows_past_a_slots_position_weigh_exactly_nothing(length,
                                                                dtype):
    """What a previous occupant left beyond `pos` changes no bit of the
    output, however large, and the step leaves it where it was."""
    mha, params, x, cache, pos = _mha_step_inputs(length, dtype, seed=3)
    beyond = (np.arange(length)[None, :]
              > np.maximum(pos, 0)[:, None])[:, :, None]       # [S, L, 1]
    clean = {n: jnp.where(beyond, 0, c) for n, c in cache.items()}
    loud = {n: jnp.where(beyond, 2.0 ** 15, c).astype(dtype)
            for n, c in cache.items()}
    y_clean, _ = mha.decode_step(params, x, clean, jnp.asarray(pos))
    y_loud, new = mha.decode_step(params, x, loud, jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(y_loud), np.asarray(y_clean))
    stale = np.broadcast_to(beyond, new["k"].shape)
    assert (np.asarray(new["k"].astype(jnp.float32))[stale] == 2.0 ** 15).all()


def _leaf_writers(model, slots=3, length=16):
    """For each leaf of the decode state the step returns: the equation of
    the step's jaxpr that produces it, and whether that equation takes the
    leaf handed in as its operand."""
    caches = kv.cache_avals(model, slots, length, jnp.float32)
    ivec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda c, tok, pos: kv._slot_step(model, model.params, model.state,
                                          tok, c, pos)[1])(caches, ivec,
                                                           ivec).jaxpr
    given = jaxpr.invars[:len(jax.tree.leaves(caches))]
    by_out = {v: e for e in jaxpr.eqns for v in e.outvars}
    return [(by_out[v], any(by_out[v].invars[0] is g for g in given))
            for v in jaxpr.outvars]


@pytest.mark.parametrize("make", [_lm, _ds])
def test_each_leaf_is_written_once_and_by_whole_minor_rows(make):
    """The structural guard: a leaf of the state comes straight out of one
    scatter whose operand is the donated leaf, and the axes the update
    spans all lie after the axes that place it (whole minor rows).  A window
    with the heads *before* the position, ``[1, H, 1, D]`` into ``[S, H, L,
    D]``, is what XLA expanded into a loop of S passes a leaf."""
    writers = _leaf_writers(make())
    assert len(writers) == 4
    for eqn, takes_leaf in writers:
        assert eqn.primitive.name == "scatter" and takes_leaf, eqn
        dn = eqn.params["dimension_numbers"]
        operand, updates = eqn.invars[0].aval, eqn.invars[2].aval
        window_axes = [a for a in range(operand.ndim)
                       if a not in dn.inserted_window_dims
                       and a not in dn.operand_batching_dims]
        spans = [a for a, d in zip(window_axes, dn.update_window_dims)
                 if updates.shape[d] > 1]
        places = set(range(operand.ndim)) - set(spans)
        assert spans and min(spans) > max(places), dn


@pytest.mark.parametrize("make", [_lm, _ds])
def test_donated_state_comes_back_in_its_own_buffers(make, recwarn):
    m = make()
    step = jax.jit(lambda p, s, c, tok, pos: kv._slot_step(m, p, s, tok, c,
                                                           pos),
                   donate_argnums=(2,))
    caches = tuple(init_kv_cache(m, 3, 16, jnp.float32))
    before = [a.unsafe_buffer_pointer() for a in jax.tree.leaves(caches)]
    tok = jnp.asarray([5, 6, 7], jnp.int32)
    _, new, _ = step(m.params, m.state, caches, tok,
                     jnp.asarray([2, -1, 15], jnp.int32))
    jax.block_until_ready(new)
    assert not [w for w in recwarn if "donated" in str(w.message)]
    assert all(a.is_deleted() for a in jax.tree.leaves(caches))
    assert [a.unsafe_buffer_pointer() for a in jax.tree.leaves(new)] == before


@pytest.mark.parametrize("shape3", [(2, 1, 2), (2, 2, 2), (1, 2, 4)])
def test_kv_cache_rides_the_mesh_heads_over_tp_and_grows_there(shape3):
    """The declaration's last axis is the heads side by side: `kv_cache`
    puts it over tp (whole heads a share) and the rows over data x fsdp,
    and a cache grown along its length stays where it was."""
    from jax.sharding import PartitionSpec as P
    lay = MeshLayout(*shape3)
    mesh = lay.build_mesh(jax.devices()[:int(np.prod(shape3))])
    leaf = nn.MultiHeadAttention(32, 4, causal=True).decode_state(4, 8)["k"]
    assert lay.spec_for(leaf.role, leaf.shape, min_size=0) == \
        P(("data", "fsdp"), None, "tp")
    m = _lm()
    caches = tuple(init_kv_cache(m, 4, 8, jnp.float32, mesh=mesh))
    marked = tuple({n: a + 1 for n, a in c.items()} for c in caches)
    grown = kv.grow_cache(m, marked, 32, mesh)
    for c in grown:
        for a in c.values():
            assert a.shape == (4, 32, 32)
            assert tuple(a.sharding.spec) == (("data", "fsdp"), None, "tp")
            assert {s.data.shape for s in a.addressable_shards} == {
                (4 // (shape3[0] * shape3[1]), 32, 32 // shape3[2])}
            old, new = np.split(np.asarray(a), [8], axis=1)
            assert (old == 1).all() and (new == 0).all()


def test_cached_generate_tokens_are_the_parents_on_a_fixed_seed():
    """Tokens the tree before ISSUE 30 gave for this seed (its
    `[rows, H, L, D]` leaves), greedy and beam, float32 and bfloat16 cache:
    the layout of the state is no part of the result."""
    from bigdl_tpu.models import beam_generate
    lm = TransformerLM(vocab_size=64, max_len=64, d_model=32, num_heads=4,
                       num_layers=2).build(jax.random.key(30))
    prompt = np.array([[5, 9, 33, 2, 17], [40, 1, 1, 62, 8]], np.int32)
    first = [5, 9, 33, 2, 17, 8, 63, 35, 53, 1, 22, 27, 19, 51, 14, 58, 56]
    assert cached_generate(lm, prompt, 12, 24).tolist() == [
        first, [40, 1, 1, 62, 8, 24, 62, 19, 56, 59, 10, 25, 32, 23, 42, 0,
                0]]
    assert cached_generate(lm, prompt[0], 12, 24,
                           cache_dtype=jnp.bfloat16).tolist() == first
    assert beam_generate(lm, prompt[0], 8, 24, beam_size=3).tolist() == [
        5, 9, 33, 2, 17, 22, 27, 19, 51, 14, 58, 56, 59]


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
