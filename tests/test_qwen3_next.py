"""Qwen3-Next on the serving path (ISSUE 41): gated delta-rule layers whose
matrix state has no length, grouped-query attention with normed, partly
rotated heads and an output gate, softmax-routed gated experts beside a
shared expert with a gate of its own, and one chip's share of a wider layer,
held to the benchmark's plain reference
(benchmark/reference/qwen3_next_share4.py, which imports nothing of the
program and runs the recurrence position by position) at a small size on the
CPU.

Float32 policy throughout, so the program and the reference differ by the
order of float32 sums only.  Tolerances: layer outputs and log-probabilities
here are O(1) and sums run over at most a few hundred terms, so 2e-4 absolute
is some hundred float32 roundings.  The same reference computed with fp8
operands (the benchmark's control) lies 0.05 or more away."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_next_share4 as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.models import Qwen3NextLM, cached_generate
from bigdl_tpu.nn import GatedDeltaNet, MultiHeadAttention, RMSNorm
from bigdl_tpu.parallel.expert import GatedMoE
from bigdl_tpu.serve import DecodeEngine

TOL = 2e-4


@pytest.fixture(autouse=True)
def _float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        yield
    set_policy(prior)


WHOLE = {"linear_num_key_heads": 4, "linear_num_value_heads": 8,
         "num_attention_heads": 8, "num_key_value_heads": 2}
#: one chip's quarter of every layer, as the benchmark's configuration cuts
SHARE = {"linear_num_key_heads": 1, "linear_num_value_heads": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "num_experts": 4}


def small_cfg(**over):
    """A whole small model: hidden 64; linear layers of 4 key heads of 8 and
    8 value heads of 16, 4 taps, chunks of 8; 8 query heads over 2
    key-value heads of 16, the first 4 of each rotated; 16 experts of width
    32, 3 a token, a shared expert of 32; 6 layers (linear x 3, full,
    linear x 2); vocabulary 211.  ``over`` cuts a share out of it."""
    cfg = {"vocab_size": 211, "hidden_size": 64, "num_hidden_layers": 6,
           "full_attention_interval": 4, "head_dim": 16,
           "partial_rotary_factor": 0.25, "rope_theta": 10000000,
           "linear_key_head_dim": 8, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "chunk_size": 8,
           "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32, "num_experts": 16,
           "held": {"first_expert": 0, "router_outputs": 16},
           "num_experts_per_tok": 3, "rms_norm_eps": 1e-6,
           "initializer_range": 0.2, "norm_weight_std": 0.1,
           "gdn_a_range": [0.01, 0.5], "param_dtype": "float32",
           "compute_dtype": "float32", "published": dict(WHOLE), **WHOLE}
    cfg.update(over)
    return cfg


def build(cfg):
    z = ref.sizes(cfg)
    w = z["whole"]
    return Qwen3NextLM(
        z["vocab"], z["hidden"], z["layers"], w["num_attention_heads"],
        w["num_key_value_heads"], z["head_dim"], w["linear_num_key_heads"],
        w["linear_num_value_heads"], z["dk"], z["dv"], z["expert"],
        z["shared"], z["routed"], z["k"], z["interval"],
        cfg["partial_rotary_factor"], z["theta"], z["taps"], z["chunk"],
        v_heads_held=z["v_heads"], k_heads_held=z["k_heads"],
        heads_held=z["heads"], kv_heads_held=z["kv_heads"],
        experts_held=z["held"], eps=z["eps"])


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


def _gdn(cfg):
    z = ref.sizes(cfg)
    w = z["whole"]
    layer = GatedDeltaNet(z["hidden"], w["linear_num_key_heads"],
                          w["linear_num_value_heads"], z["dk"], z["dv"],
                          z["taps"], z["chunk"], v_heads_held=z["v_heads"],
                          k_heads_held=z["k_heads"], eps=z["eps"])
    return z, layer, ref.init_params(cfg, jax.random.key(7))[1][1]


def _attn(cfg, **options):
    z = ref.sizes(cfg)
    options = options or dict(qk_norm=True, gated=True,
                              rope=(z["theta"], z["rotary"]))
    layer = MultiHeadAttention(z["hidden"], z["heads"], causal=True,
                               with_bias=False, num_kv_heads=z["kv_heads"],
                               head_dim=z["head_dim"], eps=z["eps"],
                               **options)
    return z, layer, ref.init_params(cfg, jax.random.key(7))[7][1]


def _moe(cfg):
    z = ref.sizes(cfg)
    layer = GatedMoE(z["hidden"], z["expert"], z["routed"], z["k"],
                     n_shared=1, held=z["held"], score="softmax",
                     renormalise=True, d_shared=z["shared"],
                     shared_gate=True)
    return z, layer, ref.init_params(cfg, jax.random.key(7))[2][1]


def _x(t, seed=11, rows=1):
    return jax.random.normal(jax.random.key(seed), (rows, t, 64))


# ------------------------------------------- (a) the chunked form, its state


@pytest.mark.parametrize("length", [1, 5, 8, 16, 19, 37])
def test_chunked_form_equals_the_position_by_position_recurrence(length):
    z, layer, p = _gdn(small_cfg())
    x = _x(length)
    got, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(got[0], ref.linear_attention(z, p, x[0], "f32"),
                               atol=TOL)


@pytest.mark.parametrize("length,real", [(16, 16), (16, 9), (32, 3), (8, 1),
                                         (24, 13)])
def test_a_prompts_pads_and_a_slots_stale_state_do_not_move_the_state(
        length, real):
    """A prompt of ``real`` tokens in a bucket of ``length`` enters a slot
    that holds another sequence's state: both leaves of the row are what
    the recurrence gives after ``real`` positions from nothing, the other
    row is untouched, and the steps that follow equal the reference's
    continuation."""
    z, layer, p = _gdn(small_cfg(**SHARE))
    x = _x(length + 4, seed=5)
    spec = layer.decode_state(2, 8)
    cache = {n: jax.random.normal(jax.random.key(i), leaf.shape)
             for i, (n, leaf) in enumerate(spec.items())}
    y, new = layer.decode_prefill(p, x[:, :length], cache, 1, real)
    want_y, (s, window) = ref.linear_attention(
        z, p, x[0, :real], "f32",
        (jnp.zeros(spec["ssm"].shape[1:]), jnp.zeros(spec["conv"].shape[1:])))
    np.testing.assert_allclose(y[0, :real], want_y, atol=TOL)
    np.testing.assert_allclose(new["ssm"][1], s, atol=TOL)
    np.testing.assert_allclose(new["conv"][1], window, atol=TOL)
    for n in cache:
        np.testing.assert_array_equal(new[n][0], cache[n][0])
    # four steps on from the prefilled row, the other row idle
    more = x[0, length:length + 4]
    want_more, _ = ref.linear_attention(z, p, more, "f32", (s, window))
    for t in range(4):
        step_x = jnp.stack([jnp.zeros(64), more[t]])[:, None]
        y, new = layer.decode_step(p, step_x, new, jnp.array([-1, real + t]))
        np.testing.assert_allclose(y[1, 0], want_more[t], atol=TOL)


def test_matrix_state_is_float32_whatever_the_caches_dtype():
    from bigdl_tpu.models import decode as kv
    model = build(small_cfg(**SHARE))
    avals = kv.cache_avals(model, 3, 16, jnp.bfloat16)
    kinds = [{n: (a.shape, a.dtype) for n, a in c.items()} for c in avals]
    assert kinds[0] == {"ssm": ((3, 2, 8, 16), jnp.float32),
                        "conv": ((3, 3, 2 * 8 + 2 * 16), jnp.bfloat16)}
    assert kinds[3] == {"k": ((3, 16, 16), jnp.bfloat16),
                        "v": ((3, 16, 16), jnp.bfloat16)}
    total, fixed = kv.state_bytes_per_row(model, 16, jnp.bfloat16)
    assert fixed == 5 * (2 * 8 * 16 * 4 + 3 * 48 * 2)
    assert total - fixed == 16 * 2 * 16 * 2


# ------------------------------------------------- (b) attention's options


def _plain_attention(p, x, z, qk_norm, gated, rope):
    """One option or several, written out: x [T, 64]."""
    t, d = x.shape[0], z["head_dim"]
    h, kv = z["heads"], z["kv_heads"]
    q = x @ p["wq"]
    gate = None
    if gated:
        q = q.reshape(t, h, 2 * d)
        q, gate = q[..., :d], q[..., d:]
    q = q.reshape(t, h, d)
    k = (x @ p["wk"]).reshape(t, kv, d)
    v = (x @ p["wv"]).reshape(t, kv, d)
    if qk_norm:
        q = ref.rms_norm(q, p["q_norm"], z["eps"])
        k = ref.rms_norm(k, p["k_norm"], z["eps"])
    if rope:
        q, k = (ref.rotate(a, jnp.arange(t), z["rotary"], z["theta"])
                for a in (q, k))
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    if gated:
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(t, h * d) @ p["wo"]


@pytest.mark.parametrize("qk_norm,gated,rope", [
    (True, False, False), (False, True, False), (False, False, True),
    (True, True, True)], ids=["qk_norm", "gated", "rope", "all"])
def test_each_attention_option_against_the_written_out_form(qk_norm, gated,
                                                            rope):
    """The full-sequence form, and a prefill of 9 real positions in a
    bucket of 16 then 4 steps through the cache, each row at its own
    position."""
    cfg = small_cfg()
    z = ref.sizes(cfg)
    options = dict(qk_norm=qk_norm, gated=gated,
                   rope=(z["theta"], z["rotary"]) if rope else None)
    _, layer, whole = _attn(cfg, **options)
    p = {n: whole[n] for n in ("wk", "wo", "wv")}
    p["wq"] = whole["wq"] if gated else whole["wq"][:, :8 * 16]
    if qk_norm:
        p.update(q_norm=whole["q_norm"], k_norm=whole["k_norm"])
    assert jax.tree.map(jnp.shape, layer._init(jax.random.key(0))) == \
        jax.tree.map(jnp.shape, p)
    x = _x(20, seed=2)
    want = _plain_attention(p, x[0], z, qk_norm, gated, rope)
    got, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(got[0], want, atol=TOL)
    cache = {n: jnp.full((2, 24, 2 * 16), 7.0) for n in "kv"}
    y, cache = layer.decode_prefill(p, x[:, :16], cache, 1, 9)
    np.testing.assert_allclose(y[0, :9], _plain_attention(
        p, x[0, :9], z, qk_norm, gated, rope), atol=TOL)
    seq = x[0, :9]
    for t in range(4):
        new = x[0, 16 + t]
        seq = jnp.concatenate([seq, new[None]])
        y, cache = layer.decode_step(
            p, jnp.stack([jnp.ones(64), new])[:, None], cache,
            jnp.array([-1, 9 + t]))
        np.testing.assert_allclose(y[1, 0], _plain_attention(
            p, seq, z, qk_norm, gated, rope)[-1], atol=TOL)


def test_the_full_attention_layer_against_the_reference():
    z, layer, p = _attn(small_cfg())
    x = _x(23, seed=4)
    got, _ = layer.apply(p, {}, x)
    np.testing.assert_allclose(got[0], ref.attention(z, p, x[0], "f32"),
                               atol=TOL)


#: sha256 (16 hex digits) of ``str(jax.make_jaxpr(...))`` of the default
#: layers' programs on the parent commit 5ff0caa (jax 0.9.0, float32
#: policy, matmul precision "highest" as this file's fixture sets it): the
#: options this PR adds leave them as they were (``mha_prefill`` is PR 42's:
#: a prefill takes a slot a row, ``[1]`` here where the parent's took a
#: scalar; a lone row's write is the parent's dynamic-update-slice)
PARENT_JAXPRS = {"mha_apply": "39c8efd041ce5c13",
                 "mha_prefill": "f45dd2f0f63c7160",
                 "mha_step": "eee4ce298f101713", "rms": "ee212ca3d95968f2",
                 "moe": "b8f19d60c3022956"}


def test_the_default_layers_trees_and_programs_are_what_they_were():
    def digest(fn, *a):
        return hashlib.sha256(
            str(jax.make_jaxpr(fn)(*a)).encode()).hexdigest()[:16]

    m = MultiHeadAttention(32, 4, causal=True, num_kv_heads=2, head_dim=16)
    p = m._init(jax.random.key(0))
    assert sorted(p) == ["bk", "bo", "bq", "bv", "wk", "wo", "wq", "wv"]
    x = jnp.ones((2, 8, 32))
    cache = {"k": jnp.zeros((3, 16, 32)), "v": jnp.zeros((3, 16, 32))}
    got = {
        "mha_apply": digest(lambda p, x: m._apply(p, x), p, x),
        "mha_prefill": digest(
            lambda p, x, c: m.decode_prefill(p, x[:1], c, 1, 5), p, x, cache),
        "mha_step": digest(
            lambda p, x, c: m.decode_step(p, jnp.ones((3, 1, 32)), c,
                                          jnp.array([0, 3, -1])),
            p, x, cache)}
    r = RMSNorm(32)
    pr = r._init(jax.random.key(0))
    assert float(pr["weight"].sum()) == 32.0
    got["rms"] = digest(lambda p, x: r._apply(p, x), pr, x)
    g = GatedMoE(32, 16, 8, 2, n_shared=1, held=(2, 4))
    pg = g._init(jax.random.key(0))
    assert sorted(pg) == ["gate", "shared_down", "shared_gate", "shared_up",
                          "w_down", "w_gate", "w_up"]
    assert float(sum(jnp.abs(v).sum() for v in jax.tree.leaves(pg))) == \
        pytest.approx(1251.7808837890625, rel=1e-6)
    got["moe"] = digest(lambda p, x: g._forward(p, x)[0], pg, x)
    assert got == PARENT_JAXPRS


def test_the_zero_centred_norm_and_the_shared_gate_against_the_reference():
    norm = RMSNorm(64, 1e-6, plus_one=True)
    assert float(jnp.abs(norm._init(jax.random.key(0))["weight"]).sum()) == 0
    w = 0.3 * jax.random.normal(jax.random.key(1), (64,))
    x = _x(5)
    got, _ = norm.apply({"weight": w}, {}, x)
    np.testing.assert_allclose(got, ref.rms_norm(x, w, 1e-6), atol=1e-6)
    assert float(jnp.abs(got - RMSNorm(64, 1e-6).apply(
        {"weight": w}, {}, x)[0]).max()) > 0.5
    z, layer, p = _moe(small_cfg())
    got, _ = layer.apply(p, layer._init_state(), x)
    want = ref.moe(z, p, x[0], "f32")
    np.testing.assert_allclose(got[0], want, atol=TOL)
    # without the gate the shared expert weighs 1: another layer
    ungated = dict(p, shared_score=jnp.full_like(p["shared_score"], 1e4 / 64)
                   * jnp.sign(x[0, 0])[:, None])
    assert float(jnp.abs(ref.moe(z, ungated, x[0], "f32") - want).max()) > 0.05


# ------------------------------------------------ (c) four shares, one layer


def _linear_share(p, j, dk=8, dv=16, hk=4, hv=8):
    """Share ``j`` of four of a whole linear layer's parameters: key head
    ``j`` with value heads ``2j, 2j + 1``."""
    q0, k0, v0, z0 = 0, hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv
    key = np.arange(j * dk, (j + 1) * dk)
    val = np.arange(2 * j * dv, (2 * j + 2) * dv)
    cols = np.concatenate([q0 + key, k0 + key, v0 + val])
    heads = np.arange(2 * j, 2 * j + 2)
    return {"A_log": p["A_log"][heads], "dt_bias": p["dt_bias"][heads],
            "conv_weight": p["conv_weight"][:, cols],
            "in_ba": p["in_ba"][:, np.concatenate([heads, hv + heads])],
            "in_qkvz": p["in_qkvz"][:, np.concatenate([cols, z0 + val])],
            "norm": p["norm"], "out_proj": p["out_proj"][val]}


def _attention_share(p, j, d=16):
    """Share ``j`` of four: query heads ``2j, 2j + 1`` with their gates and
    key-value head ``j // 2``, which two shares hold alike."""
    q = np.arange(2 * j * 2 * d, (2 * j + 2) * 2 * d)
    kv = np.arange((j // 2) * d, (j // 2 + 1) * d)
    return {"q_norm": p["q_norm"], "k_norm": p["k_norm"],
            "wq": p["wq"][:, q], "wk": p["wk"][:, kv], "wv": p["wv"][:, kv],
            "wo": p["wo"][np.arange(2 * j * d, (2 * j + 2) * d)]}


@pytest.mark.parametrize("kind", ["linear", "full", "experts"])
def test_four_shares_add_up_to_the_whole_layer(kind):
    """The uncut reference's layer against the sum of what the program's
    four shares give; the shared expert under its gate, which every share
    computes alike, counted once (the router chooses in all four and adds
    nothing of its own)."""
    whole_cfg, share_cfg = small_cfg(), small_cfg(**SHARE)
    x = _x(13, seed=9)
    total = 0.0
    if kind == "linear":
        z, _, whole = _gdn(whole_cfg)
        want = ref.linear_attention(z, whole, x[0], "f32")
        _, layer, _ = _gdn(share_cfg)
        parts = [_linear_share(whole, j) for j in range(4)]
    elif kind == "full":
        z, _, whole = _attn(whole_cfg)
        want = ref.attention(z, whole, x[0], "f32")
        _, layer, _ = _attn(share_cfg)
        parts = [_attention_share(whole, j) for j in range(4)]
    else:
        z, _, whole = _moe(whole_cfg)
        want = ref.moe(z, whole, x[0], "f32")
    for j in range(4):
        if kind == "experts":
            _, layer, _ = _moe(dict(share_cfg, held={
                "first_expert": 4 * j, "router_outputs": 16}))
            e = slice(4 * j, 4 * j + 4)
            part = dict(whole, w_gate=whole["w_gate"][e],
                        w_up=whole["w_up"][e], w_down=whole["w_down"][e])
            y, _ = layer.apply(part, layer._init_state(), x)
        else:
            y, _ = layer.apply(parts[j], {}, x)
        total = total + y[0]
    if kind == "experts":       # four shares added the shared expert: once
        none = dict(whole, **{n: whole[n][:0]
                              for n in ("w_gate", "w_up", "w_down")})
        total = total - 3 * ref.moe(z, none, x[0], "f32", held=(0, 0))
    np.testing.assert_allclose(total, want, atol=2 * TOL)


# ------------------------------------------------------------ (d) the model


@pytest.mark.parametrize("share", [{}, SHARE], ids=["whole", "share"])
def test_model_against_the_reference_on_seeded_weights(share):
    cfg = small_cfg(**share)
    model, params, state, p0 = seeded(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 21), 0, 211)
    got, _ = model.apply(params, state, toks)
    want = jax.nn.log_softmax(ref.logits(cfg, p0, toks), axis=-1)
    np.testing.assert_allclose(got, want, atol=TOL)
    # the control one precision down is far outside the tolerance
    low = jax.nn.log_softmax(ref.logits(cfg, p0, toks, "fp8"), axis=-1)
    assert float(jnp.abs(low - want).max()) > 0.05


# ----------------------------------- (e) prefill and steps in the engine


def _served(cfg, prompts, slots=2, page=16, first_in_flight=False, **kw):
    import time
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
    return model, p0, outs, st, hs


def _prompts(lengths, seed=50):
    return [(np.random.default_rng(seed + i).integers(1, 211, n)
             .astype(np.int32), k) for i, (n, k) in enumerate(lengths)]


def test_prefill_steps_and_a_pages_growth_equal_the_full_forward():
    """Through `DecodeEngine`: prompts of 4, 5, 11 and 19 tokens land in
    buckets of 8, 16 and 32, so the prefill computes pads, and the 16-long
    cache the first request opens grows by a page under it, once it is in
    flight, when the second needs 24 positions (its matrix state carried
    over bit for bit); every served token is the reference's greedy one for
    the sequence so far (logits compared, not tokens), and the tokens are
    `cached_generate`'s, which walks position by position with no
    prefill."""
    cfg = small_cfg(**SHARE)
    prompts = _prompts([(4, 11), (5, 19), (11, 9), (19, 7)])
    model, p0, outs, st, hs = _served(cfg, prompts, first_in_flight=True,
                                      min_step_s=0.01)
    assert st["cache_grows"] >= 1 and st["cache_len"] >= 32
    fixed = 5 * (2 * 8 * 16 * 4 + 3 * 48 * 4)
    assert st["state_bytes_per_slot"] == fixed
    assert st["state_bytes_fixed"] == 2 * fixed
    assert st["state_bytes_per_position"] == 2 * 16 * 4
    assert st["cache_bytes_per_slot"] == fixed \
        + st["cache_len"] * st["state_bytes_per_position"]
    # 4 of 16 experts held, 3 choices a token in each of 6 layers: what was
    # counted here and elsewhere is every choice of every real token
    positions = sum(len(o) - 1 for o in outs)
    assert st["expert_tokens"] + st["expert_tokens_elsewhere"] == \
        6 * 3 * positions - 3 * sum(len(p) - 1 for p, _k in prompts)
    assert 0 < st["expert_tokens"] < st["expert_tokens_elsewhere"]
    assert st["expert_tokens_max"] * 4 >= st["expert_tokens"]
    for (p, k), out, h in zip(prompts, outs, hs):
        assert len(out) == len(p) + k
        assert h.routing.shape == (6, len(out) - 1, 3)
        lg = ref.logits(cfg, p0, jnp.asarray(out)[None])[0]
        at = np.arange(len(p) - 1, len(out) - 1)
        gap = lg[at].max(-1) - lg[at, out[len(p):]]
        assert float(gap.max()) < 1e-3, (len(p), gap)
        np.testing.assert_array_equal(out, cached_generate(model, p, k, 64))


def test_a_slots_second_occupant_gets_a_fresh_engines_tokens():
    """One slot, three requests in turn: the second and third enter a slot
    whose matrix state, convolution window, keys and values the one before
    left behind; each gets bit-equal tokens to the same request in a fresh
    engine."""
    cfg = small_cfg(**SHARE)
    prompts = _prompts([(13, 8), (6, 10), (17, 5)], seed=70)
    _, _, outs, _, _ = _served(cfg, prompts, slots=1)
    for pr, out in zip(prompts, outs):
        _, _, (alone,), _, _ = _served(cfg, [pr], slots=1)
        np.testing.assert_array_equal(out, alone)


def test_counters_and_the_report_carry_both_kinds_of_state():
    from bigdl_tpu.utils import telemetry
    from bigdl_tpu.utils.telemetry import Tracer
    cfg = small_cfg(**SHARE)
    tr = Tracer("memory://qwen3n_spans", flush_every=0)
    telemetry.set_active(tr)
    try:
        _, _, _, st, _ = _served(cfg, _prompts([(5, 3)]))
    finally:
        telemetry.set_active(None)
    events = tr.events_tail(4096)
    track = [e for e in events
             if e.get("ph") == "C" and e.get("name") == "serve.decode"]
    assert track
    for key in ("state_bytes_fixed", "state_bytes_per_position"):
        assert track[-1]["args"][key] == st[key] > 0
    report = telemetry.format_report(
        telemetry.phase_breakdown({"traceEvents": events}))
    assert "state_bytes_fixed=" in report
    assert "state_bytes_per_position=" in report
