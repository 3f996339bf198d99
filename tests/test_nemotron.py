"""Nemotron-H on the serving path (ISSUE 32): Mamba-2 layers whose state has
no length, grouped-query attention without positions, sigmoid-routed plain
experts with a selection bias, and one chip's share of a wider layer, held to
the benchmark's plain reference (benchmark/reference/nemotron3_nano_share2.py,
which imports nothing of the program and runs the recurrence position by
position) at a small size on the CPU.

Float32 policy throughout, so the program and the reference differ by the
order of float32 sums only.  Tolerances: layer outputs and log-probabilities
here are O(1) and sums run over at most a few hundred terms, so 2e-4 absolute
is some hundred float32 roundings.  The same reference computed with fp8
operands (the benchmark's control) lies 0.05 or more away."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron3_nano_share2 as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.models import cached_generate
from bigdl_tpu.models.nemotron import NemotronHLM
from bigdl_tpu.nn import Mamba2Mixer, MultiHeadAttention
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


def small_cfg(**over):
    """A whole small layer of each kind: hidden 64; 8 Mamba heads of 8 in 4
    groups of state 16, 4 taps, chunks of 8; 8 query heads over 2 key-value
    heads of 16; 16 experts of width 32, 3 a token, a shared expert of 64;
    pattern MEM*E; vocabulary 211.  ``over`` cuts a share out of it."""
    cfg = {"vocab_size": 211, "hidden_size": 64,
           "hybrid_override_pattern": "MEM*E", "mamba_num_heads": 8,
           "mamba_head_dim": 8, "n_groups": 4, "ssm_state_size": 16,
           "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 8,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 32,
           "moe_shared_expert_intermediate_size": 64, "n_routed_experts": 16,
           "held": {"first_expert": 0, "router_outputs": 16},
           "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1,
           "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
           "time_step_min": 0.001, "time_step_max": 0.1,
           "time_step_floor": 1e-4, "initializer_range": 0.2,
           "select_bias_std": 0.05, "param_dtype": "float32",
           "compute_dtype": "float32",
           "published": {"mamba_num_heads": 8, "n_groups": 4,
                         "num_attention_heads": 8, "num_key_value_heads": 2}}
    cfg.update(over)
    return cfg


SHARE = dict(mamba_num_heads=4, n_groups=2, num_attention_heads=4,
             num_key_value_heads=1, n_routed_experts=8)


def build(cfg):
    from benchmark.configs import nemotron3_nano_share2 as cm
    return cm.build_model(cfg)


def seeded(cfg, seed=3):
    """The reference's seeded weights, laid out as the program's tree."""
    p0 = ref.init_params(cfg, jax.random.key(seed))
    model = build(cfg)
    shapes, _ = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree.leaves(p0)
    assert [x.shape for x in leaves] == \
        [s.shape for s in jax.tree.leaves(shapes)]
    params = jax.tree.unflatten(jax.tree.structure(shapes), leaves)
    _, state = model.init(jax.random.key(0))
    return model, params, state, p0


def mamba_layer(cfg, seed=5):
    z = ref.sizes(cfg)
    p = ref.init_params(dict(cfg, hybrid_override_pattern="M"),
                        jax.random.key(seed))[1][1]
    w = z["whole"]
    m = Mamba2Mixer(z["hidden"], w["mamba_num_heads"], z["m_dim"],
                    w["n_groups"], z["state"], z["taps"], z["chunk"],
                    heads_held=z["m_heads"], eps=z["eps"])
    return z, p, m


# ------------------------------------------------------------- (a) the scan


@pytest.mark.parametrize("length", [1, 5, 8, 16, 19, 37])
def test_chunked_scan_equals_the_position_by_position_recurrence(length):
    """`Mamba2Mixer._apply` (chunks of 8) against the reference's loop over
    positions, for lengths that are and are not multiples of the chunk."""
    z, p, m = mamba_layer(small_cfg())
    u = jax.random.normal(jax.random.key(length), (2, length, 64))
    got = m._apply(p, u)
    want = jnp.stack([ref.mamba(z, p, row, "f32") for row in u])
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("length,real", [(16, 16), (16, 9), (32, 3), (8, 1)])
def test_a_prompts_pads_do_not_move_the_state(length, real):
    """`decode_prefill` of a padded bucket leaves in row `slot` the state
    after the last *real* position, whatever the slot held and whatever the
    pads are, and then a step continues the recurrence."""
    z, p, m = mamba_layer(small_cfg())
    u = jax.random.normal(jax.random.key(7), (1, length + 1, 64))
    pads = u.at[:, real:length].set(99.0)[:, :length]      # loud pads
    spec = m.decode_state(3, 64)
    assert all(leaf.length_axis is None for leaf in spec.values())
    cache = {n: jnp.full(leaf.shape, 7.0, leaf.dtype or jnp.float32)
             for n, leaf in spec.items()}                  # a stale occupant
    y, cache = m.decode_prefill(p, pads, cache, 1, real)
    want, (s, window) = ref.mamba(
        z, p, u[0, :real], "f32",
        state=(jnp.zeros((8, 8, 16)), jnp.zeros((3, ref.conv_dim(z)))))
    np.testing.assert_allclose(y[0, :real], want, atol=TOL)
    np.testing.assert_allclose(cache["ssm"][1], s, atol=TOL)
    np.testing.assert_allclose(cache["conv"][1], window, atol=TOL)
    assert float(jnp.abs(cache["ssm"][0] - 7.0).max()) == 0.0   # others' rows
    # one step on: position `real` of the unpadded sequence
    nxt = jnp.broadcast_to(u[:, real], (3, 64))[:, None]
    y1, cache = m.decode_step(p, nxt, cache, jnp.asarray([-1, real, -1]))
    want1, _ = ref.mamba(z, p, u[0, real:real + 1], "f32", state=(s, window))
    np.testing.assert_allclose(y1[1, 0], want1[0], atol=TOL)


def test_mamba_state_is_float32_whatever_the_caches_dtype():
    from bigdl_tpu.models import decode as kv
    model = NemotronHLM(64, 32, "M*", 4, 8, 2, 16, 4, 8, 4, 1, 8, 16, 32, 8,
                        2).build(jax.random.key(0))
    avals = kv.cache_avals(model, 3, 16, jnp.bfloat16)
    assert avals[0]["ssm"].dtype == jnp.float32
    assert avals[0]["ssm"].shape == (3, 4, 8, 16)
    assert avals[0]["conv"].dtype == jnp.bfloat16
    assert avals[0]["conv"].shape == (3, 3, 4 * 8 + 2 * 2 * 16)
    assert avals[1]["k"].shape == (3, 16, 8) and \
        avals[1]["k"].dtype == jnp.bfloat16
    total, fixed = kv.state_bytes_per_row(model, 16, jnp.bfloat16)
    assert fixed == 4 * 8 * 16 * 4 + 3 * 96 * 2
    assert total == fixed + 2 * 16 * 8 * 2


# ---------------------------------------------- (e) grouped-query attention


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1), (4, 4)])
def test_grouped_query_attention_against_the_plain_form(heads, kv_heads):
    cfg = small_cfg(num_attention_heads=heads, num_key_value_heads=kv_heads)
    z = ref.sizes(cfg)
    p = ref.init_params(dict(cfg, hybrid_override_pattern="*"),
                        jax.random.key(2))[1][1]
    mha = MultiHeadAttention(64, heads, causal=True, with_bias=False,
                             num_kv_heads=kv_heads, head_dim=16)
    shapes, _ = jax.eval_shape(mha.init, jax.random.key(0))
    assert {k: v.shape for k, v in shapes.items()} == \
        {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.key(3), (2, 12, 64))
    want = jnp.stack([ref.attention(z, p, row, "f32") for row in x])
    np.testing.assert_allclose(mha._apply(p, x), want, atol=TOL)
    # prefill of a padded prompt, then a step a row at its own position
    assert mha.decode_state(2, 16)["k"].shape == (2, 16, kv_heads * 16)
    cache = {n: jnp.zeros((2, 16, kv_heads * 16)) for n in "kv"}
    y, cache = mha.decode_prefill(p, x[:1, :8], cache, 1, 8)
    np.testing.assert_allclose(y, want[:1, :8], atol=TOL)
    y1, cache = mha.decode_step(p, x[:, 8:9], cache, jnp.asarray([-1, 8]))
    np.testing.assert_allclose(y1[1, 0], jnp.stack(
        [ref.attention(z, p, jnp.concatenate([x[0, :8], x[1, 8:9]]),
                       "f32")])[0, 8], atol=TOL)


def test_default_attention_builds_the_plain_tree():
    """With the defaults the class is what it was: names, shapes and the
    declared state of as many key heads as query heads."""
    mha = MultiHeadAttention(32, 4, causal=True)
    shapes, _ = jax.eval_shape(mha.init, jax.random.key(0))
    assert {k: v.shape for k, v in shapes.items()} == {
        "wq": (32, 32), "wk": (32, 32), "wv": (32, 32), "wo": (32, 32),
        "bq": (32,), "bk": (32,), "bv": (32,), "bo": (32,)}
    assert mha.decode_state(2, 8)["k"].shape == (2, 8, 32)
    with pytest.raises(ValueError):
        MultiHeadAttention(32, 4, num_kv_heads=3)


def test_grouped_kv_cache_rides_tp_on_its_width():
    from bigdl_tpu.parallel.layout import MeshLayout
    lay = MeshLayout(data=2, fsdp=1, tp=2)
    mha = MultiHeadAttention(64, 8, causal=True, num_kv_heads=2, head_dim=16)
    leaf = mha.decode_state(4, 32)["k"]
    spec = lay.spec_for(leaf.role, leaf.shape, min_size=0)
    assert tuple(spec) == (("data", "fsdp"), None, "tp")
    m = Mamba2Mixer(64, 8, 8, 4, 16)
    ssm, conv = (m.decode_state(4, 32)[n] for n in ("ssm", "conv"))
    assert tuple(lay.spec_for(ssm.role, ssm.shape, min_size=0)) == \
        (("data", "fsdp"), "tp", None, None)
    assert tuple(lay.spec_for(conv.role, conv.shape, min_size=0)) == \
        (("data", "fsdp"), None, None)


# ------------------------------------------------------------ (f) the router


def _moe(cfg, **kw):
    z = ref.sizes(cfg)
    return z, GatedMoE(z["hidden"], z["expert"], z["routed"], z["k"],
                       n_shared=1, scale=z["scale"], held=z["held"],
                       score="sigmoid", select_bias=True, renormalise=True,
                       gated=False, act="relu2", d_shared=z["shared"], **kw)


def test_the_selection_bias_chooses_and_does_not_weigh():
    cfg = small_cfg()
    z, moe = _moe(cfg)
    p = ref.init_params(dict(cfg, hybrid_override_pattern="E"),
                        jax.random.key(4))[1][1]
    x = jax.random.normal(jax.random.key(5), (40, 64))
    w, idx = moe.route(p, x)
    np.testing.assert_allclose(w.sum(-1), 2.5, atol=1e-5)
    w0, idx0 = moe.route(dict(p, select_bias=jnp.zeros(16)), x)
    changed = np.asarray(jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1)
    assert 0 < changed.sum() < 40            # the bias changes some choices
    # and no weight: a token whose choice it leaves alone has the same ones
    np.testing.assert_allclose(jnp.sort(w, -1)[~changed],
                               jnp.sort(w0, -1)[~changed], atol=1e-6)
    # the weights are the chosen sigmoid scores, renormalised, not s + b
    s = jax.nn.sigmoid(ref.router_logits(p["gate"], x))
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)
    # the reference routes alike
    dense = ref.routing(z, p, x)
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, idx, -1), w, atol=1e-6)


def test_plain_experts_against_the_reference_and_two_products():
    cfg = small_cfg()
    z, moe = _moe(cfg)
    p = ref.init_params(dict(cfg, hybrid_override_pattern="E"),
                        jax.random.key(4))[1][1]
    shapes, _ = jax.eval_shape(moe.init, jax.random.key(0))
    assert sorted(shapes) == ["gate", "select_bias", "shared_down",
                              "shared_up", "w_down", "w_up"]
    x = jax.random.normal(jax.random.key(6), (2, 9, 64))
    y, st = moe.apply(p, moe._init_state(), x)
    want = jnp.stack([ref.moe(z, p, row, "f32") for row in x])
    np.testing.assert_allclose(y, want, atol=TOL)
    assert int(st["expert_tokens"].sum()) == 18 * 3
    jaxpr = str(jax.make_jaxpr(lambda p, x: moe._forward(p, x)[0])(p, x))
    assert jaxpr.count("= ragged_dot") == 2         # up and down, no gate


def test_the_softmax_gated_layer_is_what_it_was():
    """`GatedMoE`'s defaults build DeepSeek-V2's layer: the same names, and
    the softmax, unrenormalised, gated arithmetic."""
    moe = GatedMoE(32, 16, 8, 2, n_group=2, topk_group=1, n_shared=2)
    shapes, _ = jax.eval_shape(moe.init, jax.random.key(0))
    assert {k: v.shape for k, v in shapes.items()} == {
        "gate": (32, 8), "w_gate": (8, 32, 16), "w_up": (8, 32, 16),
        "w_down": (8, 16, 32), "shared_gate": (32, 32),
        "shared_up": (32, 32), "shared_down": (32, 32)}
    p, _ = moe.init(jax.random.key(1))
    w, _ = moe.route(p, jax.random.normal(jax.random.key(2), (5, 32)))
    assert float(w.sum(-1).max()) < 1.0       # softmax scores, not renormed


# --------------------------------------------------------- (g) the share test


def _halves(cfg, kind):
    """One whole layer's reference weights and the two shares' slices."""
    whole = ref.init_params(dict(cfg, hybrid_override_pattern=kind),
                            jax.random.key(9))[1][1]
    z = ref.sizes(cfg)
    if kind == "M":
        inner, g, n = 64, 4, 16
        cols = lambda h0, g0: np.concatenate([
            np.arange(h0 * 8, h0 * 8 + 32),                      # z
            inner + np.arange(h0 * 8, h0 * 8 + 32),              # x
            2 * inner + np.arange(g0 * n, (g0 + 2) * n),         # B
            2 * inner + g * n + np.arange(g0 * n, (g0 + 2) * n),  # C
            2 * inner + 2 * g * n + np.arange(h0, h0 + 4)])      # dt
        chans = lambda h0, g0: cols(h0, g0)[32:-4] - inner
        part = lambda h0, g0: {
            "A_log": whole["A_log"][h0:h0 + 4], "D": whole["D"][h0:h0 + 4],
            "conv_bias": whole["conv_bias"][chans(h0, g0)],
            "conv_weight": whole["conv_weight"][:, chans(h0, g0)],
            "dt_bias": whole["dt_bias"][h0:h0 + 4],
            "in_proj": whole["in_proj"][:, cols(h0, g0)],
            "norm": whole["norm"][h0 * 8:h0 * 8 + 32],
            "out_proj": whole["out_proj"][h0 * 8:h0 * 8 + 32]}
        return z, whole, [part(0, 0), part(4, 2)]
    if kind == "*":
        q = lambda h0: slice(h0 * 16, h0 * 16 + 64)
        kv = lambda j: slice(j * 16, j * 16 + 16)
        part = lambda h0, j: {"wq": whole["wq"][:, q(h0)],
                              "wk": whole["wk"][:, kv(j)],
                              "wv": whole["wv"][:, kv(j)],
                              "wo": whole["wo"][q(h0)]}
        return z, whole, [part(0, 0), part(4, 1)]
    part = lambda e0: dict(whole, w_up=whole["w_up"][e0:e0 + 8],  # rows
                           w_down=whole["w_down"][e0:e0 + 8])
    return z, whole, [part(0), part(8)]


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_two_shares_add_up_to_the_whole_layer(kind):
    """The guide's share test: the program's two shares of one layer, with
    what both chips compute alike (the shared expert) counted once, add up
    to what the uncut reference gives for the whole layer."""
    cfg = small_cfg()
    z, whole, parts = _halves(cfg, kind)
    x = jax.random.normal(jax.random.key(11), (1, 13, 64))
    want = ref.mixer(z, whole, x[0], "f32")
    if kind == "M":
        layers = [Mamba2Mixer(64, 8, 8, 4, 16, 4, 8, heads_held=4)] * 2
    elif kind == "*":
        layers = [MultiHeadAttention(64, 4, causal=True, with_bias=False,
                                     num_kv_heads=1, head_dim=16)] * 2
    else:
        layers = [_moe(dict(cfg, n_routed_experts=8, held={
            "first_expert": e0, "router_outputs": 16}))[1] for e0 in (0, 8)]
    total = 0.0
    for layer, p in zip(layers, parts):
        y, _ = layer.apply(p, layer._init_state() if kind == "E" else {}, x)
        total = total + y[0]
    if kind == "E":         # both shares added the shared expert: once
        total = total - ref.plain_mlp(x[0], whole["shared_up"],
                                      whole["shared_down"], "f32")
    np.testing.assert_allclose(total, want, atol=2 * TOL)


# ------------------------------------------------------------ (h) the model


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


# ----------------------------------- (b) (c) prefill and steps in the engine


def _served(cfg, prompts, slots=2, **kw):
    model, params, state, p0 = seeded(cfg)
    model.attach(params, state)
    with DecodeEngine(model, slots=slots, page=16, max_len=64, **kw) as eng:
        outs = [h.result(300.0) for h in
                [eng.submit(p, k) for p, k in prompts]]
        st = eng.stats()
    return model, p0, outs, st


def _prompts(lengths, seed=50):
    return [(np.random.default_rng(seed + i).integers(1, 211, n)
             .astype(np.int32), k) for i, (n, k) in enumerate(lengths)]


def test_prefill_then_steps_equal_the_full_forward_with_pads_in_the_bucket():
    """Through `DecodeEngine`: prompts of 5, 11 and 19 tokens land in
    buckets of 8, 16 and 32, so the prefill computes pads; every served
    token is the reference's greedy one for the sequence so far (logits
    compared, not tokens: the served token's reference logit lies within
    rounding of the reference's best).  Fails if a pad moves the state."""
    cfg = small_cfg(**SHARE)
    prompts = _prompts([(5, 9), (11, 6), (19, 7), (8, 5)])
    model, p0, outs, st = _served(cfg, prompts)
    assert st["state_bytes_per_slot"] == 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert st["cache_bytes_per_slot"] == st["state_bytes_per_slot"] \
        + st["cache_len"] * 2 * 16 * 4
    for (p, k), out in zip(prompts, outs):
        assert len(out) == len(p) + k
        lg = ref.logits(cfg, p0, jnp.asarray(out)[None])[0]
        at = np.arange(len(p) - 1, len(out) - 1)
        gap = lg[at].max(-1) - lg[at, out[len(p):]]
        assert float(gap.max()) < 1e-3, (len(p), gap)
        # and the oracle's own walk (position by position, no prefill)
        np.testing.assert_array_equal(out, cached_generate(model, p, k, 64))


def test_a_slots_second_occupant_gets_a_fresh_engines_tokens():
    """One slot, three requests in turn: the second and third enter a slot
    whose fixed-size state the one before left behind; each gets bit-equal
    tokens to the same request in a fresh engine."""
    cfg = small_cfg(**SHARE)
    prompts = _prompts([(13, 8), (6, 10), (17, 5)], seed=70)
    _, _, outs, _ = _served(cfg, prompts, slots=1)
    for pr, out in zip(prompts, outs):
        _, _, (alone,), _ = _served(cfg, [pr], slots=1)
        np.testing.assert_array_equal(out, alone)


def test_counters_and_spans_carry_the_fixed_state(tmp_path):
    from bigdl_tpu.utils import telemetry
    from bigdl_tpu.utils.telemetry import Tracer
    cfg = small_cfg(**SHARE)
    tr = Tracer("memory://nemo_spans", flush_every=0)
    telemetry.set_active(tr)
    try:
        _, _, _, st = _served(cfg, _prompts([(5, 3)]))
    finally:
        telemetry.set_active(None)
    events = tr.events_tail(4096)
    admits = [e for e in events if e.get("name") == "decode.admit"]
    assert admits and all(
        e["args"]["state_bytes"] == st["state_bytes_per_slot"]
        for e in admits)
    track = [e for e in events
             if e.get("ph") == "C" and e.get("name") == "serve.decode"]
    assert track and track[-1]["args"]["state_bytes_per_slot"] == \
        st["state_bytes_per_slot"]
    bd = telemetry.phase_breakdown({"traceEvents": events})
    assert "state_bytes_per_slot" in telemetry.format_report(bd)
