"""DeepSeek-V2 on the serving path (ISSUE 27): latent attention with a latent
cache, group-limited routing without dropped tokens, shared experts, and one
chip's share of a wider layer, held to the benchmark's plain reference
(benchmark/reference/deepseek_v2_share4.py, which imports nothing of the
program) at a small size on the CPU.

Float32 policy throughout, so the program and the reference differ by the
order of float32 sums only.  Tolerances: log-probabilities and layer outputs
here are O(1) and sums run over at most 211 terms, so 2e-4 absolute is some
hundred float32 roundings.  The same reference computed with fp8 operands
(the control of the benchmark) lies 0.05 or more away, so a program that
computed below its stated precision would fail every one of these."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v2_share4 as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.models.deepseek import DeepSeekV2LM
from bigdl_tpu.nn import LatentAttention
from bigdl_tpu.nn.rotary import rope_inv_freq, yarn_mscale
from bigdl_tpu.parallel.expert import GatedMoE, group_limited_top_k
from bigdl_tpu.serve import DecodeEngine

TOL = 2e-4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def small_cfg(heads=8, held=(0, 16), **over):
    """The issue's small size: hidden 64, 8 heads, 16 experts in 4 groups,
    the 2 best groups, 3 experts a token, 2 shared, 3 layers, vocabulary
    211.  ``heads`` and ``held`` cut a share out of it."""
    cfg = {"vocab_size": 211, "hidden_size": 64, "num_hidden_layers": 3,
           "num_attention_heads": heads, "q_lora_rank": 32,
           "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "intermediate_size": 128,
           "moe_intermediate_size": 32, "n_routed_experts": held[1],
           "held": {"first_expert": held[0], "router_outputs": 16},
           "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
           "n_shared_experts": 2, "routed_scaling_factor": 16,
           "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
           "rope_theta": 10000, "rope_scaling": YARN,
           "initializer_range": 0.02, "param_dtype": "float32",
           "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


def build(cfg):
    z = ref.sizes(cfg)
    return DeepSeekV2LM(
        vocab_size=z["vocab"], hidden=z["hidden"], num_layers=z["layers"],
        heads_held=z["heads"], q_lora_rank=z["q_lora"],
        kv_lora_rank=z["kv_lora"], qk_nope_head_dim=z["nope"],
        qk_rope_head_dim=z["rope"], v_head_dim=z["v"],
        dense_width=z["dense"], expert_width=z["expert"],
        num_experts=z["routed"], experts_per_token=z["k"],
        n_group=z["n_group"], topk_group=z["topk_group"],
        n_shared=z["shared"], routed_scaling_factor=z["scale"],
        first_k_dense=z["first_dense"], experts_held=z["held"],
        rope_theta=z["theta"], rope_scaling=z["scaling"], eps=z["eps"])


def seeded(cfg, seed=3, std=0.2):
    """The reference's seeded weights, wider than the published 0.02 so that
    at 64 dimensions the router and the scores are far from uniform, laid
    out as the program's tree."""
    cfg = dict(cfg, initializer_range=std)
    p0 = ref.init_params(cfg, jax.random.key(seed))
    model = build(cfg)
    shapes, _ = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree.leaves(p0)
    assert [x.shape for x in leaves] == \
        [s.shape for s in jax.tree.leaves(shapes)]
    params = jax.tree.unflatten(jax.tree.structure(shapes), leaves)
    model.attach(params, model.init(jax.random.key(0))[1])
    return cfg, p0, model


@pytest.fixture(autouse=True)
def float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    yield
    set_policy(prior)


def _logp(cfg, p0, toks, prec="f32"):
    return np.asarray(jax.nn.log_softmax(
        ref.logits(cfg, p0, jnp.asarray(toks), prec), axis=-1))


# (a) ---------------------------------------------------------------------


def test_full_forward_equals_the_reference_and_fp8_does_not():
    cfg, p0, model = seeded(small_cfg())
    toks = np.random.default_rng(0).integers(0, 211, (2, 24)).astype(np.int32)
    want = _logp(cfg, p0, toks)
    got, _ = model.apply(model.params, model.state, jnp.asarray(toks))
    assert np.abs(np.asarray(got) - want).max() < TOL
    # the tolerance separates: fp8 operands land far outside it
    assert np.abs(_logp(cfg, p0, toks, "fp8") - want).max() > 100 * TOL


def test_the_share_runs_through_the_same_forward():
    """The benchmark's cut at the small size: 4 of 8 heads, experts 4..11 of
    16 (not from 0, so `first` is exercised), against the reference given
    the same share."""
    cfg, p0, model = seeded(small_cfg(heads=4, held=(4, 8)))
    toks = np.random.default_rng(1).integers(0, 211, (1, 17)).astype(np.int32)
    got, _ = model.apply(model.params, model.state, jnp.asarray(toks))
    assert np.abs(np.asarray(got) - _logp(cfg, p0, toks)).max() < TOL


# (b) ---------------------------------------------------------------------


def test_prefill_then_decode_through_the_engines_programs():
    """The engine's two executables, driven by hand so every served
    position's log-probabilities can be read: three slots at different
    positions, and slot 0 reused by a short prompt after a longer occupant
    (its stale rows must weigh exactly nothing).  Every row is held to the
    reference's full forward over the tokens it was fed."""
    cfg, p0, model = seeded(small_cfg(heads=4, held=(4, 8)))
    eng = DecodeEngine(model, slots=3, page=32, max_len=32,
                       cache_dtype=np.float32)
    L = 32
    caches = eng._fresh_caches(L)
    r = np.random.default_rng(5)
    seqs = {}          # slot -> tokens fed so far; logps served

    def admit(slot, t0, bucket):
        nonlocal caches
        toks = np.zeros(bucket, np.int32)
        toks[:t0] = r.integers(0, 211, t0)
        lp, _toks, caches, (counts, _chosen) = eng._prefill_exe(
            1, bucket, L)(
            eng._params, eng._state, caches, jnp.zeros(3, jnp.int32),
            jnp.asarray(toks)[None], np.array([slot], np.int32),
            np.array([t0], np.int32))
        seqs[slot] = {"toks": list(toks[:t0]), "lp": [np.asarray(lp)[0]]}
        return np.asarray(counts)

    def step(active):
        nonlocal caches
        tok = np.zeros(3, np.int32)
        pos = np.full(3, -1, np.int32)
        for s in active:
            nxt = int(r.integers(0, 211))       # any token: teacher-forced
            seqs[s]["toks"].append(nxt)
            tok[s], pos[s] = nxt, len(seqs[s]["toks"]) - 1
        # every token from the host, as for a request that samples
        lp, _toks, caches, (counts, _chosen) = eng._step_exe(L)(
            eng._params, eng._state, caches, jnp.zeros(3, jnp.int32),
            jnp.asarray(np.stack([pos, np.where(pos >= 0, tok, -1)])))
        for s in active:
            seqs[s]["lp"].append(np.asarray(lp[s]))
        return np.asarray(counts)

    def check(slot):
        toks = np.asarray(seqs[slot]["toks"], np.int32)[None]
        want = _logp(cfg, p0, toks)[0]
        n = len(seqs[slot]["lp"])
        got = np.stack(seqs[slot]["lp"])
        assert np.abs(got - want[len(want) - n:]).max() < TOL, slot

    c = admit(0, 13, 16)
    # a prefill counts its 13 real tokens, not the bucket's 16 positions, in
    # the first expert layer; the second comes after the last attention
    # layer, where only the prompt's last position goes on: 3 choices each,
    # here or elsewhere
    assert c.sum() == (13 + 1) * 3
    admit(1, 3, 8)
    for _ in range(4):
        step([0, 1])
    admit(2, 7, 8)
    for _ in range(5):
        c = step([0, 1, 2])
    assert c.sum() == 2 * 3 * 3
    check(0)
    check(2)
    # slots 0 and 2 are free now: idle rows are counted nowhere (and may
    # be written to: position 0 of a free slot, which a prefill overwrites)
    c = step([1])
    assert c.sum() == 2 * 1 * 3
    admit(0, 2, 8)                     # reuse after the longer occupant
    for _ in range(6):
        step([0, 1])
    check(0)
    check(1)


def test_engine_serves_and_counts_experts():
    cfg, p0, model = seeded(small_cfg(heads=4, held=(4, 8)))
    prompts = [np.random.default_rng(s).integers(0, 211, n).astype(np.int32)
               for s, n in enumerate((5, 11, 3, 9))]
    with DecodeEngine(model, slots=2, page=32, max_len=32) as eng:
        outs = [h.result(120.0) for h in [eng.submit(p, 6) for p in prompts]]
        st = eng.stats()
    for p, out in zip(prompts, outs):
        # greedy tokens are the reference's own argmax, teacher-forced
        lp = _logp(cfg, p0, out[None])[0]
        np.testing.assert_array_equal(out[len(p):],
                                      lp[len(p) - 1:-1].argmax(-1))
    # a prefill routes the prompt in the first expert layer and its last
    # position in the second; each of the 5 steps one token in both
    choices = sum(3 * (len(p) + 1) + 5 * 2 * 3 for p in prompts)
    assert st["expert_tokens"] + st["expert_tokens_elsewhere"] == choices
    assert 0 < st["expert_tokens_max"] <= st["expert_tokens"]
    # 5 layers' worth? no: 3 layers x (16 + 4) float32 values a position
    assert st["cache_bytes_per_slot"] == 3 * (16 + 4) * 4 * 32


# (c) ---------------------------------------------------------------------


def test_absorbed_decode_form_equals_the_expanded_form():
    attn = LatentAttention(64, 8, 32, 16, 8, 4, 8, rope_scaling=YARN)
    p, _ = attn.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 9, 64))
    want = attn._apply(p, x)
    cache = {n: jnp.zeros(leaf.shape)
             for n, leaf in attn.decode_state(2, 12).items()}
    got = []
    for t in range(9):
        y, cache = attn.decode_step(p, x[:, t:t + 1], cache,
                                    jnp.full((2,), t, jnp.int32))
        got.append(y)
    # same sums in another order: a few float32 roundings
    assert jnp.abs(jnp.concatenate(got, 1) - want).max() < 1e-5
    # a long sequence attends in query blocks: the same numbers
    attn.QUERY_BLOCK = 4
    x = jax.random.normal(jax.random.key(2), (1, 12, 64))
    blocked = attn._apply(p, x)
    attn.QUERY_BLOCK = 512
    assert jnp.abs(blocked - attn._apply(p, x)).max() < 1e-5
    # a padded prompt: blocks of queries that hold no real position are
    # skipped, the real positions read as before, the cache takes every row
    attn.QUERY_BLOCK = 4
    cache = {n: jnp.zeros(leaf.shape)
             for n, leaf in attn.decode_state(2, 12).items()}
    y, new = jax.jit(lambda length: attn.decode_prefill(
        p, x, cache, 1, length))(5)
    attn.QUERY_BLOCK = 512
    assert jnp.abs(y[0, :5] - blocked[0, :5]).max() < 1e-5
    assert not np.asarray(y[0, 8:]).any()         # the third block: skipped
    assert np.asarray(new["c_kv"][1]).all() and not \
        np.asarray(new["c_kv"][0]).any()


# (d) ---------------------------------------------------------------------


def test_yarn_frequencies_and_score_scale_written_out():
    """The published numbers: 64 rotary dimensions, base 10,000, factor 40
    over 4,096 positions, beta_fast 32, beta_slow 1."""
    f = rope_inv_freq(64, 10000.0, YARN)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # the pair that makes 32 turns over 4,096 positions is
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10; one turn:
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    assert math.floor(64 * math.log(4096 / (64 * math.pi))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)      # fast
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)  # slow
    for i in range(11, 23):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(
            f[i], plain[i] / 40 * ramp + plain[i] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f, ref.yarn_inv_freq(64, 10000.0, YARN),
                               rtol=1e-6)
    np.testing.assert_allclose(rope_inv_freq(64), plain, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    attn = LatentAttention(5120, 32, 1536, 512, 128, 64, 128,
                           rope_scaling=YARN)
    assert attn.score_scale == pytest.approx(192 ** -0.5 * m * m)
    assert attn.score_scale == pytest.approx(
        ref.score_scale({"scaling": YARN, "nope": 128, "rope": 64}))


# (e) ---------------------------------------------------------------------


def _ref_choice(scores, n_group, topk_group, k):
    z = {"n_group": n_group, "topk_group": topk_group, "k": k, "scale": 1.0}
    # routing() takes the router's matrix and the input: an identity matrix
    # and log-scores make softmax(x W) the scores themselves
    logs = jnp.log(jnp.asarray(scores))
    return np.asarray(ref.routing(z, jnp.eye(scores.shape[1]), logs))


def test_group_limited_top_k_on_ties_and_a_full_group():
    e, g = 16, 4
    base = np.full((4, e), 1.0)
    # row 0: every score equal: groups 0, 1, experts 0, 1, 2 (lower first)
    # row 1: one group holds the three best and more: all from group 2
    base[1, 8:12] = [9, 8, 7, 6]
    # row 2: the best expert sits in a group that loses... cannot: a
    # group's score is its best.  Two groups tie for second place: the
    # lower one is kept
    base[2, 0] = 5
    base[2, 5] = base[2, 13] = 3
    base[2, 6] = 2.5
    base[2, 14] = 2.9         # better than 2.5, but group 3 lost the tie
    # row 3: equal experts inside the kept groups
    base[3, 4:8] = 4
    base[3, 12] = 6
    scores = base / base.sum(-1, keepdims=True)
    w, idx = group_limited_top_k(jnp.asarray(scores, jnp.float32), g, 2, 3)
    idx = np.sort(np.asarray(idx), axis=-1)
    assert idx.tolist() == [[0, 1, 2], [8, 9, 10], [0, 5, 6], [4, 5, 12]]
    want = _ref_choice(scores.astype(np.float32), g, 2, 3)
    for t in range(4):
        assert sorted(np.nonzero(want[t])[0].tolist()) == idx[t].tolist()
        np.testing.assert_allclose(np.sort(np.asarray(w[t])),
                                   np.sort(want[t][want[t] > 0]), rtol=1e-6)


def test_random_scores_choose_as_the_reference_does():
    s = jax.nn.softmax(jax.random.normal(jax.random.key(4), (64, 160)) * 1.4)
    w, idx = group_limited_top_k(s, 8, 3, 6)
    want = _ref_choice(np.asarray(s), 8, 3, 6)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_every_token_to_one_expert_and_nothing_is_dropped():
    moe = GatedMoE(32, 16, 16, 3, n_group=4, topk_group=2, n_shared=0,
                   scale=2.0)
    p, s = moe.init(jax.random.key(0))
    # a router that sends every token to experts 4, 5, 6 whatever it holds
    gate = np.zeros((32, 16), np.float32)
    p["gate"] = jnp.asarray(gate)
    bias = np.zeros(16, np.float32)
    bias[[4, 5, 6]] = [3.0, 2.0, 1.0]
    x = jax.random.normal(jax.random.key(1), (1, 40, 32))
    real = moe.route
    moe.route = lambda params, xt: group_limited_top_k(
        jnp.broadcast_to(jax.nn.softmax(jnp.asarray(bias)), (40, 16)),
        4, 2, 3)
    y, ns = moe.apply(p, s, x)
    moe.route = real
    w = np.asarray(jax.nn.softmax(jnp.asarray(bias)))
    xt = x[0]
    want = sum(w[e] * (jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e]))
               @ p["w_down"][e] for e in (4, 5, 6))
    assert jnp.abs(y[0] - want).max() < 1e-5
    counts = np.asarray(ns["expert_tokens"])
    assert counts[[4, 5, 6]].tolist() == [40, 40, 40] and counts.sum() == 120


# (f) ---------------------------------------------------------------------


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    cfg = small_cfg()
    z = ref.sizes(cfg)
    p0 = ref.init_params(dict(cfg, initializer_range=0.2),
                         jax.random.key(7))
    ffn = p0[2][3]                       # the first expert layer's weights
    x = jax.random.normal(jax.random.key(8), (21, 64))
    whole = ref.moe(z, ffn, x, "f32")
    shared = ref.gated(x, ffn["shared_gate"], ffn["shared_up"],
                       ffn["shared_down"], "f32")
    total = 0
    for first in range(0, 16, 4):
        layer = GatedMoE(64, 32, 16, 3, n_group=4, topk_group=2, n_shared=2,
                         scale=16, held=(first, 4))
        p = dict(ffn)
        for n in ("w_gate", "w_up", "w_down"):
            p[n] = ffn[n][first:first + 4]
        y, ns = layer.apply(p, layer._init_state(), x[None])
        total = total + y[0]
        # and the reference given the same share says the same
        want = ref.moe(dict(z, held=(first, 4)), p, x, "f32")
        assert jnp.abs(y[0] - want).max() < TOL
        assert int(ns["expert_tokens"].sum()) == 21 * 3
    # the shared experts are computed alike on all four: counted once
    assert jnp.abs(total - 3 * shared - whole).max() < TOL


def test_the_four_head_shares_add_up_to_the_uncut_layer():
    cfg = small_cfg()
    z = ref.sizes(cfg)
    p0 = ref.init_params(dict(cfg, initializer_range=0.2),
                         jax.random.key(9))
    at = p0[1][1]
    x = jax.random.normal(jax.random.key(10), (11, 64))
    whole = ref.attention(z, at, x, "f32")
    total = 0
    for h0 in range(0, 8, 2):
        cols = lambda w, d: w.reshape(w.shape[0], 8, d)[:, h0:h0 + 2] \
            .reshape(w.shape[0], 2 * d)
        p = dict(at, wuq=cols(at["wuq"], 12), wukv=cols(at["wukv"], 16),
                 wo=at["wo"].reshape(8, 8, 64)[h0:h0 + 2].reshape(16, 64))
        layer = LatentAttention(64, 2, 32, 16, 8, 4, 8, rope_scaling=YARN)
        y = layer._apply(p, x[None])[0]
        assert jnp.abs(y - ref.attention(dict(z, heads=2), p, x,
                                         "f32")).max() < TOL
        total = total + y
    assert jnp.abs(total - whole).max() < TOL


# routing is discrete --------------------------------------------------------


def test_expert_choices_agree_with_the_reference_in_float32():
    """How often the program's choice of experts differs from the
    reference's: in float32, never, on 3 x 40 tokens; in bfloat16 (the
    benchmark's dtype) a near tie can flip, rarely."""
    cfg, p0, model = seeded(small_cfg())
    z = ref.sizes(cfg)
    x = jax.random.normal(jax.random.key(11), (40, 64))
    for layer in (2, 3):
        ffn = p0[layer][3]
        moe = GatedMoE(64, 32, 16, 3, n_group=4, topk_group=2, n_shared=2,
                       scale=16)
        w, idx = moe.route(ffn, x)
        want = np.asarray(ref.routing(z, ffn["gate"], x))
        got = np.zeros_like(want)
        np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=-1)
        assert ((got > 0) != (want > 0)).sum() == 0
        np.testing.assert_allclose(got, want, rtol=1e-5)
        xb = x.astype(jnp.bfloat16).astype(jnp.float32)
        _, idx_b = moe.route(ffn, xb)
        flipped = sum(set(a.tolist()) != set(b.tolist())
                      for a, b in zip(np.asarray(idx), np.asarray(idx_b)))
        assert flipped <= 4            # of 40 tokens


# counters -------------------------------------------------------------------


def test_expert_counters_reach_stats_metrics_and_the_report(monkeypatch):
    from bigdl_tpu.utils import metrics_export, telemetry
    from bigdl_tpu.utils.telemetry import Tracer
    cfg, p0, model = seeded(small_cfg(heads=4, held=(4, 8)))
    prompt = np.arange(1, 8, dtype=np.int32)
    # nobody reads: the step fetches the logits and what the expert layers
    # report (one count vector, the chosen experts), and the track is not
    # computed
    monkeypatch.setattr(metrics_export, "_REGISTRY", None)
    telemetry.set_active(None)
    fetched = []
    real = DecodeEngine._count_experts
    monkeypatch.setattr(DecodeEngine, "_count_experts",
                        lambda self, c: fetched.append(np.asarray(c).shape)
                        or real(self, c))
    with DecodeEngine(model, slots=2, page=16, max_len=16) as eng:
        eng.generate(prompt, 4)
        quiet = eng.stats()
    assert set(fetched) == {(9,)} and len(fetched) == 1 + 3   # 8 held + 1
    assert quiet["expert_tokens"] + quiet["expert_tokens_elsewhere"] \
        == 3 * (7 + 1) + 3 * 2 * 3
    # with a registry and a tracer they read the same numbers
    reg = metrics_export.arm()
    tr = Tracer("memory://unused", flush_every=0)
    telemetry.set_active(tr)
    try:
        with DecodeEngine(model, slots=2, page=16, max_len=16) as eng:
            eng.generate(prompt, 4)
            st = eng.stats()
        text = reg.render()
    finally:
        telemetry.set_active(None)
        metrics_export.disarm()
    assert st["expert_tokens"] == quiet["expert_tokens"]
    assert f'bigdl_decode_expert_tokens_total{{held="here"}} ' \
           f'{st["expert_tokens"]}' in text
    assert f'bigdl_decode_expert_tokens_total{{held="elsewhere"}} ' \
           f'{st["expert_tokens_elsewhere"]}' in text
    imbalance = st["expert_tokens_max"] / (st["expert_tokens"] / 8)
    assert f"bigdl_decode_expert_imbalance {imbalance:g}"[:36] in text
    track = [e["args"] for e in tr.events_tail(4096)
             if e["ph"] == "C" and e["name"] == "serve.decode"]
    assert track[-1]["expert_tokens"] == st["expert_tokens"]
    assert track[-1]["expert_tokens_max"] == st["expert_tokens_max"]
    bd = telemetry.phase_breakdown({"traceEvents": [
        {"ph": "C", "name": "serve.decode", "ts": 1.0, "args": track[-1]}]})
    line = [ln for ln in telemetry.format_report(bd).splitlines()
            if ln.startswith("decode:")][0]
    assert f"expert_tokens={st['expert_tokens']}" in line
    assert "expert_tokens_elsewhere=" in line and "expert_tokens_max=" in line


def test_a_model_without_experts_counts_none():
    from bigdl_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=64, max_len=32, d_model=32, num_heads=2,
                       num_layers=1).build(jax.random.key(0))
    with DecodeEngine(lm, slots=1, page=8) as eng:
        eng.generate(np.arange(1, 5, dtype=np.int32), 2)
        st = eng.stats()
    assert "expert_tokens" not in st


# the small layers ---------------------------------------------------------


def test_rmsnorm_silu_and_the_gated_mlp_written_out():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.deepseek import GatedMLP
    x = jax.random.normal(jax.random.key(0), (3, 5, 16)) * 3
    norm = nn.RMSNorm(16, eps=1e-6).build(jax.random.key(1))
    norm.params = {"weight": jnp.linspace(0.5, 2.0, 16)}
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * norm.params["weight"]
    np.testing.assert_allclose(norm.forward(x), want, rtol=1e-6)
    np.testing.assert_allclose(
        want, ref.rms_norm(x, norm.params["weight"], 1e-6), rtol=1e-6)
    # bfloat16 in, bfloat16 out, statistics in float32
    assert norm.forward(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    np.testing.assert_allclose(nn.SiLU().forward(x), x / (1 + jnp.exp(-x)),
                               rtol=1e-5, atol=1e-6)
    mlp = GatedMLP(16, 24).build(jax.random.key(2))
    gate, up, down = (leaf["weight"] for leaf in (
        mlp.params[0][0][0], mlp.params[0][1], mlp.params[2]))
    assert "bias" not in mlp.params[2]
    want = (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T
    np.testing.assert_allclose(mlp.forward(x), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        want[0], ref.gated(x[0], gate.T, up.T, down.T, "f32"),
        rtol=1e-4, atol=1e-5)
    # gradients flow through the facade
    g = mlp.backward(x, jnp.ones_like(want))
    assert g.shape == x.shape and np.isfinite(np.asarray(g)).all()
