"""``models.MellumLM`` (ISSUE 48) against the plain reference
(``benchmark/reference/mellum2_12b_share4.py``) on seeded weights at a small
size: the logits, ``cached_generate`` and ``DecodeEngine`` through the ring
(prefill, steps, a page's growth, a slot's second occupant); the share test
of the guide's section 4 (four chips' parts of a block add up to the uncut
reference's); the declared state at the published widths; and the engine's
``slot_positions`` counter."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.reference import mellum2_12b_share4 as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.models import MellumLM, cached_generate
from bigdl_tpu.models import decode as kv
from bigdl_tpu.nn import RotaryAttention, WindowAttention
from bigdl_tpu.parallel.expert import GatedMoE
from bigdl_tpu.serve import DecodeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    REPO, "benchmark", "configs", "mellum2_12b_share4.json")))
CM = harness.load_module(os.path.join(
    REPO, "benchmark", "configs", "mellum2_12b_share4.py"), "cm_mellum_test")
TOL = 1e-4
#: the rehearse size's share: 2 of 4 query heads with 1 of 2 key-value
#: heads, 4 of 16 experts
SHARE = dict(CFG, **CFG["rehearse"])
#: the same model uncut
WHOLE = dict(SHARE, num_attention_heads=4, num_key_value_heads=2,
             num_experts=16)


@pytest.fixture(autouse=True)
def _float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    yield
    set_policy(prior)


def seeded(cfg, seed=3):
    model = CM.build_model(cfg)
    params, state = harness.program_weights(CM, cfg, model,
                                            jax.random.key(seed))
    return model, params, state, CM.init_params(cfg, jax.random.key(seed))


@pytest.mark.parametrize("cfg", [WHOLE, SHARE], ids=["whole", "share"])
def test_model_against_the_reference_on_seeded_weights(cfg):
    model, params, state, p0 = seeded(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 29), 0, 211)
    got, _ = model.apply(params, state, toks)
    plain = jax.jit(lambda p, t, prec: jax.nn.log_softmax(
        ref.logits(cfg, p, t, prec), axis=-1), static_argnums=2)
    want = plain(p0, toks, "f32")
    np.testing.assert_allclose(got, want, atol=TOL)
    # the control one precision down is far outside the tolerance
    low = plain(p0, toks, "fp8")
    assert float(jnp.abs(low - want).max()) > 0.05


def test_the_factory_builds_each_layer_from_layer_types():
    model = CM.build_model(SHARE)
    mixers = [m for m, _spec in kv._stateful_modules(model)]
    assert [type(m) for m in mixers] == [
        WindowAttention, WindowAttention, WindowAttention, RotaryAttention,
        WindowAttention, WindowAttention]
    assert all(m.window == 8 for m in mixers if type(m) is WindowAttention)
    full = mixers[3]
    assert full.window is None
    assert full.attention_factor == pytest.approx(1.2772588722239782)
    np.testing.assert_array_equal(
        full.inv_freq, ref.yarn_inv_freq(
            16, SHARE["rope_parameters"]["full_attention"]))
    assert all(m.attention_factor == 1.0 for m in mixers if m is not full)
    with pytest.raises(ValueError):
        MellumLM(211, 64, ["linear_attention"], 2, 1, 16, 32, 16, 3, 8,
                 SHARE["rope_parameters"])


def _gap(p0, cfg, row, t0):
    """The widest gap by which a served token's reference logit lies under
    the reference's best, over the positions that chose tokens ``t0..``."""
    logits = np.asarray(jax.jit(lambda p, t: ref.logits(cfg, p, t))(
        p0, jnp.asarray(row)[None]))[0]
    at = np.arange(t0 - 1, len(row) - 1)
    return float((logits[at].max(-1) - logits[at, row[at + 1]]).max())


def test_cached_generate_walks_the_ring():
    """``cached_generate`` sends every position through the layers' own
    ``decode_step`` (the layers are ``_shaped``): 11 prompt positions and 19
    more through rings of 8 rows."""
    model, params, state, p0 = seeded(SHARE)
    model.attach(params, state)
    prompt = np.asarray(jax.random.randint(jax.random.key(2), (2, 11), 0,
                                           211))
    out = np.asarray(cached_generate(model, prompt, 19, 32))
    assert out.shape == (2, 30)
    assert max(_gap(p0, SHARE, row, 11) for row in out) < TOL


def _served(prompts, slots=2, page=16, **kw):
    model, params, state, p0 = seeded(SHARE)
    model.attach(params, state)
    with DecodeEngine(model, slots=slots, page=page, max_len=64, **kw) as eng:
        hs = [eng.submit(p, k) for p, k in prompts]
        outs = [np.asarray(h.result(300.0)) for h in hs]
        st = eng.stats()
    return p0, outs, st


def _prompts(lengths, seed=50):
    r = np.random.default_rng(seed)
    return [(r.integers(0, 211, n).astype(np.int32), k) for n, k in lengths]


def test_prefill_steps_and_a_pages_growth_equal_the_full_forward():
    """Two slots, five requests: prompts shorter than the window of 8, equal
    to it and three times it, grouped prefills with pads, a cache that grows
    from 16 to 48 positions under rows in flight (the rings are carried
    over as they are), slots taken again by shorter requests."""
    prompts = _prompts([(3, 12), (8, 9), (25, 20), (5, 4), (13, 30)])
    p0, outs, st = _served(prompts)
    for (p, k), row in zip(prompts, outs):
        assert len(row) == len(p) + k
        assert _gap(p0, SHARE, row, len(p)) < TOL
    assert st["cache_grows"] >= 1 and st["seqs_done"] == 5
    # five window layers' rings, 2 x 8 x 16 float32 each, whatever the
    # length; one full layer's key and value a position
    assert st["state_bytes_per_slot"] == 5 * 2 * 8 * 16 * 4
    assert st["state_bytes_per_position"] == 2 * 16 * 4
    assert st["state_bytes_fixed"] == 2 * st["state_bytes_per_slot"]


def test_slot_positions_counts_what_the_steps_rows_may_read():
    """One request alone: a prompt of 5 and 7 tokens.  The prefill gives the
    first; six steps give the rest, their input tokens at positions 5..10,
    so their rows may read 6..11 positions: 51."""
    _p0, _outs, st = _served(_prompts([(5, 7)]), slots=1)
    assert st["decode_steps"] == 6
    assert st["slot_positions"] == sum(range(6, 12)) == 51


def test_the_state_at_the_published_widths():
    """By ``cache_avals``, nothing allocated: a slot holds 21 rings of 2 x
    1,024 x 128 bfloat16 = 11,010,048 B whatever the length, and 7 x 2 x 128
    x 2 B = 3,584 B a position (ISSUE 48)."""
    CM.set_policy(CFG)
    model = CM.build_model(CFG)
    for length in (1024, 5120):
        total, fixed = kv.state_bytes_per_row(model, length, jnp.bfloat16)
        assert fixed == 11_010_048
        assert total - fixed == 3_584 * length
    avals = kv.cache_avals(model, 192, 5120, jnp.bfloat16)
    assert len(avals) == 28
    rings = [a for i, a in enumerate(avals) if i % 4 != 3]
    grows = [a for i, a in enumerate(avals) if i % 4 == 3]
    assert all(a["k"].shape == a["v"].shape == (192, 1024, 128)
               for a in rings)
    assert all(a["k"].shape == a["v"].shape == (192, 5120, 128)
               for a in grows)
    assert CM.state_bytes_per_row(CFG) == {"ring": 11_010_048,
                                           "position": 3_584}
    specs = [spec for _m, spec in kv._stateful_modules(model, 192, 5120)]
    assert [spec["k"].length_axis for spec in specs] == [None, None, None,
                                                         1] * 7


# ------------------------------------------------------------ the share


def _attention_share(p, j, d=16):
    """Chip ``j`` of four: query head ``j`` with key-value head ``j // 2``
    (the uncut layer's 4 query heads on 2)."""
    h = slice(j * d, (j + 1) * d)
    g = slice(j // 2 * d, (j // 2 + 1) * d)
    return {"wq": p["wq"][:, h], "wk": p["wk"][:, g], "wv": p["wv"][:, g],
            "wo": p["wo"][h]}


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention",
                                  "experts"])
def test_four_shares_add_up_to_the_whole_layer(kind):
    """The uncut reference's layer against the sum of what the program's
    four shares give: heads 0 / 1 / 2 / 3 each with its key-value head,
    experts 0-3 / 4-7 / 8-11 / 12-15; the router chooses in all four and
    adds nothing of its own, and a block has nothing else that every chip
    computes alike but its norms, which come before the layer."""
    z = ref.sizes(WHOLE)
    _model, _params, _state, p0 = seeded(WHOLE)
    x = jnp.asarray(np.random.default_rng(9).normal(
        size=(1, 21, 64)).astype(np.float32))
    total = 0.0
    if kind == "experts":
        whole = p0[2][1]
        want = ref.moe(z, whole, x[0], "f32")
        for j in range(4):
            layer = GatedMoE(64, 32, 16, 3, n_shared=0, held=(4 * j, 4),
                             score="softmax", renormalise=True)
            e = slice(4 * j, 4 * j + 4)
            part = dict(whole, w_gate=whole["w_gate"][e],
                        w_up=whole["w_up"][e], w_down=whole["w_down"][e])
            total = total + layer.apply(part, layer._init_state(), x)[0][0]
    else:
        whole = p0[1][1]
        want = ref.attention(z, whole, x[0], "f32", kind)
        rope = SHARE["rope_parameters"][kind]
        for j in range(4):
            if kind == "sliding_attention":
                layer = WindowAttention(64, 1, 8, num_kv_heads=1,
                                        head_dim=16, rope_theta=100)
            else:
                layer = RotaryAttention(
                    64, 1, num_kv_heads=1, head_dim=16, rope_theta=100,
                    rope_scaling=dict(rope, type="yarn"),
                    attention_factor=rope["attention_factor"])
            total = total + layer.apply(_attention_share(whole, j), {},
                                        x)[0][0]
    np.testing.assert_allclose(total, want, atol=2 * TOL)
