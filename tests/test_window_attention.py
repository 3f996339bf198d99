"""``nn.WindowAttention`` and ``nn.RotaryAttention`` (ISSUE 48) against a
plain ``[T, T]``-mask reference in float32 (``benchmark/reference/
mellum2_12b_share4.attention``, which imports nothing of the program): the
full-sequence form, a prefill and then steps through the ring, at prompts
shorter than, equal to and several times the window, with pads in a grouped
prefill, with a slot taken again by a shorter request; YaRN's frequencies and
factor against numbers worked out by hand from the published row."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import mellum2_12b_share4 as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.nn import MultiHeadAttention, RotaryAttention, WindowAttention
from bigdl_tpu.nn.module import StateLeaf
from bigdl_tpu.nn.rotary import rope_inv_freq, yarn_mscale

E, H, G, D, W = 32, 4, 2, 8, 6
#: a YaRN group cut to the size: the ramp runs over pairs 0..2 of 4, so the
#: blend and the factor move positions under 40
YARN = {"rope_type": "yarn", "rope_theta": 100.0, "factor": 16,
        "original_max_position_embeddings": 8, "beta_fast": 1,
        "beta_slow": 0.25, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 100.0}
ROPE = {"full_attention": YARN, "sliding_attention": PLAIN}
Z = {"head_dim": D, "window": W, "rope": ROPE}


@pytest.fixture(autouse=True)
def float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    yield
    set_policy(prior)


def _layer(kind, block=4):
    heads = dict(num_kv_heads=G, head_dim=D)
    if kind == "sliding_attention":
        layer = WindowAttention(E, H, W, rope_theta=100.0, **heads)
    else:
        layer = RotaryAttention(
            E, H, rope_theta=100.0, rope_scaling=dict(YARN, type="yarn"),
            attention_factor=YARN["attention_factor"], **heads)
    layer.QUERY_BLOCK = block      # blocks of 4 queries, so the band has some
    params, _ = layer.init(jax.random.key(3))
    return layer, jax.tree.map(lambda a: a * 3.0, params)   # far from uniform


def _x(rows, T, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(rows, T, E)).astype(np.float32))


def _plain(params, row, kind):
    """One row ``[T, E]`` through the plain reference's attention."""
    return jax.jit(lambda p, x: ref.attention(Z, p, x, "f32", kind))(
        params, row)


def _want(params, x, kind):
    return jnp.stack([_plain(params, row, kind) for row in x])


KINDS = ["sliding_attention", "full_attention"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("T", [3, 6, 8, 16, 28])
def test_the_full_sequence_form_is_the_plain_masks(kind, T):
    """T shorter than the window, equal to it, and several times it; 8, 16
    and 28 are whole blocks of 4 queries, 3 and 6 one block."""
    layer, params = _layer(kind)
    x = _x(2, T)
    got, _ = layer.apply(params, {}, x)
    np.testing.assert_allclose(got, _want(params, x, kind), atol=2e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_the_gradient_is_the_plain_masks(kind):
    """``Module.forward``'s form is differentiable (the band is ``jnp``; the
    full layer's core is the library's flash attention): the gradient of a
    scalar of the output by every leaf is the plain reference's."""
    layer, params = _layer(kind)
    x = _x(1, 16, seed=3)
    got = jax.grad(lambda p: jnp.sum(jnp.square(
        layer.apply(p, {}, x)[0])))(params)
    want = jax.grad(lambda p: jnp.sum(jnp.square(
        ref.attention(Z, p, x[0], "f32", kind))))(params)
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-3)


def test_the_window_is_what_differs_from_the_full_layer():
    layer, params = _layer("sliding_attention")
    x = _x(1, 16)
    inside = np.asarray(layer.apply(params, {}, x)[0])
    wide = WindowAttention(E, H, 64, rope_theta=100.0, num_kv_heads=G,
                           head_dim=D)
    outside = np.asarray(wide.apply(params, {}, x)[0])
    np.testing.assert_allclose(inside[:, :W], outside[:, :W], atol=1e-6)
    assert np.abs(inside[:, W:] - outside[:, W:]).max() > 1e-2


def _serve(layer, params, seqs, P, rows=None, cache=None):
    """``seqs``: list of ``[T_i, E]`` inputs; the first ``t0_i`` of each go
    through one grouped prefill of bucket ``P`` (pads after them), the rest
    one step at a time, all rows together.  Returns each row's outputs ``[T_i,
    E]`` (prefill's real positions, then the steps') and the cache."""
    n = len(seqs)
    t0 = [s[1] for s in seqs]
    xs = [s[0] for s in seqs]
    S = rows or n
    if cache is None:
        cache = {k: jnp.zeros(leaf.shape, jnp.float32)
                 for k, leaf in layer.decode_state(S, 64).items()}
    x = jnp.zeros((n, P, E), jnp.float32)
    for i in range(n):
        x = x.at[i, :t0[i]].set(xs[i][:t0[i]])
    y, cache = jax.jit(layer.decode_prefill)(params, x, cache, jnp.arange(n),
                                             jnp.asarray(t0))
    outs = [[np.asarray(y[i, :t0[i]])] for i in range(n)]
    step_fn = jax.jit(layer.decode_step)
    longest = max(len(a) for a in xs)
    for step in range(longest - min(t0)):
        pos = np.full((S,), -1, np.int32)
        tok = jnp.zeros((S, 1, E), jnp.float32)
        for i in range(n):
            p = t0[i] + step
            if p < len(xs[i]):
                pos[i] = p
                tok = tok.at[i, 0].set(xs[i][p])
        y, cache = step_fn(params, tok, cache, jnp.asarray(pos))
        for i in range(n):
            if pos[i] >= 0:
                outs[i].append(np.asarray(y[i]))
    return [np.concatenate(o, axis=0) for o in outs], cache


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("t0", [2, 6, 7, 16, 25])
def test_prefill_then_steps_are_the_plain_masks(kind, t0):
    """Prompts shorter than the window, equal to it, one past it, and several
    times it; 14 steps follow, so every ring wraps, the longest five
    times."""
    layer, params = _layer(kind)
    x = _x(1, t0 + 14, seed=t0)[0]
    (got,), _ = _serve(layer, params, [(x, t0)], P=32)
    want = _plain(params, x, kind)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_a_grouped_prefill_keeps_its_pads_out(kind):
    """Three prompts of 3, 9 and 16 real positions in one bucket of 16: the
    first two rows' pads are computed, and must not show in any later
    step."""
    layer, params = _layer(kind)
    seqs = [(_x(1, t0 + 9, seed=10 + t0)[0], t0) for t0 in (3, 9, 16)]
    got, cache = _serve(layer, params, seqs, P=16)
    for (x, _t0), g in zip(seqs, got):
        np.testing.assert_allclose(
            g, _plain(params, x, kind), atol=3e-5)


def test_the_ring_holds_the_last_real_positions_and_zeros():
    layer, params = _layer("sliding_attention")
    x = _x(2, 16, seed=4)
    cache = {k: jnp.full(leaf.shape, 7.0, jnp.float32)    # an old occupant
             for k, leaf in layer.decode_state(3, 64).items()}
    lengths = jnp.asarray([4, 11])
    _, new = layer.decode_prefill(params, x, cache, jnp.asarray([2, 0]),
                                  lengths)
    k = layer._proj(params, x, "k")
    _q, k, _ = layer._shape(params, layer._proj(params, x, "q"), k,
                            jnp.arange(16)[None])
    # row 0 (4 real positions, fewer than the window) went to slot 2: ring
    # rows 0..3 hold positions 0..3, rows 4 and 5 are zero, not sevens and
    # not the pads' keys
    np.testing.assert_array_equal(new["k"][2, :4], k[0, :4])
    assert not np.asarray(new["k"][2, 4:]).any()
    # row 1 (11 real): ring row j holds the last position = j mod 6 below 11
    for j, p in enumerate([6, 7, 8, 9, 10, 5]):
        np.testing.assert_array_equal(new["k"][0, j], k[1, p])
    # the slot no row went to is untouched
    assert (np.asarray(new["k"][1]) == 7.0).all()


def test_a_slot_taken_again_by_a_shorter_request_reads_nothing_old():
    """The ring of a slot that held a long request, prefilled by a short
    one: every later output is bit for bit what a fresh cache gives."""
    layer, params = _layer("sliding_attention")
    long = (_x(1, 30, seed=1)[0], 20)
    _, used = _serve(layer, params, [long], P=32)
    short = (_x(1, 12, seed=2)[0], 3)
    (fresh,), _ = _serve(layer, params, [short], P=8)
    (again,), _ = _serve(layer, params, [short], P=8, cache=used)
    np.testing.assert_array_equal(again, fresh)


def test_an_idle_row_changes_no_other_row():
    layer, params = _layer("sliding_attention")
    x = _x(1, 20, seed=5)[0]
    (alone,), _ = _serve(layer, params, [(x, 5)], P=8)
    (beside, _other), _ = _serve(
        layer, params, [(x, 5), (_x(1, 7, seed=6)[0], 5)], P=8)
    np.testing.assert_allclose(beside, alone, atol=2e-5)


def test_the_two_reads_carry_their_scope_names():
    for kind, name in (("sliding_attention", "window_attn"),
                       ("full_attention", "full_attn")):
        layer, params = _layer(kind)
        cache = {k: jnp.zeros(leaf.shape, jnp.float32)
                 for k, leaf in layer.decode_state(2, 16).items()}
        text = jax.jit(layer.decode_step).lower(
            params, _x(2, 1), cache, jnp.zeros((2,), jnp.int32)).as_text(
                debug_info=True)
        assert name in text
        assert ("window_attn" in text) == (name == "window_attn")


def test_the_declared_state():
    window, _ = _layer("sliding_attention")
    full, _ = _layer("full_attention")
    for length in (1, 64, 4096):
        assert window.decode_state(5, length) == {
            "k": StateLeaf((5, W, G * D), None, "kv_cache"),
            "v": StateLeaf((5, W, G * D), None, "kv_cache")}
        assert full.decode_state(5, length) == \
            MultiHeadAttention(E, H, causal=True, num_kv_heads=G,
                               head_dim=D).decode_state(5, length)
    assert window._shaped and full._shaped and window.causal
    with pytest.raises(ValueError):
        WindowAttention(E, H, 0)


def test_yarn_frequencies_and_factor_are_the_rows_by_hand():
    """``rope_parameters.full_attention`` of the published row: theta
    500,000, factor 16 over 8,192 original positions, beta 32 / 1, a head of
    128.  The pair that makes 32 turns over 8,192 positions is 128 ln(8192 /
    (32 x 2 pi)) / (2 ln 500000) = 18.08, rounded down 18; the pair that
    makes one, 34.98, rounded up 35.  Pairs up to 18 keep their frequency,
    pairs from 35 take a sixteenth, pair m between them ``1 - r + r / 16``
    of it with ``r = (m - 18) / 17``."""
    row = {"type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1}
    got = rope_inv_freq(128, 500000, row)
    plain = rope_inv_freq(128, 500000)
    np.testing.assert_allclose(plain[[0, 18, 26, 63]],
                               [1.0, 0.024955409, 0.0048394212,
                                2.4551407e-06], rtol=1e-6)
    np.testing.assert_allclose(
        got[[0, 18, 19, 26, 34, 35, 63]] / plain[[0, 18, 19, 26, 34, 35, 63]],
        [1.0, 1.0, 1 - 15 / 16 / 17, 1 - 15 / 16 * 8 / 17,
         1 - 15 / 16 * 16 / 17, 1 / 16, 1 / 16], rtol=1e-6)
    assert got[26] == pytest.approx(0.0027043824, rel=1e-6)
    # the row's attention_factor is the method's own temperature
    assert yarn_mscale(16, 1) == pytest.approx(1.2772588722239782, abs=1e-15)
    # and the reference's frequencies, written apart, are the same numbers
    np.testing.assert_array_equal(
        ref.yarn_inv_freq(128, dict(row, rope_type="yarn")), got)
    layer = RotaryAttention(2304, 8, num_kv_heads=1, head_dim=128,
                            rope_theta=500000, rope_scaling=row,
                            attention_factor=1.2772588722239782)
    np.testing.assert_array_equal(layer.inv_freq, got)
    window = WindowAttention(2304, 8, 1024, num_kv_heads=1, head_dim=128,
                             rope_theta=500000)
    np.testing.assert_array_equal(window.inv_freq, plain)
    assert window.attention_factor == 1.0 and window.rotary_dim == 128


def test_the_factor_is_on_cos_and_sin():
    """Both the query and the key carry the factor: a score carries its
    square, the values none."""
    layer, params = _layer("full_attention")
    q = _x(1, 5, seed=8)[..., :H * D]
    k = _x(1, 5, seed=9)[..., :G * D]
    pos = jnp.arange(5)[None]
    q1, k1, gate = layer._shape(params, q, k, pos)
    layer.attention_factor = 1.0
    q0, k0, _ = layer._shape(params, q, k, pos)
    assert gate is None
    np.testing.assert_allclose(q1, q0 * YARN["attention_factor"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(k1, k0 * YARN["attention_factor"], rtol=1e-5,
                               atol=1e-6)
    # position 0 turns nothing
    np.testing.assert_allclose(q0[:, 0], q[:, 0], rtol=1e-6)
