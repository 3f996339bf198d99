"""Mamba-1's selective state-space mixer (``nn.MambaMixer``, the Jamba
family's layer) at a small size on the CPU, against the plain reference's
position-by-position recurrence (``benchmark/reference/jamba2_3b.py``) on
seeded weights: the whole sequence, a group of prompts of unequal lengths
through ``decode_prefill`` and on through ``decode_step``, what a pad and a
fill-up row may not do, the leaves as declared, the Pallas kernel against the
``lax.scan`` form, and the count of operations."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import jamba2_3b as ref
from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
from bigdl_tpu.nn import MambaMixer
from bigdl_tpu.ops import ssm

TOL = 1e-5
#: bfloat16 operands in the four products, float32 recurrence: a mixer's
#: output of size ~0.5 differs from the float32 reference's by rounding of
#: 2^-8 relative in each of three products in a row
BF16_TOL = 3e-2


@pytest.fixture
def float32_policy():
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.float32,
                           compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        yield
    set_policy(prior)


def small_cfg(**over):
    """Hidden 32, 64 channels, a state of 8 a channel, rank 4, 4 taps."""
    cfg = {"vocab_size": 211, "hidden_size": 32, "num_hidden_layers": 4,
           "attn_layer_period": 4, "attn_layer_offset": 1,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "intermediate_size": 64, "mamba_expand": 2, "mamba_d_state": 8,
           "mamba_dt_rank": 4, "mamba_d_conv": 4, "rms_norm_eps": 1e-6,
           "time_step_min": 0.001, "time_step_max": 0.1,
           "initializer_range": 0.2,
           "tie_word_embeddings": True, "param_dtype": "float32",
           "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


def _mixer(cfg, seed=3):
    """(sizes, layer, the reference's seeded parameters of layer 0, with
    norms off 1 so that leaving one out shows)."""
    z = ref.sizes(cfg)
    layer = MambaMixer(z["hidden"], z["inner"], z["state"], z["rank"],
                       z["taps"], eps=z["eps"])
    p = dict(ref.init_params(cfg, jax.random.key(seed))[1][1])
    for i, name in enumerate(("dt_norm", "B_norm", "C_norm")):
        p[name] = 1.0 + 0.3 * jax.random.normal(jax.random.key(90 + i),
                                                p[name].shape)
    shapes = jax.eval_shape(layer._init, jax.random.key(0))
    assert {k: v.shape for k, v in p.items()} == \
        {k: v.shape for k, v in shapes.items()}
    return z, layer, p


def _x(t, seed=11, rows=1, hidden=32):
    return jax.random.normal(jax.random.key(seed), (rows, t, hidden))


@pytest.mark.parametrize("length", [1, 3, 4, 9, 16, 21])
def test_whole_sequence_equals_the_position_by_position_recurrence(
        length, float32_policy):
    z, layer, p = _mixer(small_cfg())
    x = _x(length, rows=2)
    got, _ = layer.apply(p, {}, x)
    for b in range(2):
        np.testing.assert_allclose(got[b], ref.mamba(z, p, x[b], "f32"),
                                   atol=TOL)


def test_bfloat16_policy_stays_within_its_stated_tolerance():
    """The published dtype: parameters and the four products' operands
    bfloat16, the recurrence float32."""
    prior = get_policy()
    set_policy(DTypePolicy(param_dtype=jnp.bfloat16,
                           compute_dtype=jnp.bfloat16))
    try:
        cfg = small_cfg(param_dtype="bfloat16", compute_dtype="bfloat16")
        z, layer, p = _mixer(cfg)
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
        x = _x(19)
        got, _ = layer.apply(p, {}, x)
        assert got.dtype == jnp.bfloat16
        with jax.default_matmul_precision("highest"):
            want = ref.mamba(z, p, x[0], "f32")
        gap = float(jnp.abs(got[0].astype(jnp.float32) - want).max())
        assert 0 < gap < BF16_TOL, gap
    finally:
        set_policy(prior)


@pytest.mark.parametrize("lengths", [(16, 9), (3, 16), (1, 7), (13, 13)])
def test_a_group_of_unequal_prompts_then_steps_through_the_cache(
        lengths, float32_policy):
    """Two prompts of unequal lengths in one bucket of 16 enter rows 2 and 0
    of a cache that holds other sequences' state: a pad moves neither the
    state nor the convolution's tail, both leaves of each row are what the
    recurrence gives after the row's real positions from nothing, the third
    row is untouched, and four steps on equal the reference's
    continuation."""
    z, layer, p = _mixer(small_cfg())
    x = _x(20, seed=5, rows=2)
    spec = layer.decode_state(3, 8)
    cache = {n: jax.random.normal(jax.random.key(i), leaf.shape)
             for i, (n, leaf) in enumerate(spec.items())}
    slot, length = jnp.array([2, 0]), jnp.array(lengths)
    # pads hold other tokens' activations, not zeros
    y, new = layer.decode_prefill(p, x[:, :16], cache, slot, length)
    zero = (jnp.zeros(spec["ssm"].shape[1:]),
            jnp.zeros(spec["conv"].shape[1:]))
    carried = {}
    for b, (s, n) in enumerate(zip((2, 0), lengths)):
        want_y, (h, window) = ref.mamba(z, p, x[b, :n], "f32", zero)
        np.testing.assert_allclose(y[b, :n], want_y, atol=TOL)
        np.testing.assert_allclose(new["ssm"][s], h, atol=TOL)
        np.testing.assert_allclose(new["conv"][s], window, atol=TOL)
        carried[b] = (h, window)
    for n in cache:
        np.testing.assert_array_equal(new[n][1], cache[n][1])
    more = x[:, 16:20]
    want = [ref.mamba(z, p, more[b], "f32", carried[b])[0] for b in (0, 1)]
    for t in range(4):
        step_x = jnp.stack([more[1, t], jnp.zeros(32), more[0, t]])[:, None]
        y, new = layer.decode_step(
            p, step_x, new,
            jnp.array([lengths[1] + t, -1, lengths[0] + t]))
        np.testing.assert_allclose(y[2, 0], want[0][t], atol=TOL)
        np.testing.assert_allclose(y[0, 0], want[1][t], atol=TOL)


def test_a_fill_up_row_writes_nothing(float32_policy):
    """A group program wider than its requests: the row past them has a
    slot past the cache's rows and a length of 0, and no row of either leaf
    changes for it."""
    _, layer, p = _mixer(small_cfg())
    x = _x(8, seed=7, rows=2)
    spec = layer.decode_state(2, 8)
    cache = {n: jax.random.normal(jax.random.key(i), leaf.shape)
             for i, (n, leaf) in enumerate(spec.items())}
    _, new = layer.decode_prefill(p, x, cache, jnp.array([1, 2]),
                                  jnp.array([5, 0]))
    for n in cache:
        np.testing.assert_array_equal(new[n][0], cache[n][0])
        assert float(jnp.abs(new[n][1] - cache[n][1]).max()) > 0


def test_the_leaves_as_declared():
    """``ssm`` float32 ``[rows, N, d_inner]`` whatever the cache's dtype,
    the channels last; ``conv`` the cache's; neither has a length axis."""
    from bigdl_tpu.models import decode as kv
    from bigdl_tpu.nn import Sequential
    _, layer, _ = _mixer(small_cfg())
    spec = layer.decode_state(5, 99)
    assert spec["ssm"] == ((5, 8, 64), None, "ssm_state", jnp.float32)
    assert spec["conv"] == ((5, 3, 64), None, "latent_cache", None)
    (avals,) = kv.cache_avals(Sequential().add(layer), 5, 99, jnp.bfloat16)
    assert avals["ssm"].dtype == jnp.float32
    assert avals["conv"].dtype == jnp.bfloat16
    total, fixed = kv.state_bytes_per_row(Sequential().add(layer), 99,
                                          jnp.bfloat16)
    assert total == fixed == 8 * 64 * 4 + 3 * 64 * 2


# ------------------------------------------------------------ the scan


def _scan_inputs(rows, T, N, C, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return (jax.nn.softplus(f(rows, T, C)), f(rows, T, C), f(rows, T, N),
            f(rows, T, N), -jnp.exp(0.3 * f(N, C)), f(C))


@pytest.mark.parametrize("rows,T", [(1, 8), (2, 20), (3, 64), (2, 130)])
def test_the_scan_equals_the_recurrence(rows, T):
    """The chunked ``lax.scan`` against ``recur`` position by position:
    whole and partial chunks of ``_UNROLL`` positions, more than one."""
    delta, x, B, C, A, D = _scan_inputs(rows, T, 16, 256)
    y1, h1 = ssm.selective_scan(delta, x, B, C, A, D)
    h, ys = jnp.zeros((rows, 16, 256), jnp.float32), []
    for t in range(T):
        h, y = ssm.recur(h, delta[:, t], x[:, t], B[:, t], C[:, t], A, D)
        ys.append(y)
    np.testing.assert_allclose(y1, jnp.stack(ys, axis=1), atol=2e-5)
    np.testing.assert_allclose(h1, h, atol=2e-5)


def _kernel(*operands):
    """The Pallas form, interpreted on the CPU."""
    return ssm._pallas(*operands, True)


FORMS = {"scan": ssm._scan, "kernel": _kernel}


@pytest.mark.parametrize("rows,T,channels", [
    (1, 8, 128),        # one iteration of eight positions
    (1, 5, 128),        # fewer positions than an iteration
    (2, 64, 256),       # a whole chunk
    (2, 130, 256),      # two chunks and part of a third
    (4, 128, 128),      # a group of four rows, two whole chunks
    (1, 72, 384),       # three blocks of 128 channels
    (2, 20, 1280),      # five blocks of 256
])
def test_the_kernel_equals_the_scan_and_the_recurrence(rows, T, channels):
    """The Pallas kernel a TPU takes (interpreted here) against the
    ``lax.scan`` form and against ``recur`` position by position: ``y`` at
    every position and the state after the last."""
    delta, x, B, C, A, D = _scan_inputs(rows, T, 16, channels, seed=T)
    y_k, h_k = _kernel(delta, x, B, C, A, D)
    y_s, h_s = ssm._scan(delta, x, B, C, A, D)
    assert y_k.shape == (rows, T, channels)
    assert h_k.shape == (rows, 16, channels)
    h, ys = jnp.zeros((rows, 16, channels), jnp.float32), []
    for t in range(T):
        h, y = ssm.recur(h, delta[:, t], x[:, t], B[:, t], C[:, t], A, D)
        ys.append(y)
    for y_w, h_w in ((y_s, h_s), (jnp.stack(ys, axis=1), h)):
        np.testing.assert_allclose(y_k, y_w, atol=2e-5)
        np.testing.assert_allclose(h_k, h_w, atol=2e-5)


@pytest.mark.parametrize("channels,form", [(128, "kernel"), (1280, "kernel"),
                                           (64, "scan"), (200, "scan")])
def test_the_backend_and_the_width_choose_the_form(channels, form,
                                                   monkeypatch):
    """On a TPU a channel count that fills whole lanes takes the kernel and
    any other the ``lax.scan`` form; off a TPU every width takes the
    latter."""
    taken = []
    monkeypatch.setattr(ssm, "_pallas", lambda *a, f=ssm._pallas:
                        taken.append("kernel") or f(*a, True))
    monkeypatch.setattr(ssm, "_scan",
                        lambda *a, f=ssm._scan: taken.append("scan") or f(*a))
    operands = _scan_inputs(1, 8, 4, channels)
    want = FORMS["scan"](*operands)
    ssm.selective_scan(*operands)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = ssm.selective_scan(*operands)
    assert taken == ["scan", form]
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_position_with_no_step_moves_nothing(form):
    """A prompt's pads have ``delta = 0``: the last state is the state after
    the last real position (here the ninth of 16, and of 80: past a chunk
    of the kernel), bit for bit what a scan of the real positions gives."""
    scan = FORMS[form]
    for T, real in ((16, 9), (80, 9), (80, 70)):
        delta, x, B, C, A, D = _scan_inputs(2, T, 8, 128, seed=4)
        delta = delta.at[:, real:].set(0.0)
        _, last = scan(delta, x, B, C, A, D)
        _, at = scan(delta[:, :real], x[:, :real], B[:, :real], C[:, :real],
                     A, D)
        np.testing.assert_array_equal(last, at)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_fill_up_row_of_the_scan_moves_nothing(form):
    """A group's fill-up row has ``delta = 0`` at every position: its state
    stays zero, its ``y`` is ``D x``, and the rows beside it read what they
    read alone."""
    delta, x, B, C, A, D = _scan_inputs(4, 24, 8, 128, seed=6)
    delta = delta.at[2].set(0.0)
    y, last = FORMS[form](delta, x, B, C, A, D)
    np.testing.assert_array_equal(last[2], jnp.zeros_like(last[2]))
    np.testing.assert_allclose(y[2], D * x[2], atol=1e-6)
    for b in (0, 1, 3):
        y_b, last_b = FORMS[form](delta[b:b + 1], x[b:b + 1], B[b:b + 1],
                                  C[b:b + 1], A, D)
        np.testing.assert_array_equal(y[b], y_b[0])
        np.testing.assert_array_equal(last[b], last_b[0])


def test_the_kernel_is_differentiated_through_the_scan_form():
    """A Pallas call has no derivative of its own: the kernel's is the
    ``lax.scan`` form's, so a model that trains on a TPU still does."""
    operands = _scan_inputs(2, 20, 8, 128, seed=8)

    def loss(form):
        def f(*a):
            y, last = form(*a)
            return jnp.sum(jnp.square(y)) + jnp.sum(last)
        return jax.grad(f, argnums=tuple(range(6)))(*operands)

    for got, want in zip(loss(_kernel), loss(ssm._scan)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_flops_counts_the_kernel_by_its_stated_cost():
    """``utils/flops`` reads a Pallas call's ``cost_estimate``: the
    equation's operations over the padded positions (20 become 24 of eight
    an iteration; 130 become three chunks of 64), seven a state element and
    three a channel, where the ``lax.scan`` form's elementwise work counts
    nothing."""
    from bigdl_tpu.utils.flops import fn_flops
    for rows, T, padded in ((2, 20, 24), (1, 130, 192)):
        operands = _scan_inputs(rows, T, 16, 256)
        assert fn_flops(_kernel, *operands) == \
            rows * padded * 256 * (7 * 16 + 3)
        assert fn_flops(ssm._scan, *operands) == 0.0


def test_flops_counts_the_mixer(float32_policy):
    """``utils/flops`` on the mixer: its four products (elementwise work,
    the scan's among it, is not counted)."""
    from bigdl_tpu.utils.flops import fn_flops
    z, layer, p = _mixer(small_cfg())
    T, d, c, n, r = 24, z["hidden"], z["inner"], z["state"], z["rank"]
    got = fn_flops(lambda p, x: layer.apply(p, {}, x)[0], p, _x(T))
    assert got == 2.0 * T * (d * 2 * c + c * (r + 2 * n) + r * c + c * d)
