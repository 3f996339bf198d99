"""Tiny-input-channel conv padding (the LeNet compile-pathology fix).

XLA's TPU backend compiles grad-of-conv at C_in=1 pathologically slowly
(docs/benchmarking.md); `_pad_tiny_cin` pads C_in up to 8 with zero channels.
These tests pin the numerics: forward values and every gradient must be
identical with the pad on (default) and off (BIGDL_TPU_CONV_PAD_MIN_CIN=0).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.nn import SpatialConvolution, SpatialDilatedConvolution


def _fwd(conv, params, x):
    return conv.apply(params, {}, x)[0]


def _loss_and_grads(monkeypatch, min_cin, seed=0):
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", str(min_cin))
    conv = SpatialConvolution(1, 6, 5, 5, pad_w=2, pad_h=2)
    params, _ = conv.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (4, 28, 28, 1))

    def loss(p, xx):
        return jnp.sum(_fwd(conv, p, xx) ** 2)

    val, grads = jax.value_and_grad(loss)(params, x)
    gx = jax.grad(loss, argnums=1)(params, x)
    return val, grads, gx


def test_pad_preserves_forward_and_grads(monkeypatch):
    v1, g1, gx1 = _loss_and_grads(monkeypatch, 8)
    v0, g0, gx0 = _loss_and_grads(monkeypatch, 0)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0), rtol=1e-5,
                               atol=1e-6)


def test_pad_changes_compiled_shapes(monkeypatch):
    """The whole point: with the pad on, the conv the compiler sees has C_in=8."""
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    conv = SpatialConvolution(1, 6, 5, 5)
    params, _ = conv.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 28, 28, 1))
    hlo = jax.jit(lambda p, xx: _fwd(conv, p, xx)).lower(params, x).as_text()
    assert "2x28x28x8" in hlo, hlo[:2000]


def test_pad_skips_wide(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    # wide input: no pad inserted
    conv = SpatialConvolution(16, 8, 3, 3)
    p, _ = conv.init(jax.random.PRNGKey(0))
    hlo = jax.jit(lambda pp, xx: _fwd(conv, pp, xx)).lower(
        p, jnp.zeros((2, 8, 8, 16))).as_text()
    assert "stablehlo.pad" not in hlo


def test_grouped_conv_pads_per_group(monkeypatch):
    """Grouped convs used to bypass the pad entirely (their grad-of-conv
    pathology included); the pad is now group-aware — each group's channel
    block is zero-extended so feature_group_count still divides."""
    g = SpatialConvolution(4, 8, 3, 3, n_group=4)
    pg, _ = g.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 4))

    def loss(p, xx):
        return jnp.sum(_fwd(g, p, xx) ** 2)

    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    v1, g1 = jax.value_and_grad(loss)(pg, x)
    gx1 = jax.grad(loss, argnums=1)(pg, x)
    # the compiler sees the padded per-group width: C_in = 4 groups x 8
    hlo = jax.jit(lambda pp, xx: _fwd(g, pp, xx)).lower(pg, x).as_text()
    assert "2x8x8x32" in hlo, hlo[:2000]
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
    v0, g0 = jax.value_and_grad(loss)(pg, x)
    gx0 = jax.grad(loss, argnums=1)(pg, x)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-5, atol=1e-6)


def test_dilated_conv_inherits_pad(monkeypatch):
    conv = SpatialDilatedConvolution(1, 4, 3, 3, dilation_w=2, dilation_h=2)
    p, _ = conv.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, 1))
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    y_on = _fwd(conv, p, x)
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
    y_off = _fwd(conv, p, x)
    np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off), rtol=1e-6)


def test_other_conv_families_inherit_pad(monkeypatch):
    """Temporal (WIO), Volumetric (DHWIO) and Full (lhs-dilated) convs get the
    same treatment — the Full conv's forward IS a gradient-conv-shaped program."""
    from bigdl_tpu.nn import (SpatialFullConvolution, TemporalConvolution,
                              VolumetricConvolution)
    cases = [
        (TemporalConvolution(1, 4, 3), jax.random.normal(
            jax.random.PRNGKey(1), (2, 16, 1))),
        (VolumetricConvolution(1, 4, 3, 3, 3), jax.random.normal(
            jax.random.PRNGKey(2), (2, 8, 8, 8, 1))),
        (SpatialFullConvolution(1, 4, 3, 3, stride_w=2, stride_h=2),
         jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 1))),
    ]
    for conv, x in cases:
        p, _ = conv.init(jax.random.PRNGKey(0))
        monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
        y_on = _fwd(conv, p, x)
        hlo = jax.jit(lambda pp, xx, c=conv: _fwd(c, pp, xx)).lower(
            p, x).as_text()
        assert "stablehlo.pad" in hlo, type(conv).__name__
        monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
        y_off = _fwd(conv, p, x)
        np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=type(conv).__name__)


# ----------------------------------------------------------------------
# reshaped-matmul (im2col) route — ops/convmm.py via BIGDL_TPU_CONV_ROUTE
# ----------------------------------------------------------------------

@pytest.mark.parametrize("conv,shape", [
    # the LeNet pathology shape family: C_in=1, 5x5
    (SpatialConvolution(1, 6, 5, 5), (4, 28, 28, 1)),
    (SpatialConvolution(1, 6, 5, 5, 2, 2, pad_w=-1, pad_h=-1),
     (4, 28, 28, 1)),                                # SAME + stride
    (SpatialConvolution(2, 8, 3, 3, pad_w=1, pad_h=1), (2, 12, 12, 2)),
    (SpatialConvolution(1, 4, 1, 1), (2, 9, 9, 1)),  # 1x1 degenerate
    (SpatialDilatedConvolution(1, 4, 3, 3, dilation_w=2, dilation_h=2),
     (2, 12, 12, 1)),
], ids=["lenet5x5", "same_stride2", "pad1", "1x1", "dilated"])
def test_matmul_route_forward_and_grad_parity(monkeypatch, conv, shape):
    """Acceptance: the reshaped-matmul route matches the lax.conv route
    (pad disabled = the untouched program) on forward values and every
    gradient, at float tolerance (the contraction is reassociated)."""
    p, _ = conv.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), shape)

    def loss(pp, xx):
        return jnp.mean(_fwd(conv, pp, xx) ** 2)

    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
    v0, g0 = jax.value_and_grad(loss)(p, x)
    gx0 = jax.grad(loss, argnums=1)(p, x)
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
    v1, g1 = jax.value_and_grad(loss)(p, x)
    gx1 = jax.grad(loss, argnums=1)(p, x)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-4, atol=1e-5)


def test_matmul_route_eliminates_grad_of_conv(monkeypatch):
    """The route's point: the train-step gradient program contains NO
    convolution at all — XLA never sees the pathological grad-of-conv."""
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    conv = SpatialConvolution(1, 6, 5, 5)
    p, _ = conv.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 28, 28, 1))

    def loss(pp, xx):
        return jnp.sum(_fwd(conv, pp, xx) ** 2)

    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).as_text()
    assert "stablehlo.convolution" not in hlo
    assert "dot_general" in hlo
    # the pad route keeps the conv (and its grad-conv)
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "pad")
    hlo_pad = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).as_text()
    assert "stablehlo.convolution" in hlo_pad


def test_matmul_route_scope(monkeypatch):
    """Route selection: wide C_in stays on lax; grouped and lhs-dilated
    convs fall back to the pad (the matmul route covers the single-group
    correlation shape only)."""
    from bigdl_tpu.nn.conv import _conv_route
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
    wide = jnp.zeros((3, 3, 16, 8))
    tiny = jnp.zeros((5, 5, 1, 6))
    assert _conv_route(wide, 1) == "lax"
    assert _conv_route(tiny, 1) == "matmul"
    assert _conv_route(tiny, 4) == "pad"              # grouped
    assert _conv_route(tiny, 1, (2, 2)) == "pad"      # lhs-dilated (Full)
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "pad")
    assert _conv_route(tiny, 1) == "pad"
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "lax")
    assert _conv_route(tiny, 1) == "lax"


def test_matmul_route_bf16_policy(monkeypatch):
    """Under the bf16 compute policy the matmul route casts exactly like
    the lax route (x and w to compute dtype, f32 accumulation)."""
    from bigdl_tpu.common import DTypePolicy, get_policy, set_policy
    prev = get_policy()
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    try:
        conv = SpatialConvolution(1, 6, 5, 5)
        p, _ = conv.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 14, 14, 1))
        monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
        monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "pad")
        y_pad = _fwd(conv, p, x)
        monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
        y_mm = _fwd(conv, p, x)
        assert y_mm.dtype == y_pad.dtype
        np.testing.assert_allclose(np.asarray(y_mm, np.float32),
                                   np.asarray(y_pad, np.float32),
                                   rtol=0.05, atol=0.05)
    finally:
        set_policy(prev)


def test_lenet_trains_on_matmul_route(monkeypatch):
    """End-to-end: LeNet forwards identically on the matmul route."""
    from bigdl_tpu.models.lenet import LeNet5
    model = LeNet5(class_num=10)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28, 1))
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
    y0, _ = model.apply(params, state, x)
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    monkeypatch.setenv("BIGDL_TPU_CONV_ROUTE", "matmul")
    y1, _ = model.apply(params, state, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-6)


def test_lenet_stack_trains_with_pad(monkeypatch):
    """End-to-end: the LeNet front conv forwards identically with the pad."""
    from bigdl_tpu.models.lenet import LeNet5
    model = LeNet5(class_num=10)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28, 1))
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "8")
    y, _ = model.apply(params, state, x)
    assert y.shape == (8, 10) and bool(jnp.isfinite(y).all())
    monkeypatch.setenv("BIGDL_TPU_CONV_PAD_MIN_CIN", "0")
    y0, _ = model.apply(params, state, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-6)
