"""Fused train-step arithmetic (ISSUE 7 tentpole): multi-tensor optimizer
update (optim/fused.py) and the bucketed bf16 gradient wire
(parallel/wire.py).

The contract under test: fusing changes the compiled program's granularity
(a handful of large kernels instead of one per leaf), never the scalar
expression each element sees.  One update of each method is BIT-identical
(test_method_fused_update_bitwise).  A whole 5-step training run agrees to
float tolerance, not bitwise: XLA contracts multiply-adds differently in
differently shaped loops, and under ZeRO (ShardedDataParallel) the
bucket/buffer sharding constraints also change how GSPMD decomposes the
cross-device gradient reduction (`_assert_parity` says what is held to
what).

Also pins the wire/clip ORDERING: clipping always sees wire-rounded
gradients (compress-then-aggregate, docs/performance.md "Step arithmetic
& overlap"); the bucketed wire must preserve that bit-for-bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.common import set_seed
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import Adam, Optimizer, SGD, Trigger
from bigdl_tpu.optim.method import Adadelta, Adagrad, Adamax, LBFGS, RMSprop
from bigdl_tpu.optim import fused as fused_mod
from bigdl_tpu.parallel import wire as wire_mod
from bigdl_tpu.parallel.sharding import DataParallel, ShardedDataParallel
from bigdl_tpu.utils.engine import Engine


def _tree(seed=0):
    """A mixed-dtype pytree shaped like a small model's params."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "conv": {"weight": jax.random.normal(k[0], (5, 5, 1, 6)),
                 "bias": jax.random.normal(k[1], (6,))},
        "bn": {"weight": jax.random.normal(k[2], (6,), jnp.bfloat16)},
        "fc": [jax.random.normal(k[3], (84, 10)),
               jax.random.normal(k[4], (10,), jnp.bfloat16)],
    }


def _assert_bitwise(a, b, msg=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype, msg
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# ----------------------------------------------------------------------
# layout / fuse / unfuse
# ----------------------------------------------------------------------

def test_fuse_unfuse_roundtrip_bitwise():
    t = _tree()
    layout = fused_mod.plan(t)
    # one buffer per dtype present (f32 + bf16 here)
    assert len(layout.groups) == 2
    bufs = fused_mod.fuse(layout, t)
    assert all(b.ndim == 1 for b in bufs)
    assert sum(int(b.size) for b in bufs) == sum(layout.sizes)
    _assert_bitwise(fused_mod.unfuse(layout, bufs), t, "roundtrip")


def test_layout_matches_rejects_scalars_and_shape_drift():
    t = _tree()
    layout = fused_mod.plan(t)
    assert layout.matches(jax.tree.map(jnp.zeros_like, t))
    # same structure, different leaf shape => not a param-shaped slot tree
    bad = jax.tree.map(lambda x: jnp.zeros(x.size), t)
    assert not layout.matches(bad)
    # scalar state (Adam's t counter) must never fuse
    single = {"w": jnp.ones((4, 4))}
    l2 = fused_mod.plan(single)
    assert not l2.matches({"w": jnp.float32(3.0)})


def test_single_leaf_per_dtype_falls_back():
    """Nothing to fuse => the per-leaf update runs (no added reshapes)."""
    m = SGD(0.1)
    p = {"w": jnp.ones((8,))}
    g = {"w": jnp.full((8,), 0.5)}
    s = m.init_state(p)
    ref = m.update(g, p, s, 0.1)
    out = m.update_fused(g, p, s, 0.1)
    _assert_bitwise(out[0], ref[0])
    _assert_bitwise(out[1], ref[1])


# ----------------------------------------------------------------------
# per-method bit parity
# ----------------------------------------------------------------------

@pytest.mark.parametrize("method", [
    SGD(0.1, momentum=0.9, weight_decay=1e-4),
    Adam(1e-3),
    Adagrad(1e-2),
    Adadelta(),
    Adamax(2e-3),
    RMSprop(1e-3),
], ids=lambda m: type(m).__name__)
def test_method_fused_update_bitwise(method):
    p = _tree(1)
    g = jax.tree.map(lambda x: (x * 0.01).astype(x.dtype), _tree(2))
    s = method.init_state(p)
    lr = method.get_learning_rate()
    p_ref, s_ref = method.update(g, p, s, lr)
    p_f, s_f = method.update_fused(g, p, s, lr)
    _assert_bitwise(p_f, p_ref, type(method).__name__)
    _assert_bitwise(s_f, s_ref, type(method).__name__ + " state")
    # second step from the fused state keeps agreeing (slot trees took the
    # roundtrip once already)
    p_ref2, s_ref2 = method.update(g, p_ref, s_ref, lr)
    p_f2, s_f2 = method.update_fused(g, p_f, s_f, lr)
    _assert_bitwise(p_f2, p_ref2, type(method).__name__ + " step2")
    _assert_bitwise(s_f2, s_ref2, type(method).__name__ + " state2")


def test_lbfgs_opts_out():
    m = LBFGS()
    assert m.supports_fused is False
    p = {"w": jnp.ones((6,)), "v": jnp.ones((3, 2))}
    g = jax.tree.map(lambda x: x * 0.1, p)
    s = m.init_state(p)
    ref = m.update(g, p, s, 1.0)
    out = m.update_fused(g, p, s, 1.0)  # silently the per-leaf path
    _assert_bitwise(out[0], ref[0])


# ----------------------------------------------------------------------
# bucketed gradient wire
# ----------------------------------------------------------------------

def test_bucket_assignment_caps_and_order():
    sizes = [100, 200, 50, 1000, 10]
    itemsize = 2  # bf16
    cap_mb = 600 * 2 / (1 << 20)  # 600 elements
    buckets = wire_mod.bucket_assignment(sizes, itemsize, cap_mb)
    assert [i for b in buckets for i in b] == list(range(len(sizes)))
    for b in buckets:
        elems = sum(sizes[i] for i in b)
        assert elems <= 600 or len(b) == 1  # oversized leaf rides alone
    assert buckets == [[0, 1, 2], [3], [4]]


def test_wire_cast_bucketed_bitwise():
    g = _tree(3)
    ref = wire_mod.wire_cast(g, jnp.bfloat16, 0.0)
    for mb in (0.001, 0.01, 1024.0):
        out = wire_mod.wire_cast(g, jnp.bfloat16, mb)
        _assert_bitwise(out, ref, f"bucket_mb={mb}")


def test_wire_cast_none_passthrough():
    g = _tree(4)
    assert wire_mod.wire_cast(g, None, 8.0) is g


# ----------------------------------------------------------------------
# end-to-end parity (the acceptance criterion)
# ----------------------------------------------------------------------

def _samples(n=128, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(0.0, 0.1, size=(n, 28, 28, 1)).astype(np.float32)
    ys = rng.integers(0, 10, size=n)
    return [Sample(xs[i], np.int32(ys[i])) for i in range(n)]


class _LossCapture:
    def __init__(self):
        self.losses = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(float(value))


def _resnet_block_model():
    """A ResNet-style model small enough for 5 CPU steps: conv stem, one
    basic residual block, pool, linear head."""
    from bigdl_tpu.models.resnet import ShortcutType, _basic_block
    set_seed(11)
    m = nn.Sequential()
    m.add(nn.SpatialConvolution(1, 8, 3, 3, 2, 2, 1, 1))
    m.add(nn.SpatialBatchNormalization(8))
    m.add(nn.ReLU())
    blk, _ = _basic_block(8, 8, 1, ShortcutType.B)
    m.add(blk)
    m.add(nn.Reshape([14 * 14 * 8]))
    m.add(nn.Linear(14 * 14 * 8, 10))
    m.add(nn.LogSoftMax())
    return m


def _train(model_fn, steps=5, strategy=None, clip_norm=None):
    set_seed(7)
    model = model_fn()
    ds = DataSet.array(_samples()).transform(
        SampleToMiniBatch(32, drop_last=True))
    cap = _LossCapture()
    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(cap))
    if strategy is not None:
        opt.set_strategy(strategy)
    if clip_norm is not None:
        opt.set_gradient_clipping_by_l2_norm(clip_norm)
    opt.optimize()
    return cap.losses, [np.asarray(l) for l in jax.tree.leaves(model.params)]


def _lenet():
    from bigdl_tpu.models import LeNet5
    return LeNet5(10)


@pytest.fixture(autouse=True)
def _fresh_engine():
    Engine.reset()
    yield
    Engine.reset()


_LR, _STEPS = 1e-3, 5   # what _train runs: Adam(1e-3), five steps

#: the block model's conv biases that sit in front of a BatchNorm.  BN takes
#: the batch mean out again, so their true gradient is ZERO and what reaches
#: Adam is rounding noise (test_noise_leaves_are_the_conv_biases_before_bn);
#: Adam's m/sqrt(v) turns noise of any size into steps of +-lr.  Two programs
#: that round differently therefore move these three leaves apart by whole
#: Adam steps while every other leaf, and the loss, agrees to float tolerance.
_NOISE_LEAVES = {
    "_resnet_block_model": ("[0]['bias']", "[3][0][0][0]['bias']",
                            "[3][0][0][3]['bias']"),
}


def _leaf_names(model_fn):
    """Key paths of the model's parameter leaves, in `_train`'s order."""
    params = model_fn().build(jax.random.PRNGKey(0)).params
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def _assert_parity(model_fn, losses1, params1, losses0, params0):
    """Fused against per-leaf after `_STEPS` of Adam(`_LR`): every leaf to
    rtol 1e-4 / atol 1e-5 and the losses to 1e-5.

    Not bitwise: the two programs hand XLA differently shaped elementwise
    loops (one fused buffer against one loop a leaf), and whether it
    contracts a multiply-add differs between them and between compiler
    versions.  The named `_NOISE_LEAVES` alone are held to nothing tighter
    than the distance Adam can travel, 2 * lr * steps."""
    np.testing.assert_allclose(losses1, losses0, rtol=1e-5)
    names = _leaf_names(model_fn)
    noise = _NOISE_LEAVES.get(model_fn.__name__, ())
    assert set(noise) <= set(names) and len(names) == len(params1)
    for name, a, b in zip(names, params1, params0):
        a, b = a.astype(np.float32), b.astype(np.float32)
        if name in noise:
            assert np.abs(a - b).max() <= 2 * _LR * _STEPS, name
        else:
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                       err_msg=name)


def test_noise_leaves_are_the_conv_biases_before_bn():
    """Why `_assert_parity` lets three leaves go: their gradient is rounding
    noise, orders of magnitude under every other leaf's, so there is no
    signal in them for the two programs to agree on."""
    model = _resnet_block_model().build(jax.random.PRNGKey(0))
    batch = _samples()[:32]
    x = jnp.stack([s.feature for s in batch])
    y = jnp.asarray([int(s.label) for s in batch])
    criterion = nn.ClassNLLCriterion()

    def loss(params):
        out, _ = model.apply(params, model.state, x, training=True)
        return criterion.forward(out, y)

    grads = jax.grad(loss)(model.params)
    size = {jax.tree_util.keystr(path): float(jnp.abs(g).max()) for path, g
            in jax.tree_util.tree_flatten_with_path(grads)[0]}
    noise = _NOISE_LEAVES["_resnet_block_model"]
    assert max(size[n] for n in noise) < 1e-4
    assert min(v for n, v in size.items() if n not in noise) > 1e-2


@pytest.mark.parametrize("model_fn", [_lenet, _resnet_block_model],
                         ids=["lenet", "resnet_block"])
def test_fused_update_parity_data_parallel(model_fn, monkeypatch):
    """Acceptance: 5-step LeNet and a ResNet-block model, pure DP — the
    fused update agrees with the per-leaf path to float tolerance
    (bit-identical under some XLA versions, not under jax 0.9.0's: step 4
    of LeNet reads 2.7044744 against 2.7044747)."""
    Engine.init()
    losses0, params0 = _train(model_fn)
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
    losses1, params1 = _train(model_fn)
    _assert_parity(model_fn, losses1, params1, losses0, params0)


@pytest.mark.parametrize("model_fn", [_lenet, _resnet_block_model],
                         ids=["lenet", "resnet_block"])
def test_fused_update_parity_zero(model_fn, monkeypatch):
    """Acceptance: the same runs under ZeRO (ShardedDataParallel).  The
    fused buffers' P('data') sharding constraint changes how GSPMD
    decomposes the cross-device reduction, so parity is the documented
    float tolerance (reassociation-level), not bitwise."""
    Engine.init()
    losses0, params0 = _train(
        model_fn, strategy=ShardedDataParallel(min_size=1))
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
    losses1, params1 = _train(
        model_fn, strategy=ShardedDataParallel(min_size=1))
    _assert_parity(model_fn, losses1, params1, losses0, params0)


def test_bucketed_wire_parity_and_clip_ordering(monkeypatch):
    """The bucketed wire is bit-identical to the per-leaf wire, INCLUDING
    under L2-norm clipping — which proves the ordering: the norm is
    computed on wire-rounded grads either way (wire-before-clip).  If the
    bucketed path clipped first, the bf16 rounding of already-scaled
    grads would diverge bitwise within a step."""
    Engine.init()
    for clip in (None, 1.0):
        monkeypatch.delenv("BIGDL_TPU_WIRE_BUCKET_MB", raising=False)
        losses0, params0 = _train(_lenet, clip_norm=clip)
        monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "0.25")
        losses1, params1 = _train(_lenet, clip_norm=clip)
        assert losses1 == losses0, f"clip={clip}"
        for a, b in zip(params1, params0):
            np.testing.assert_array_equal(a, b, err_msg=f"clip={clip}")


def test_bucketed_wire_with_fused_update_and_zero(monkeypatch):
    """All three knobs at once (bucketed wire + fused update + ZeRO): the
    full fused-arithmetic step trains to the same losses within the
    documented ZeRO tolerance."""
    Engine.init()
    losses0, params0 = _train(
        _lenet, strategy=ShardedDataParallel(min_size=1))
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "0.25")
    losses1, params1 = _train(
        _lenet, strategy=ShardedDataParallel(min_size=1))
    np.testing.assert_allclose(losses1, losses0, rtol=1e-5)
    for a, b in zip(params1, params0):
        np.testing.assert_allclose(
            a.astype(np.float32), b.astype(np.float32),
            rtol=1e-4, atol=1e-5)


def test_step_knobs_recorded(monkeypatch):
    """_build_step records the knobs it was traced with; the compile card
    carries them."""
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "4")
    Engine.init(devices=[jax.devices()[0]])
    model = _lenet()
    model.build(jax.random.PRNGKey(0))
    opt = Optimizer(model, dataset=None, criterion=nn.ClassNLLCriterion(),
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(0.1))
    opt._build_step(Engine.mesh())
    assert opt._step_knobs == {"fused_update": True, "wire_bucket_mb": 4.0,
                               "donate": True}
