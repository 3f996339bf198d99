"""End-to-end training: LeNet-5 on synthetic MNIST over an 8-device CPU mesh.

Mirrors the reference's DistriOptimizerSpec (SURVEY.md §4): node-count is a
parameter — the same distributed machinery (sharded batch, replicated params,
XLA all-reduce) runs on 8 virtual CPU devices exactly as it would on 8 TPU
chips.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import Engine
from bigdl_tpu.common import set_seed
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.models import LeNet5
from bigdl_tpu.optim import (Adam, SGD, Optimizer, Trigger, Top1Accuracy,
                             Evaluator, Predictor)
from bigdl_tpu.parallel import DataParallel, ShardedDataParallel


def synthetic_mnist(n=512, seed=0):
    """Separable synthetic digits: class k has a bright k-th 2x2 block."""
    rng = np.random.default_rng(seed)
    images = rng.normal(0.0, 0.1, size=(n, 28, 28)).astype(np.float32)
    labels = rng.integers(0, 10, size=n)
    for i, l in enumerate(labels):
        r, c = divmod(int(l), 5)
        images[i, 4 + r * 10: 12 + r * 10, 2 + c * 5: 7 + c * 5] += 1.5
    return [Sample.from_ndarray(images[i], np.int32(labels[i]))
            for i in range(n)]


def make_optimizer(strategy=None, batch_size=64, samples=None):
    # the global stream is whatever this worker's previous test left behind
    # (final losses 0.77 to 1.0013 over a handful of seeds, PR 22): seed it,
    # so the thresholds below test the code and not the order of the files
    set_seed(0)
    model = LeNet5(10)
    ds = DataSet.array(samples or synthetic_mnist()) \
        .transform(SampleToMiniBatch(batch_size, drop_last=True))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(),
                    strategy=strategy or DataParallel())
    opt.set_optim_method(Adam(learning_rate=1e-3))
    opt.set_end_when(Trigger.max_epoch(3))
    opt.set_log_interval(4)
    return model, opt


def test_lenet_trains_on_8_device_mesh():
    Engine.init()  # all 8 virtual CPU devices on the 'data' axis
    assert Engine.device_count() == 8
    model, opt = make_optimizer()
    opt.optimize()
    # loss must have dropped well below random (ln(10) ~ 2.3)
    assert opt.optim_method.hyper["loss"] < 1.0
    # evaluate
    val = synthetic_mnist(256, seed=1)
    ds = DataSet.array(val)
    results = Evaluator(model).test(ds, [Top1Accuracy()], batch_size=64)
    acc, n = results[0][1].result()
    assert n == 256
    assert acc > 0.8, f"accuracy {acc}"


def test_lenet_sharded_data_parallel():
    Engine.init()
    model, opt = make_optimizer(strategy=ShardedDataParallel(min_size=1),
                                samples=synthetic_mnist(256))
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    assert np.isfinite(opt.optim_method.hyper["loss"])


def test_lenet_remat_conv_out():
    """set_remat('conv_out') saves only MXU conv outputs across fwd/bwd
    (nn/conv tags them with checkpoint_name); training must still converge
    identically in expectation — the policy changes the schedule, not math."""
    Engine.init()
    model, opt = make_optimizer()
    opt.set_remat("conv_out")
    opt.optimize()
    assert opt.optim_method.hyper["loss"] < 1.0


def test_checkpoint_and_resume(tmp_path):
    Engine.init()
    model, opt = make_optimizer(samples=synthetic_mnist(128))
    opt.set_end_when(Trigger.max_epoch(1))
    opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(1))
    opt.optimize()
    from bigdl_tpu.utils import file_io
    latest = file_io.latest_checkpoint(str(tmp_path))
    assert latest is not None
    blob = file_io.load(latest[0])
    assert "params" in blob and "state" in blob
    # weights roundtrip
    w0 = jax.tree.leaves(blob["params"])[0]
    assert np.all(np.isfinite(np.asarray(w0)))


def test_predictor():
    Engine.init()
    model = LeNet5(10).build()
    pred = Predictor(model, batch_size=32)
    x = np.random.default_rng(0).normal(size=(50, 28, 28)).astype(np.float32)
    ds = DataSet.array([Sample.from_ndarray(x[i]) for i in range(50)])
    probs = pred.predict(ds)
    assert probs.shape == (50, 10)
    classes = pred.predict_class(ds)
    assert classes.shape == (50,) and classes.min() >= 0 and classes.max() < 10


def test_validation_during_training():
    Engine.init()
    samples = synthetic_mnist(256)
    model, opt = make_optimizer(samples=samples)
    opt.set_end_when(Trigger.max_epoch(2))
    val_ds = DataSet.array(synthetic_mnist(128, seed=2))
    opt.set_validation(Trigger.every_epoch(), val_ds, [Top1Accuracy()],
                       batch_size=64)
    opt.optimize()
    assert "score" in opt.optim_method.hyper


def test_lr_schedule_advances_during_training():
    """Regression: driver state must feed evalCounter to the schedule family."""
    from bigdl_tpu.optim import Step
    Engine.init()
    samples = synthetic_mnist(128)
    model = LeNet5(10)
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32, drop_last=True))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion())
    sgd = SGD(learning_rate=0.1, learning_rate_schedule=Step(2, 0.5))
    opt.set_optim_method(sgd)
    opt.set_end_when(Trigger.max_iteration(5))
    opt.optimize()
    # after 5 iterations (evalCounter=5) lr must have decayed 0.1 * 0.5^2
    lr = sgd.get_learning_rate(sgd.hyper)
    assert abs(lr - 0.1 * 0.25) < 1e-9, lr


def test_gradient_accumulation_matches_full_batch():
    """set_gradient_accumulation(4): microbatched grads averaged inside the
    step must reproduce the full-batch trajectory on an rng-free model
    (differences are float reassociation only)."""
    from bigdl_tpu.common import set_seed

    Engine.init()
    samples = synthetic_mnist(256)

    def train(accum):
        set_seed(5)
        model, opt = make_optimizer(batch_size=64, samples=samples)
        opt.set_optim_method(SGD(learning_rate=0.05))
        opt.set_end_when(Trigger.max_epoch(1))
        if accum > 1:
            opt.set_gradient_accumulation(accum)
        opt.optimize()
        return jax.tree.leaves(jax.tree.map(np.asarray, model.params))

    base, acc = train(1), train(4)
    for a, b in zip(base, acc):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_gradient_accumulation_indivisible_batch_rejected():
    Engine.init()
    model, opt = make_optimizer(batch_size=64)
    opt.set_gradient_accumulation(7)  # 64 % 7 != 0
    with pytest.raises(ValueError, match="not divisible"):
        opt.optimize()


def test_gradient_accumulation_with_remat_and_bn():
    """accumulation composes with remat and BN state threading (each
    microbatch normalizes with its own stats; running stats advance)."""
    Engine.init()
    model = nn.Sequential() \
        .add(nn.Reshape((28, 28, 1))) \
        .add(nn.SpatialConvolution(1, 4, 3, 3, 1, 1, -1, -1)) \
        .add(nn.SpatialBatchNormalization(4)) \
        .add(nn.ReLU()) \
        .add(nn.Reshape((28 * 28 * 4,))) \
        .add(nn.Linear(28 * 28 * 4, 10)) \
        .add(nn.LogSoftMax())
    ds = DataSet.array(synthetic_mnist(256)).transform(
        SampleToMiniBatch(64, drop_last=True))
    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(learning_rate=1e-3))
           .set_end_when(Trigger.max_epoch(3))
           .set_gradient_accumulation(4)
           .set_remat("conv_out"))
    opt.optimize()
    assert opt.optim_method.hyper["loss"] < 1.0
    # BN running stats advanced through the scan
    rm = np.asarray(jax.tree.leaves(model.state)[0])
    assert np.abs(rm).sum() > 0
