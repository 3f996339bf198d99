"""Regression tests for the round-3 advisor findings."""

import numpy as np
import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import scale_epoch


def test_direct_scale_assignment_bumps_epoch():
    """finding 1: m.scale_w = x (no setter) must invalidate cached trees."""
    lin = nn.Linear(4, 3)
    lin.build(jax.random.PRNGKey(0))
    assert lin._grad_scale_tree() is None  # all-ones fast path, cached
    before = scale_epoch()
    lin.scale_w = 2.0  # direct attribute assignment, not set_scale_w
    assert scale_epoch() > before
    tree = lin._grad_scale_tree()
    assert tree is not None
    assert float(tree["weight"]) == 2.0 and float(tree["bias"]) == 1.0


def test_dense_hoist_cap(monkeypatch):
    """finding 2: the HBM hoist cap applies to dense cells, and the fallback
    scan path computes the same values."""
    cell = nn.LSTM(8, 16)
    params, _ = cell.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (12, 4, 8))  # (T, B, I)

    proj = cell.project_inputs(params, xs)
    assert proj is not None  # under the default cap: hoisted

    monkeypatch.setenv("BIGDL_TPU_RNN_HOIST_MAX_ELEMENTS", "16")
    assert cell.project_inputs(params, xs) is None  # capped out
    # t == 1 exemption (Cell.step delegation must keep working)
    assert cell.project_inputs(params, xs[:1]) is not None

    rec_capped = nn.Recurrent().add(cell)
    y_capped = rec_capped.forward(jnp.swapaxes(xs, 0, 1))
    monkeypatch.delenv("BIGDL_TPU_RNN_HOIST_MAX_ELEMENTS")
    rec = nn.Recurrent().add(cell)
    rec.params = rec_capped.params
    rec.state = rec_capped.state
    y_hoisted = rec.forward(jnp.swapaxes(xs, 0, 1))
    np.testing.assert_allclose(np.asarray(y_capped), np.asarray(y_hoisted),
                               rtol=1e-5, atol=1e-6)


def test_preemption_armed_without_main_thread(tmp_path):
    """finding 3: arming is derived from rank-consistent inputs, so a
    non-main thread (where signal.signal raises) still arms."""
    import threading

    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.optim.trigger import Trigger as _T

    rng = np.random.default_rng(0)
    samples = [Sample.from_ndarray(rng.normal(size=(4,)).astype(np.float32),
                                   np.int32(rng.integers(0, 2)))
               for _ in range(16)]
    model = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    ds = DataSet.array(samples).transform(SampleToMiniBatch(8))
    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(SGD(0.1))
           .set_end_when(Trigger.max_epoch(1))
           .set_checkpoint(str(tmp_path), _T.every_epoch()))
    armed = {}

    def run():
        opt.optimize()
        armed["value"] = opt._preemption_armed

    t = threading.Thread(target=run)
    t.start()
    t.join(120)
    assert armed.get("value") is True


def test_evaluator_peek_does_not_drop_generator_sample():
    """finding 4: one-shot generator-backed datasets keep their first sample
    through Evaluator's batch-size autodetect peek."""
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.optim import Evaluator, Top1Accuracy

    n = 10
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(n, 4)).astype(np.float32)
    labels = np.arange(n) % 2

    class OneShot:
        """Minimal dataset whose data() is a single-use generator."""

        def __init__(self):
            self._used = False

        def size(self):
            return n

        def transform(self, transformer):
            from bigdl_tpu.dataset import TransformedDataSet
            return TransformedDataSet(self, transformer)

        def data(self, train=False):
            assert not self._used, "one-shot source iterated twice"
            self._used = True
            return (Sample.from_ndarray(feats[i], np.int32(labels[i]))
                    for i in range(n))

    model = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    model.build(jax.random.PRNGKey(0))
    res = Evaluator(model).test(OneShot(), [Top1Accuracy()])
    counted = res[0][1].result()[1] if hasattr(res[0][1], "result") else None
    # every one of the n samples must be evaluated — the peeked one included
    assert int(getattr(res[0][1], "count", counted)) == n
