"""Optim method + schedule + trigger unit tests.

Models the reference's optimizer unit tier (SURVEY.md §4): simple reference
implementations cross-checked against the real ones (RefLocalOptimizer idea) and
LR-schedule math specs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.optim import (SGD, Adam, Adagrad, Adadelta, Adamax, RMSprop,
                             LBFGS, Trigger, Poly, Step, MultiStep, EpochStep,
                             Default, Warmup, SequentialSchedule,
                             Top1Accuracy, Top5Accuracy)


def quadratic_min(method, steps=150, tol=1e-2):
    """All methods must minimize f(x) = ||x - c||^2."""
    c = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = method.init_state(params)
    for i in range(steps):
        grads = {"w": 2 * (params["w"] - c)}
        lr = method.get_learning_rate({"evalCounter": i, "epoch": 1})
        params, state = method.update(grads, params, state, lr)
    return float(jnp.max(jnp.abs(params["w"] - c)))


@pytest.mark.parametrize("method,steps,tol", [
    (SGD(learning_rate=0.1), 100, 1e-2),
    (SGD(learning_rate=0.05, momentum=0.9), 200, 1e-2),
    (SGD(learning_rate=0.05, momentum=0.9, nesterov=True, dampening=0.0),
     200, 1e-2),
    (SGD(learning_rate=0.1, weight_decay=1e-4), 150, 2e-2),
    (Adam(learning_rate=0.1), 300, 1e-2),
    (Adagrad(learning_rate=0.5), 400, 5e-2),
    (Adadelta(epsilon=1e-2), 500, 5e-2),
    (Adamax(learning_rate=0.2), 300, 2e-2),
    (RMSprop(learning_rate=0.05), 400, 2e-2),
    (LBFGS(learning_rate=0.5), 60, 1e-2),
])
def test_methods_minimize_quadratic(method, steps, tol):
    assert quadratic_min(method, steps) < tol


def test_sgd_matches_manual_momentum():
    m = SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
    params = {"w": jnp.asarray([1.0])}
    state = m.init_state(params)
    g = {"w": jnp.asarray([1.0])}
    params, state = m.update(g, params, state, 0.1)
    np.testing.assert_allclose(np.asarray(params["w"]), [0.9])
    params, state = m.update(g, params, state, 0.1)
    # v = 0.9*1 + 1 = 1.9; w = 0.9 - 0.1*1.9 = 0.71
    np.testing.assert_allclose(np.asarray(params["w"]), [0.71], rtol=1e-6)


def test_schedules_golden():
    opt = SGD(learning_rate=0.1)
    assert Default().get_lr(opt, {"evalCounter": 0}) == 0.1
    opt2 = SGD(learning_rate=0.1, learning_rate_decay=0.1)
    np.testing.assert_allclose(
        Default().get_lr(opt2, {"evalCounter": 10}), 0.1 / 2)
    np.testing.assert_allclose(
        Poly(0.5, 100).get_lr(opt, {"evalCounter": 75}), 0.1 * 0.5)
    np.testing.assert_allclose(
        Step(10, 0.5).get_lr(opt, {"evalCounter": 25}), 0.1 * 0.25)
    np.testing.assert_allclose(
        MultiStep([10, 20], 0.1).get_lr(opt, {"evalCounter": 15}), 0.01)
    np.testing.assert_allclose(
        EpochStep(2, 0.1).get_lr(opt, {"epoch": 5}), 0.1 * 0.01)
    w = Warmup(0.01, 5, Step(10, 0.5))
    np.testing.assert_allclose(w.get_lr(opt, {"evalCounter": 3}), 0.13)
    seq = SequentialSchedule().add(Poly(1.0, 10), 10).add(Default(), 100)
    np.testing.assert_allclose(seq.get_lr(opt, {"evalCounter": 5}), 0.05)
    np.testing.assert_allclose(seq.get_lr(opt, {"evalCounter": 50}), 0.1)


def test_triggers():
    assert Trigger.max_epoch(3)({"epoch": 4})
    assert not Trigger.max_epoch(3)({"epoch": 3})
    assert Trigger.several_iteration(5)({"neval": 10})
    assert not Trigger.several_iteration(5)({"neval": 11})
    t = Trigger.every_epoch()
    assert not t({"epoch": 1})  # records the starting epoch
    assert not t({"epoch": 1})  # same epoch: no fire
    assert t({"epoch": 2})      # epoch advanced: fire
    assert not t({"epoch": 2})  # fires once per epoch
    assert t({"epoch": 3})
    assert Trigger.min_loss(0.1)({"loss": 0.05})
    assert Trigger.max_score(0.9)({"score": 0.95})


def test_validation_methods():
    out = np.asarray([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    tgt = np.asarray([1, 0, 0])
    r = Top1Accuracy()(out, tgt)
    acc, n = r.result()
    assert n == 3
    np.testing.assert_allclose(acc, 2 / 3)
    r2 = r + Top1Accuracy()(out, np.asarray([1, 0, 1]))
    np.testing.assert_allclose(r2.result()[0], 5 / 6)
    out5 = np.tile(np.arange(10, dtype=np.float64), (2, 1))
    assert Top5Accuracy()(out5, np.asarray([9, 5])).result()[0] == 1.0
    assert Top5Accuracy()(out5, np.asarray([0, 4])).result()[0] == 0.0


def test_lbfgs_rosenbrock_improves():
    m = LBFGS(learning_rate=2e-3, history_size=10)

    def f(w):
        return (1 - w[0]) ** 2 + 100 * (w[1] - w[0] ** 2) ** 2

    params = {"w": jnp.asarray([-1.0, 1.0])}
    state = m.init_state(params)
    f0 = float(f(params["w"]))
    for _ in range(200):
        grads = {"w": jax.grad(f)(params["w"])}
        params, state = m.update(grads, params, state, 2e-3)
    assert float(f(params["w"])) < f0 * 0.5


def test_lbfgs_wolfe_line_search_converges_rosenbrock():
    """optimize(feval, x) = the reference's LBFGS+lswolfe entry
    (optim/OptimMethod.scala:38 + LineSearch.scala): strong-Wolfe probes of
    feval should drive Rosenbrock essentially to its (1,1) minimum — far
    beyond what the fixed-step in-jit path achieves."""
    m = LBFGS(learning_rate=1.0, max_iter=20, history_size=10)

    def f(w):
        return (1 - w[0]) ** 2 + 100 * (w[1] - w[0] ** 2) ** 2

    def feval(params):
        w = params["w"]
        return f(w), {"w": jax.grad(f)(w)}

    params = {"w": jnp.asarray([-1.0, 1.0])}
    for _ in range(10):  # 10 outer calls x 20 inner iterations
        params, losses = m.optimize(feval, params)
    assert losses[-1] < 1e-6
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0],
                               atol=1e-3)


def test_optim_method_host_optimize_quadratic():
    """Base OptimMethod.optimize: repeated host steps on a quadratic bowl
    reach the minimum, and state (momentum) persists across calls."""
    m = SGD(learning_rate=0.1, momentum=0.9)

    def feval(params):
        w = params["w"]
        return jnp.sum((w - 3.0) ** 2), {"w": 2 * (w - 3.0)}

    params = {"w": jnp.zeros((4,))}
    for _ in range(200):
        params, losses = m.optimize(feval, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.full(4, 3.0),
                               atol=1e-3)
    assert m.hyper["evalCounter"] == 200


def test_host_optimize_state_survives_checkpoint():
    """state_dict/load_state_dict carry the host-optimize trajectory
    (momentum velocity), so a restored instance continues identically."""
    m = SGD(learning_rate=0.1, momentum=0.9)

    def feval(p):
        return jnp.sum((p["w"] - 3.0) ** 2), {"w": 2 * (p["w"] - 3.0)}

    p = {"w": jnp.zeros(3)}
    for _ in range(5):
        p, _ = m.optimize(feval, p)
    m2 = SGD(learning_rate=0.1, momentum=0.9)
    m2.load_state_dict(m.state_dict())
    p_resumed, _ = m2.optimize(feval, p)
    p_straight, _ = m.optimize(feval, p)
    np.testing.assert_allclose(np.asarray(p_straight["w"]),
                               np.asarray(p_resumed["w"]), atol=1e-7)


def test_tree_nn_accuracy():
    import numpy as np
    from bigdl_tpu.optim import TreeNNAccuracy
    # (batch=2, nodes=3, classes=2): root = last node slot
    out = np.zeros((2, 3, 2))
    out[0, -1] = [0.9, 0.1]   # predicts 0
    out[1, -1] = [0.2, 0.8]   # predicts 1
    res = TreeNNAccuracy()(out, np.array([0.0, 0.0]))
    acc, n = res.result()
    assert n == 2 and acc == 0.5


def test_validator_facade():
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import (DistriValidator, LocalValidator,
                                 Top1Accuracy, Validator)
    assert DistriValidator is Validator and LocalValidator is Validator
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(4).astype(np.float32),
                      np.float32(i % 2)) for i in range(32)]
    model = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    model.build()
    res = Validator(model, DataSet.array(samples)).test(
        [Top1Accuracy()], batch_size=16)
    _, r = res[0]
    acc, n = r.result()
    assert n == 32 and 0.0 <= acc <= 1.0


def test_import_does_not_touch_devices():
    # importing the library must not initialize a jax backend (a launcher's
    # parent has to stay off the chip); run in a clean subprocess
    import subprocess
    import sys
    code = (
        "import jax, bigdl_tpu, bigdl_tpu.optim, bigdl_tpu.nn\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert "clean" in out.stdout, out.stderr


def test_tree_nn_accuracy_per_node_targets():
    import numpy as np
    from bigdl_tpu.optim import TreeNNAccuracy
    out = np.zeros((2, 3, 2))
    out[0, -1] = [0.9, 0.1]
    out[1, -1] = [0.2, 0.8]
    # per-node (batch, nodes) labels: root label is the last column
    target = np.array([[1.0, 1.0, 0.0], [0.0, 0.0, 1.0]])
    acc, n = TreeNNAccuracy()(out, target).result()
    assert n == 2 and acc == 1.0


def test_cosine_decay_schedule():
    from bigdl_tpu.optim import SGD, CosineDecay

    sgd = SGD(learning_rate=1.0, learning_rate_schedule=CosineDecay(100))
    assert abs(sgd.get_learning_rate({"evalCounter": 0}) - 1.0) < 1e-9
    assert abs(sgd.get_learning_rate({"evalCounter": 50}) - 0.5) < 1e-9
    assert abs(sgd.get_learning_rate({"evalCounter": 100})) < 1e-9
    assert abs(sgd.get_learning_rate({"evalCounter": 999})) < 1e-9
    s2 = SGD(learning_rate=1.0,
             learning_rate_schedule=CosineDecay(100, min_factor=0.1))
    assert abs(s2.get_learning_rate({"evalCounter": 100}) - 0.1) < 1e-9


def test_warmup_cosine_continuity():
    """Warmup hands the after-schedule the PEAK lr and a re-zeroed
    counter: ramp-to-peak then cosine is continuous and T-phased."""
    from bigdl_tpu.optim import SGD, CosineDecay, Warmup

    sgd = SGD(learning_rate=0.1,
              learning_rate_schedule=Warmup(0.009, 100,
                                            after=CosineDecay(1000)))
    end_warm = sgd.get_learning_rate({"evalCounter": 99})
    start_cos = sgd.get_learning_rate({"evalCounter": 100})
    peak = 0.1 + 0.009 * 100
    assert abs(start_cos - peak) < 0.01 * peak  # continuous at handoff
    assert abs(end_warm - (peak - 0.009)) < 1e-9
    # cosine floor is reached T iters AFTER warmup, not at global T
    assert sgd.get_learning_rate({"evalCounter": 1100}) < 1e-9
    assert sgd.get_learning_rate({"evalCounter": 600}) > 0.1


def test_ema_update_math():
    """shadow = d*shadow + (1-d)*params after each inner update, exactly."""
    from bigdl_tpu.optim import EMA, SGD

    inner = SGD(learning_rate=0.5)
    ema = EMA(inner, decay=0.9)
    p = {"w": jnp.asarray([1.0, 2.0])}
    st = ema.init_state(p)
    np.testing.assert_allclose(np.asarray(st["shadow"]["w"]), [1.0, 2.0])
    g = {"w": jnp.asarray([1.0, 1.0])}
    p1, st1 = ema.update(g, p, st, jnp.float32(0.5))
    np.testing.assert_allclose(np.asarray(p1["w"]), [0.5, 1.5])  # sgd step
    np.testing.assert_allclose(np.asarray(st1["shadow"]["w"]),
                               0.9 * np.array([1.0, 2.0])
                               + 0.1 * np.array([0.5, 1.5]))
    p2, st2 = ema.update(g, p1, st1, jnp.float32(0.5))
    np.testing.assert_allclose(
        np.asarray(st2["shadow"]["w"]),
        0.9 * np.asarray(st1["shadow"]["w"]) + 0.1 * np.asarray(p2["w"]),
        rtol=1e-6)


def test_ema_through_optimizer_training():
    """EMA(Adam) trains through the compiled step; the shadow weights are a
    lagged average (differ from live, same structure) and serve a working
    model via EMA.apply_to."""
    from bigdl_tpu.optim import Adam, EMA, Evaluator, Top1Accuracy
    from bigdl_tpu.utils.engine import Engine
    from test_e2e_lenet import make_optimizer, synthetic_mnist
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models import LeNet5

    from bigdl_tpu.common import set_seed

    Engine.reset()
    Engine.init()
    set_seed(0)  # order-independent: the model init draws from the global
    # RNG stream, and convergence at 3 epochs depends on the draw
    model, opt = make_optimizer()
    # decay=0.9: at 3 epochs x 8 steps the shadow still lags the live
    # weights (the "differs" assertion below) but carries < 0.9^24 ~ 8%
    # of the random init.  The previous 0.98 left ~62% init weight in the
    # shadow, putting the accuracy bound at the mercy of jax-version
    # numeric drift (0.80 passed on jax<=0.4.30, 0.77 on 0.4.37).
    opt.set_optim_method(EMA(Adam(learning_rate=1e-3), decay=0.9))
    opt.optimize()
    live = jax.tree.leaves(model.params)
    shadow = jax.tree.leaves(
        opt.optim_method.ema_params(opt._final_opt_state))
    assert any(not np.allclose(np.asarray(a), np.asarray(b))
               for a, b in zip(live, shadow))
    ema_model = EMA.apply_to(LeNet5(10).build(), opt)
    val = DataSet.array(synthetic_mnist(256, seed=3))
    acc, _ = Evaluator(ema_model).test(val, [Top1Accuracy()],
                                       batch_size=64)[0][1].result()
    assert acc > 0.8, acc


def test_ema_apply_to_transfers_bn_state():
    """apply_to must carry the trained BN running stats, not leave the
    fresh model's zeros/ones (a BN model would otherwise eval at chance
    with no error)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import set_seed
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.optim import Adam, EMA, Optimizer, Trigger
    from bigdl_tpu.utils.engine import Engine
    from test_e2e_lenet import synthetic_mnist

    Engine.reset()
    Engine.init()
    set_seed(0)

    def bn_model():
        return (nn.Sequential()
                .add(nn.Reshape((28, 28, 1)))
                .add(nn.SpatialConvolution(1, 4, 3, 3, 1, 1, -1, -1))
                .add(nn.SpatialBatchNormalization(4))
                .add(nn.ReLU())
                .add(nn.Reshape((28 * 28 * 4,)))
                .add(nn.Linear(28 * 28 * 4, 10))
                .add(nn.LogSoftMax()))

    ds = DataSet.array(synthetic_mnist(256)).transform(
        SampleToMiniBatch(64, drop_last=True))
    opt = (Optimizer(bn_model(), ds, nn.ClassNLLCriterion())
           .set_optim_method(EMA(Adam(1e-3), decay=0.95))
           .set_end_when(Trigger.max_epoch(2)))
    opt.optimize()
    fresh = bn_model().build()
    ema_model = EMA.apply_to(fresh, opt)
    rm = np.asarray(jax.tree.leaves(ema_model.state)[0])
    assert np.abs(rm).sum() > 0  # trained running stats, not init zeros


def test_warmup_preserves_plateau_bookkeeping():
    """Warmup's counter re-basing must pass schedule writes through to the
    REAL state dict: Plateau counts one observation per epoch, not one per
    iteration (a dict copy would drop its _plateau_seen marker and the LR
    would collapse patience-fold too fast)."""
    from bigdl_tpu.optim import SGD, Warmup
    from bigdl_tpu.optim.schedules import Plateau

    sched = Warmup(0.0, 2, after=Plateau(monitor="score", patience=3,
                                         factor=0.1, mode="max"))
    sgd = SGD(learning_rate=0.1, learning_rate_schedule=sched)
    state = {"evalCounter": 5, "epoch": 1, "score": 1.0}
    for _ in range(10):  # many iterations inside ONE epoch
        lr = sgd.get_learning_rate(state)
    assert abs(lr - 0.1) < 1e-9  # patience must not tick per iteration
    # non-improving epochs tick patience once each; the 3rd (epoch 4)
    # fires the drop
    for epoch in (2, 3):
        state["epoch"] = epoch
        lr = sgd.get_learning_rate(state)
        assert abs(lr - 0.1) < 1e-9, (epoch, lr)
    state["epoch"] = 4
    lr = sgd.get_learning_rate(state)
    assert abs(lr - 0.01) < 1e-9, lr


def test_perplexity_metric():
    """exp(mean token NLL) with padding exclusion; aggregation across
    batches matches one big batch."""
    from bigdl_tpu.optim import Perplexity

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 5, 7)).astype(np.float32)
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    tgt = np.array([[1, 2, 3, -1, -1], [0, 6, 5, 4, -1]])
    m = Perplexity()
    r = m(lp, tgt)
    ppl, n = r.result()
    assert n == 7  # 3 + 4 valid tokens
    manual = -np.mean([lp[b, t, tgt[b, t]]
                       for b in range(2) for t in range(5)
                       if tgt[b, t] >= 0])
    np.testing.assert_allclose(ppl, np.exp(manual), rtol=1e-6)
    # additive aggregation == single evaluation
    r2 = m(lp[:1], tgt[:1]) + m(lp[1:], tgt[1:])
    np.testing.assert_allclose(r2.result()[0], ppl, rtol=1e-12)
    # uniform log-probs -> ppl == vocab
    uni = np.full((1, 4, 7), -np.log(7.0))
    np.testing.assert_allclose(m(uni, np.zeros((1, 4), int)).result()[0],
                               7.0, rtol=1e-6)


def test_layerwise_grad_scaling_reaches_compiled_step():
    """set_scale_w/set_scale_b must scale gradients inside the COMPILED
    train step (the reference applies scaleW/scaleB in accGradParameters,
    so layer-wise LR scaling reaches the distributed update —
    DistriOptimizer.scala:729), not just the facade backward."""
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    def run(scaled):
        from bigdl_tpu.common import set_seed
        set_seed(77)
        model = nn.Sequential(nn.Linear(6, 5), nn.Tanh(), nn.Linear(5, 3),
                              nn.LogSoftMax())
        if scaled:
            model.modules[0].set_scale_w(2.0).set_scale_b(3.0)
        r = np.random.default_rng(0)
        samples = [Sample(r.normal(size=(6,)).astype(np.float32),
                          np.int32(r.integers(0, 3))) for _ in range(8)]
        model.build()
        opt = Optimizer(model, samples, nn.ClassNLLCriterion(), batch_size=8)
        opt.set_optim_method(SGD(learning_rate=0.1))  # no momentum: delta = lr*g
        # 8 samples / batch 8 -> one batch per epoch: exactly ONE step
        opt.set_end_when(Trigger.max_epoch(1))
        before = [np.asarray(x).copy() for x in jax.tree.leaves(model.params)]
        opt.optimize()
        after = [np.asarray(x) for x in jax.tree.leaves(model.params)]
        return [a - b for a, b in zip(after, before)]

    base = run(False)
    scaled = run(True)
    # leaves order: [layer0 bias, layer0 weight, layer2 bias, layer2 weight]
    # bf16-wire tolerance: scaling happens BEFORE the wire cast (reference
    # order), so scaled-then-quantized differs from quantized-then-scaled
    # by one bf16 ulp (~0.4% relative)
    np.testing.assert_allclose(scaled[0], 3.0 * base[0], rtol=1e-2, atol=1e-7)
    np.testing.assert_allclose(scaled[1], 2.0 * base[1], rtol=1e-2, atol=1e-7)
    np.testing.assert_allclose(scaled[2], base[2], rtol=1e-2, atol=1e-8)
    np.testing.assert_allclose(scaled[3], base[3], rtol=1e-2, atol=1e-8)
    # and the scale genuinely engaged: layer0 deltas are ~3x/2x, not ~1x
    assert np.abs(scaled[1]).sum() > 1.5 * np.abs(base[1]).sum()


def test_container_level_scale_propagates():
    """Container.set_scale_w propagates to children (reference
    Container.setScaleW), so container-level scales reach both the facade
    and the compiled step's grad-scale tree."""
    import bigdl_tpu.nn as nn
    m = nn.Sequential(nn.Linear(4, 3), nn.Sequential(nn.Linear(3, 2)))
    m.set_scale_w(2.0).set_scale_b(3.0)
    assert m.modules[0].scale_w == 2.0
    assert m.modules[1].modules[0].scale_b == 3.0
    st = m._grad_scale_tree()
    leaves = jax.tree.leaves(st)
    assert sorted(set(leaves)) == [2.0, 3.0]


def test_scale_change_after_first_optimize_recompiles():
    """scaleW is baked into the compiled step as a static factor, so
    changing it between optimize() calls must recompile — the freeze idiom
    (set_scale_w(0) after a warmup phase) has to actually freeze."""
    import bigdl_tpu.nn as nn
    import numpy as np
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    model = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax()).build(
        jax.random.key(0))
    r = np.random.default_rng(0)
    samples = [Sample(r.normal(size=(4,)).astype(np.float32),
                      np.int32(r.integers(0, 3))) for _ in range(8)]
    opt = Optimizer(model, samples, nn.ClassNLLCriterion(), batch_size=8)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(Trigger.max_epoch(1))
    opt.optimize()                      # phase 1: trains normally
    w1 = np.asarray(model.params[0]["weight"]).copy()

    model.set_scale_w(0.0).set_scale_b(0.0)   # freeze everything
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()                      # phase 2: must be a no-op
    w2 = np.asarray(model.params[0]["weight"])
    np.testing.assert_array_equal(w1, w2)


def test_graph_scale_propagates_and_regularizer_is_scaled():
    """set_scale_w on a Graph reaches its nodes (reference: setScaleW on
    any module scales its parameters), and scaleW=0 freezes the
    regularizer contribution too (accRegularization takes scaleW)."""
    import bigdl_tpu.nn as nn
    import numpy as np
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.optim.regularizer import L2Regularizer

    inp = nn.Input()
    lin = nn.Linear(4, 3, w_regularizer=L2Regularizer(10.0))
    out = nn.LogSoftMax()(lin(inp))
    g = nn.Graph(inp, out).build(jax.random.key(0))
    g.set_scale_w(0.0).set_scale_b(0.0)
    st = g._grad_scale_tree()
    assert st is not None and set(jax.tree.leaves(st)) == {0.0}

    r = np.random.default_rng(0)
    samples = [Sample(r.normal(size=(4,)).astype(np.float32),
                      np.int32(r.integers(0, 3))) for _ in range(8)]
    opt = Optimizer(g, samples, nn.ClassNLLCriterion(), batch_size=8)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(Trigger.max_epoch(2))
    before = [np.asarray(x).copy() for x in jax.tree.leaves(g.params)]
    opt.optimize()
    after = [np.asarray(x) for x in jax.tree.leaves(g.params)]
    for a, b in zip(before, after):   # fully frozen incl. weight decay
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the loop logs every iteration from the pending loss (ISSUE 38): step n + 1
# is called before loss n is read, and nothing a user sees changes but when
# ---------------------------------------------------------------------------

class _Recorder:
    """A train summary that keeps what the loop hands it."""

    def __init__(self, parameters_at=()):
        self.scalars, self.histograms, self.logged_before = [], {}, {}
        self.parameters_at = set(parameters_at)

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, step, value))
        return self

    def add_histogram(self, name, values, step):
        self.histograms.setdefault(step, {})[name] = np.array(values)
        self.logged_before[step] = [s for s, _ in self.series("Loss")]
        return self

    def get_summary_trigger(self, name):
        if name == "Parameters" and self.parameters_at:
            return lambda state: state["neval"] in self.parameters_at
        return None

    def series(self, tag):
        return [(step, v) for t, step, v in self.scalars if t == tag]


def _loop_run(log_interval=1, epochs=2, method=None, summary=None,
              tap=None, **ckpt):
    """A seeded run of 4 iterations an epoch; returns the optimizer, the
    summary and every loss the loop observed, in order."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import set_seed
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    set_seed(11)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(6).astype(np.float32),
                      np.float32(i % 2)) for i in range(64)]
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(16, drop_last=True))
    if tap is not None:
        ds = ds.transform(tap)
    summary = summary or _Recorder()
    opt = (Optimizer(nn.Sequential().add(nn.Linear(6, 2)), ds,
                     nn.CrossEntropyCriterion())
           .set_optim_method(method or Adam(1e-2))
           .set_end_when(Trigger.max_epoch(epochs))
           .set_train_summary(summary)
           .set_log_interval(log_interval))
    if ckpt:
        opt.set_checkpoint(ckpt["path"], ckpt["trigger"])
    observed = []
    orig = opt._observe_loss

    def observe(lossf, state):
        observed.append(orig(lossf, state))
        return observed[-1]
    opt._observe_loss = observe
    return opt, summary, observed


@pytest.mark.parametrize("log_interval", [1, 3, 10 ** 9])
def test_every_iteration_logs_once_in_order_whatever_the_interval(
        log_interval):
    """`log_interval` decides which iterations print and write scalars,
    nothing else: the losses are the same numbers, each observed once, in
    order, and a logged iteration carries its own number."""
    opt, every, base = _loop_run(1)
    opt.optimize()
    assert [s for s, _ in every.series("Loss")] == list(range(1, 9))
    assert [v for _, v in every.series("Loss")] == base
    assert opt.optim_method.hyper["loss"] == base[-1]

    opt, some, observed = _loop_run(log_interval)
    opt.optimize()
    assert observed == base
    logged = [n for n in range(1, 9) if n % log_interval == 0]
    assert some.series("Loss") == [(n, base[n - 1]) for n in logged]
    for tag in ("LearningRate", "Throughput"):
        assert [s for s, _ in some.series(tag)] == logged
    assert all(v > 0 for _, v in some.series("Throughput"))
    assert opt.optim_method.hyper["loss"] == base[-1]


def test_no_snapshot_holds_a_step_whose_loss_was_not_seen(tmp_path):
    """A batch poisoned at the iteration a checkpoint fires on: the pending
    loss is read before the snapshot is written, so when the sentinel
    raises no snapshot of that iteration or a later one exists, and the
    run recovers to finite weights."""
    from bigdl_tpu.optim.optimizer import NonFiniteLossError
    from bigdl_tpu.utils import chaos, file_io
    k = 3
    opt, _, observed = _loop_run(
        path=str(tmp_path), trigger=Trigger.several_iteration(1))
    seen_at_raise = []
    inner = opt._observe_loss

    def observe(lossf, state):
        try:
            return inner(lossf, state)
        except NonFiniteLossError:
            seen_at_raise.append([n for _, _, n in
                                  file_io.checkpoint_lineage(str(tmp_path))])
            raise
    opt._observe_loss = observe
    chaos.clear()
    try:
        with chaos.scoped(f"data.batch=nan@{k}"):
            trained = opt.optimize()
            assert chaos.counts()["data.batch"] > k     # training went on
    finally:
        chaos.clear()
    assert seen_at_raise == [[2, 1]]
    assert all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree.leaves(trained.params))
    assert len(observed) >= 8 and all(np.isfinite(observed))


@pytest.mark.parametrize("k", [1, 3, 4])
def test_parameters_trigger_hands_over_exactly_k_updates(k):
    """The histograms of iteration k are the parameters after k updates,
    not k + 1: the loop is one call ahead of the losses it reads, never of
    the weights it hands out (k = 4 is an epoch's last iteration)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.transformer import Transformer

    class Tap(Transformer):
        batches = []

        def __call__(self, it):
            for b in it:
                self.batches.append((np.array(b.get_input()),
                                     np.array(b.get_target())))
                yield b

    tap, rec = Tap(), _Recorder(parameters_at=(k,))
    opt, _, observed = _loop_run(method=SGD(learning_rate=0.1),
                                 summary=rec, tap=tap)
    model, crit = opt.model, nn.CrossEntropyCriterion()
    model.build()
    params = jax.tree.map(np.array, model.params)   # the step donates its own
    opt.optimize()
    assert sorted(rec.histograms) == [k]
    # the loss of iteration k was read, and logged, before its weights were
    assert rec.logged_before == {k: list(range(1, k + 1))}
    assert [s for s, _ in rec.series("Loss")] == list(range(1, 9))

    def loss(p, x, y):
        return crit.loss(model.apply(p, model.state, x, training=True)[0], y)
    after = []
    for x, y in tap.batches[:k + 1]:
        g = jax.grad(loss)(params, x, y)
        params = jax.tree.map(lambda p, d: p - 0.1 * d, params, g)
        after.append([np.asarray(v) for v in jax.tree.leaves(params)])
    got = list(rec.histograms[k].values())
    assert len(got) == len(after[k - 1])
    # within the step's own rounding of k updates, an update away from
    # k - 1 and k + 1
    for leaf, a in enumerate(got):
        near = np.abs(a - after[k - 1][leaf]).max()
        for other in (k - 2, k):
            if other >= 0:
                assert near < 0.05 * np.abs(a - after[other][leaf]).max()
