"""Each plain reference against the system at tiny widths, on the CPU.

In float32 the two must agree to rounding.  Under the configuration's own
policy (bfloat16 compute) the system must pass the tolerance that the
reference computed one precision lower (fp8 operands) fails: the tolerance
separates the precision the configuration states from the next one down.
Each configuration's own operation count is checked against
``utils/flops.fn_flops``.
"""

import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

CONFIGS = ("resnet50_imagenet", "gpt2_medium")
#: largest logit error over largest reference logit.  Measured at these tiny
#: sizes (PR 24): the system in bfloat16 0.013 (ResNet-18) and 0.0056
#: (2 x 32 LM, whose bfloat16 log-probabilities alone are quantised to
#: 0.4 %); the fp8 reference 0.071 and 0.0044.  The ResNet tolerance sits
#: between its two; the LM's output quantisation hides fp8 at this width, so
#: the LM compares the reference's own bf16 recipe (0.0003) with its fp8 one.
TOL = {"resnet50_imagenet": 0.03, "gpt2_medium": 0.0015}


@pytest.fixture
def fresh_policy():
    from bigdl_tpu.common import get_policy, set_policy
    prior = get_policy()
    yield
    set_policy(prior)


def _tiny(name):
    cfg_file = harness.load_json(
        os.path.join(REPO, "benchmark", "configs", name + ".json"))
    cfg = dict(cfg_file)
    cfg.update(cfg_file["rehearse"])
    cm = harness.load_module(
        os.path.join(REPO, "benchmark", "configs", name + ".py"),
        "test_cfg_" + name)
    r = np.random.default_rng(0)
    if "classes" in cfg:
        x = r.standard_normal((4, cfg["image"], cfg["image"], 3)) \
            .astype(np.float32)
        y = r.integers(0, cfg["classes"], 4).astype(np.int32)
        out = lambda p, prec: cm.ref.logits(cfg, p, x, prec)
    else:
        x = r.integers(0, cfg["vocab_size"], (4, 32)).astype(np.int32)
        y = r.integers(0, cfg["vocab_size"], (4, 32)).astype(np.int32)
        import jax
        out = lambda p, prec: jax.nn.log_softmax(
            cm.ref.logits(cfg, p, x, prec), -1)
    return cfg, cm, x, y, out


def _system(cm, cfg, dtype, key, x, y):
    import jax.numpy as jnp
    cfg = dict(cfg, compute_dtype=dtype)
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model, key)
    out, _ = model.apply(params, state, jnp.asarray(x), training=True,
                         rng=None)
    loss = float(cm.criterion(cfg).loss(out, jnp.asarray(y)))
    return np.asarray(out, np.float32), loss, (model, params, state)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_system_in_float32(name, fresh_policy):
    import jax
    cfg, cm, x, y, out = _tiny(name)
    key = jax.random.key(7)
    p = cm.init_params(cfg, key)
    want = np.asarray(out(p, "f32"))
    want_loss = float(cm.loss_fn(cfg)(p, x, y))
    got, loss, _ = _system(cm, cfg, "float32", key, x, y)
    # float32 both sides: only the order of summation differs
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
    assert abs(loss - want_loss) / want_loss < 1e-5


@pytest.mark.parametrize("name", CONFIGS)
def test_tolerance_passes_bf16_and_fails_fp8(name, fresh_policy):
    import jax
    cfg, cm, x, y, out = _tiny(name)
    key = jax.random.key(7)
    p = cm.init_params(cfg, key)
    want = np.asarray(out(p, "f32"))
    err = lambda got: float(np.abs(got - want).max() / np.abs(want).max())
    if name == "resnet50_imagenet":
        sound = err(_system(cm, cfg, "bfloat16", key, x, y)[0])
    else:
        sound = err(np.asarray(out(p, "bf16")))
    low = err(np.asarray(out(p, "fp8")))
    assert sound < TOL[name] < low, (sound, low)


@pytest.mark.parametrize("name", CONFIGS)
def test_own_flops_count_matches_fn_flops(name, fresh_policy):
    """Forward + backward is three times the forward's matrix work; the
    forward is counted from the jaxpr by utils/flops (its count of a strided
    convolution's backward includes the zeros of the dilated gradient, so
    the forward is what is compared).  The LM's own count halves attention's
    T^2 products (causal); fn_flops counts them all."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.utils.flops import fn_flops
    cfg, cm, x, y, _out = _tiny(name)
    _got, _loss, (model, params, state) = _system(
        cm, cfg, "float32", jax.random.key(7), x, y)
    fwd = fn_flops(lambda p: model.apply(p, state, jnp.asarray(x),
                                         training=True, rng=None)[0], params)
    if name == "resnet50_imagenet":
        # the program may compute more than the algorithm needs (it pads the
        # stem's 3 input channels for the MXU: 11 % at ResNet-18), never less
        own = cm.model_flops_per_record(cfg)
        assert own <= 3 * fwd / len(x) * 1.001 <= own * 1.15
        return
    else:
        t, d, n = x.shape[1], cfg["n_embd"], cfg["n_layer"]
        own = cm.model_flops_per_record(cfg, t)
        assert own < 3 * fwd / len(x)
        own += t * 6.0 * n * t * d      # the half above the diagonal
    assert abs(own - 3 * fwd / len(x)) / own < 0.01
