"""The guard PR 31 lacked (ISSUE 32).  ``harness.program_weights`` lays the
benchmark's seeded weights onto the program's parameter tree by flatten
order, checking shapes only.  GPT-2's four attention projections all have one
shape and its four biases another, so a renamed, added or reordered leaf
passes that check and trains with ``wq`` in ``wk``'s place: every test of the
program stays green and the cell comes out not ``correct`` on the chip.

For each configuration an earlier PR brought, at its rehearse size: the
program's flattened parameter paths and shapes equal the list below, written
out from the tree of PR 30 (commit c625cf1), and the seeded weights laid on
by ``program_weights`` give, in one forward, what the plain reference gives
on the same seed.  A change that moves a leaf fails here first; one that means
to has to move the reference's tree with it and say so in this list."""

import os

import numpy as np
import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: largest error over largest reference output, float32 both sides.  Read
#: here: 1.6e-7 (GPT-2), 1.7e-6 (DeepSeek-V2), 1.3e-6 (ResNet); GPT-2 with two
#: projections of one shape changed over reads 1.4e-4 (at its published 0.02
#: a tiny model's scores are nearly flat, so the fault is small: the list of
#: paths is the sharper guard).
TOL = 2e-5

TREES = {
    "gpt2_medium": [
        ("[0]['weight']", (211, 32)), ("[1]['weight']", (64, 32)),
        ("[2][0][0][0][0]['bias']", (32,)),
        ("[2][0][0][0][0]['weight']", (32,)), ("[2][0][0][0][1]['bk']", (32,)),
        ("[2][0][0][0][1]['bo']", (32,)), ("[2][0][0][0][1]['bq']", (32,)),
        ("[2][0][0][0][1]['bv']", (32,)), ("[2][0][0][0][1]['wk']", (32, 32)),
        ("[2][0][0][0][1]['wo']", (32, 32)),
        ("[2][0][0][0][1]['wq']", (32, 32)),
        ("[2][0][0][0][1]['wv']", (32, 32)),
        ("[2][1][0][0][0]['bias']", (32,)),
        ("[2][1][0][0][0]['weight']", (32,)),
        ("[2][1][0][0][1]['bias']", (128,)),
        ("[2][1][0][0][1]['weight']", (128, 32)),
        ("[2][1][0][0][3]['bias']", (32,)),
        ("[2][1][0][0][3]['weight']", (32, 128)),
        ("[3][0][0][0][0]['bias']", (32,)),
        ("[3][0][0][0][0]['weight']", (32,)), ("[3][0][0][0][1]['bk']", (32,)),
        ("[3][0][0][0][1]['bo']", (32,)), ("[3][0][0][0][1]['bq']", (32,)),
        ("[3][0][0][0][1]['bv']", (32,)), ("[3][0][0][0][1]['wk']", (32, 32)),
        ("[3][0][0][0][1]['wo']", (32, 32)),
        ("[3][0][0][0][1]['wq']", (32, 32)),
        ("[3][0][0][0][1]['wv']", (32, 32)),
        ("[3][1][0][0][0]['bias']", (32,)),
        ("[3][1][0][0][0]['weight']", (32,)),
        ("[3][1][0][0][1]['bias']", (128,)),
        ("[3][1][0][0][1]['weight']", (128, 32)),
        ("[3][1][0][0][3]['bias']", (32,)),
        ("[3][1][0][0][3]['weight']", (32, 128)), ("[4]['bias']", (32,)),
        ("[4]['weight']", (32,)), ("[5]['bias']", (211,)),
        ("[5]['weight']", (211, 32)),
    ],
    "resnet50_imagenet": [
        ("[0]['bias']", (64,)), ("[0]['weight']", (7, 7, 3, 64)),
        ("[1]['bias']", (64,)), ("[1]['weight']", (64,)),
        ("[4][0][0][0][0]['bias']", (64,)),
        ("[4][0][0][0][0]['weight']", (3, 3, 64, 64)),
        ("[4][0][0][0][1]['bias']", (64,)),
        ("[4][0][0][0][1]['weight']", (64,)),
        ("[4][0][0][0][3]['bias']", (64,)),
        ("[4][0][0][0][3]['weight']", (3, 3, 64, 64)),
        ("[4][0][0][0][4]['bias']", (64,)),
        ("[4][0][0][0][4]['weight']", (64,)),
        ("[4][1][0][0][0]['bias']", (64,)),
        ("[4][1][0][0][0]['weight']", (3, 3, 64, 64)),
        ("[4][1][0][0][1]['bias']", (64,)),
        ("[4][1][0][0][1]['weight']", (64,)),
        ("[4][1][0][0][3]['bias']", (64,)),
        ("[4][1][0][0][3]['weight']", (3, 3, 64, 64)),
        ("[4][1][0][0][4]['bias']", (64,)),
        ("[4][1][0][0][4]['weight']", (64,)),
        ("[5][0][0][0][0]['bias']", (128,)),
        ("[5][0][0][0][0]['weight']", (3, 3, 64, 128)),
        ("[5][0][0][0][1]['bias']", (128,)),
        ("[5][0][0][0][1]['weight']", (128,)),
        ("[5][0][0][0][3]['bias']", (128,)),
        ("[5][0][0][0][3]['weight']", (3, 3, 128, 128)),
        ("[5][0][0][0][4]['bias']", (128,)),
        ("[5][0][0][0][4]['weight']", (128,)),
        ("[5][0][0][1][0]['bias']", (128,)),
        ("[5][0][0][1][0]['weight']", (1, 1, 64, 128)),
        ("[5][0][0][1][1]['bias']", (128,)),
        ("[5][0][0][1][1]['weight']", (128,)),
        ("[5][1][0][0][0]['bias']", (128,)),
        ("[5][1][0][0][0]['weight']", (3, 3, 128, 128)),
        ("[5][1][0][0][1]['bias']", (128,)),
        ("[5][1][0][0][1]['weight']", (128,)),
        ("[5][1][0][0][3]['bias']", (128,)),
        ("[5][1][0][0][3]['weight']", (3, 3, 128, 128)),
        ("[5][1][0][0][4]['bias']", (128,)),
        ("[5][1][0][0][4]['weight']", (128,)),
        ("[6][0][0][0][0]['bias']", (256,)),
        ("[6][0][0][0][0]['weight']", (3, 3, 128, 256)),
        ("[6][0][0][0][1]['bias']", (256,)),
        ("[6][0][0][0][1]['weight']", (256,)),
        ("[6][0][0][0][3]['bias']", (256,)),
        ("[6][0][0][0][3]['weight']", (3, 3, 256, 256)),
        ("[6][0][0][0][4]['bias']", (256,)),
        ("[6][0][0][0][4]['weight']", (256,)),
        ("[6][0][0][1][0]['bias']", (256,)),
        ("[6][0][0][1][0]['weight']", (1, 1, 128, 256)),
        ("[6][0][0][1][1]['bias']", (256,)),
        ("[6][0][0][1][1]['weight']", (256,)),
        ("[6][1][0][0][0]['bias']", (256,)),
        ("[6][1][0][0][0]['weight']", (3, 3, 256, 256)),
        ("[6][1][0][0][1]['bias']", (256,)),
        ("[6][1][0][0][1]['weight']", (256,)),
        ("[6][1][0][0][3]['bias']", (256,)),
        ("[6][1][0][0][3]['weight']", (3, 3, 256, 256)),
        ("[6][1][0][0][4]['bias']", (256,)),
        ("[6][1][0][0][4]['weight']", (256,)),
        ("[7][0][0][0][0]['bias']", (512,)),
        ("[7][0][0][0][0]['weight']", (3, 3, 256, 512)),
        ("[7][0][0][0][1]['bias']", (512,)),
        ("[7][0][0][0][1]['weight']", (512,)),
        ("[7][0][0][0][3]['bias']", (512,)),
        ("[7][0][0][0][3]['weight']", (3, 3, 512, 512)),
        ("[7][0][0][0][4]['bias']", (512,)),
        ("[7][0][0][0][4]['weight']", (512,)),
        ("[7][0][0][1][0]['bias']", (512,)),
        ("[7][0][0][1][0]['weight']", (1, 1, 256, 512)),
        ("[7][0][0][1][1]['bias']", (512,)),
        ("[7][0][0][1][1]['weight']", (512,)),
        ("[7][1][0][0][0]['bias']", (512,)),
        ("[7][1][0][0][0]['weight']", (3, 3, 512, 512)),
        ("[7][1][0][0][1]['bias']", (512,)),
        ("[7][1][0][0][1]['weight']", (512,)),
        ("[7][1][0][0][3]['bias']", (512,)),
        ("[7][1][0][0][3]['weight']", (3, 3, 512, 512)),
        ("[7][1][0][0][4]['bias']", (512,)),
        ("[7][1][0][0][4]['weight']", (512,)), ("[10]['bias']", (10,)),
        ("[10]['weight']", (10, 512)),
    ],
    "deepseek_v2_share4": [
        ("[0]['weight']", (211, 64)), ("[2][0][0][0][0]['weight']", (64,)),
        ("[2][0][0][0][1]['kv_norm']", (16,)),
        ("[2][0][0][0][1]['q_norm']", (32,)),
        ("[2][0][0][0][1]['wdkv']", (64, 20)),
        ("[2][0][0][0][1]['wdq']", (64, 32)),
        ("[2][0][0][0][1]['wo']", (32, 64)),
        ("[2][0][0][0][1]['wukv']", (16, 64)),
        ("[2][0][0][0][1]['wuq']", (32, 48)),
        ("[2][1][0][0][0]['weight']", (64,)),
        ("[2][1][0][0][1][0][0][0]['weight']", (128, 64)),
        ("[2][1][0][0][1][0][1]['weight']", (128, 64)),
        ("[2][1][0][0][1][2]['weight']", (64, 128)),
        ("[3][0][0][0][0]['weight']", (64,)),
        ("[3][0][0][0][1]['kv_norm']", (16,)),
        ("[3][0][0][0][1]['q_norm']", (32,)),
        ("[3][0][0][0][1]['wdkv']", (64, 20)),
        ("[3][0][0][0][1]['wdq']", (64, 32)),
        ("[3][0][0][0][1]['wo']", (32, 64)),
        ("[3][0][0][0][1]['wukv']", (16, 64)),
        ("[3][0][0][0][1]['wuq']", (32, 48)),
        ("[3][1][0][0][0]['weight']", (64,)),
        ("[3][1][0][0][1]['gate']", (64, 16)),
        ("[3][1][0][0][1]['shared_down']", (64, 64)),
        ("[3][1][0][0][1]['shared_gate']", (64, 64)),
        ("[3][1][0][0][1]['shared_up']", (64, 64)),
        ("[3][1][0][0][1]['w_down']", (8, 32, 64)),
        ("[3][1][0][0][1]['w_gate']", (8, 64, 32)),
        ("[3][1][0][0][1]['w_up']", (8, 64, 32)),
        ("[4][0][0][0][0]['weight']", (64,)),
        ("[4][0][0][0][1]['kv_norm']", (16,)),
        ("[4][0][0][0][1]['q_norm']", (32,)),
        ("[4][0][0][0][1]['wdkv']", (64, 20)),
        ("[4][0][0][0][1]['wdq']", (64, 32)),
        ("[4][0][0][0][1]['wo']", (32, 64)),
        ("[4][0][0][0][1]['wukv']", (16, 64)),
        ("[4][0][0][0][1]['wuq']", (32, 48)),
        ("[4][1][0][0][0]['weight']", (64,)),
        ("[4][1][0][0][1]['gate']", (64, 16)),
        ("[4][1][0][0][1]['shared_down']", (64, 64)),
        ("[4][1][0][0][1]['shared_gate']", (64, 64)),
        ("[4][1][0][0][1]['shared_up']", (64, 64)),
        ("[4][1][0][0][1]['w_down']", (8, 32, 64)),
        ("[4][1][0][0][1]['w_gate']", (8, 64, 32)),
        ("[4][1][0][0][1]['w_up']", (8, 64, 32)), ("[5]['weight']", (64,)),
        ("[6]['weight']", (211, 64)),
    ],
}


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
    cfg.update(param_dtype="float32", compute_dtype="float32")
    cm = harness.load_module(
        os.path.join(REPO, "benchmark", "configs", name + ".py"),
        "guard_cfg_" + name)
    return cfg, cm


@pytest.mark.parametrize("name", sorted(TREES))
def test_parameter_paths_and_shapes_are_the_parents(name, fresh_policy):
    import jax
    cfg, cm = _tiny(name)
    cm.set_policy(cfg)
    shapes, _ = jax.eval_shape(cm.build_model(cfg).init, jax.random.key(0))
    got = [(jax.tree_util.keystr(p), tuple(s.shape))
           for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert got == TREES[name]
    # and the reference's tree flattens to the same shapes in that order
    want = jax.eval_shape(lambda k: cm.init_params(cfg, k), jax.random.key(0))
    assert [tuple(s.shape) for s in jax.tree.leaves(want)] == \
        [s for _p, s in TREES[name]]


@pytest.mark.parametrize("name", sorted(TREES))
def test_seeded_weights_land_where_the_reference_has_them(name,
                                                          fresh_policy):
    """One forward of the program on ``program_weights``' tree against the
    reference's on the same seed, float32 both: they differ by the order of
    sums only, and by far more if one leaf sits in another's place."""
    import jax
    import jax.numpy as jnp
    cfg, cm = _tiny(name)
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    key = jax.random.key(2147483777 % 1000)
    params, state = harness.program_weights(cm, cfg, model, key)
    p0 = cm.init_params(cfg, key)
    r = np.random.default_rng(1)
    if "classes" in cfg:
        x = r.standard_normal((4, cfg["image"], cfg["image"], 3)) \
            .astype(np.float32)
        want = cm.ref.logits(cfg, p0, x, "f32")
    else:
        x = r.integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
        want = jax.nn.log_softmax(cm.ref.logits(cfg, p0, jnp.asarray(x)),
                                  -1)
    got, _ = model.apply(params, state, jnp.asarray(x), training=True,
                         rng=None)
    want = np.asarray(want)
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < TOL


def test_a_moved_leaf_fails_the_guard(fresh_policy):
    """The fault itself: GPT-2's tree with ``wq`` and ``wk`` of the first
    block changed over passes ``program_weights``' check of shapes and fails
    the forward comparison."""
    import jax
    import jax.numpy as jnp
    cfg, cm = _tiny("gpt2_medium")
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    key = jax.random.key(5)
    params, state = harness.program_weights(cm, cfg, model, key)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    i, j = (next(n for n, p in enumerate(paths) if p.endswith(f"['{w}']"))
            for w in ("wk", "wq"))
    leaves = [x for _p, x in flat]
    assert leaves[i].shape == leaves[j].shape
    leaves[i], leaves[j] = leaves[j], leaves[i]
    moved = jax.tree.unflatten(jax.tree.structure(params), leaves)
    x = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32))
    want = np.asarray(jax.nn.log_softmax(
        cm.ref.logits(cfg, cm.init_params(cfg, key), x), -1))
    got, _ = model.apply(moved, state, x, training=True, rng=None)
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() \
        > 5 * TOL
