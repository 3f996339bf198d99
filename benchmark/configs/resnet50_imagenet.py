"""resnet50_imagenet: how the benchmark builds this configuration out of
the program's public API, makes its weights and data from the seed, and
counts its operations.  Sizes come from resnet50_imagenet.json."""

from __future__ import annotations

import numpy as np

from benchmark.reference import common as refc
from benchmark.reference import resnet50_imagenet as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.resnet import ResNet
    return ResNet(cfg["depth"], class_num=cfg["classes"], dataset="imagenet")


def criterion(cfg):
    from bigdl_tpu.nn import CrossEntropyCriterion
    return CrossEntropyCriterion()


def optim_method(cfg):
    from bigdl_tpu.optim import SGD
    o = cfg["optimizer"]
    return SGD(learning_rate=o["lr"], momentum=o["momentum"],
               dampening=o["dampening"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def loss_fn(cfg, prec: str = "f32"):
    return lambda params, x, y: ref.loss(cfg, params, x, y, prec)


def optimizer_rule(cfg):
    """The optimizer's rule written out, for the reference to follow."""
    hyper = cfg["optimizer"]
    return refc.sgd_init, lambda p, g, s, t: refc.sgd_step(p, g, s, hyper, t)


def weight_leaves(cfg) -> np.ndarray:
    """Which leaves, in the tree's flatten order (each layer ``b`` then
    ``w``), are convolution kernels or the classifier: their gradients'
    norms are steady under rounding, where a batch norm's scale and shift
    are sums that cancel and are not (PERF.md, section 2)."""
    mask = []
    for kind, _shape in ref.layer_shapes(cfg):
        mask += {"conv": [False, True], "bn": [False, False],
                 "fc": [True, True]}[kind]
    return np.array(mask)


def update_numbers(cfg, got: dict, ref_: dict) -> dict:
    """What is compared of the program's updates (``got``: parameters after
    the first and the last followed step) against the reference's
    (``ref_``: also the seeded weights ``p0`` and the first gradient
    ``g1``).  SGD's first update gives the first gradient as the optimizer
    got it: w1 = w0 - lr (1 - dampening) g."""
    hyper, p0 = cfg["optimizer"], ref_["p0"]
    weights = weight_leaves(cfg)
    scale = 1.0 / (hyper["lr"] * (1.0 - hyper["dampening"]))
    d1 = [a - b for a, b in zip(got["p1"], p0)]
    g_got, g_ref = refc.leaf_norms(d1) * scale, refc.leaf_norms(ref_["g1"])
    # the same gradient on the classifier's bias, as the norm of the
    # difference: mean(softmax - onehot) depends on the forward pass alone,
    # so it is steady enough to tell bfloat16 from the precision below
    h = len(d1) - 2
    g_head = -scale * np.asarray(d1[h], np.float64)
    out = {"grad_norm_gap": refc.worst_leaf_gap(g_got, g_ref),
           "grad_norm_gap_weights": refc.worst_leaf_gap(g_got, g_ref, weights),
           "grad_norm_gap_median": refc.median_leaf_gap(g_got, g_ref),
           "head_grad_gap": float(np.linalg.norm(g_head - ref_["g1"][h])
                                  / np.linalg.norm(ref_["g1"][h]))}
    out.update(refc.change_numbers(got["pk"], ref_["pk"], p0, weights))
    return out


def records(cfg, traffic, seed: int):
    """``traffic['records']`` seeded images and labels: uint8 noise scaled to
    about unit variance through a table (cheap to draw; every row differs),
    in blocks drawn by a few threads, each block from the seed and its own
    index."""
    from concurrent.futures import ThreadPoolExecutor
    n, hw = traffic["records"], cfg["image"]
    x = np.empty((n, hw, hw, cfg["channels"]), np.float32)
    table = ((np.arange(256, dtype=np.float32) - 127.5)
             * np.float32(1.0 / 73.9))
    block = 128

    def fill(i):
        r = np.random.default_rng([seed, i])
        rows = x[i * block:(i + 1) * block]
        np.take(table, r.integers(0, 256, rows.shape, dtype=np.uint8),
                out=rows)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(-(-n // block))))
    y = np.random.default_rng([seed, n]).integers(
        0, cfg["classes"], n).astype(np.int32)
    return x, y


def model_flops_per_record(cfg) -> float:
    """Operations the forward and backward passes need for one image: two
    per multiply-add of every convolution and of the classifier, three times
    (forward, gradient to the input, gradient to the weight).  Counted from
    the shapes; batch norm, pooling and the loss are not matrix work and are
    left out (under 1 %)."""
    block, counts = ref.STAGES[cfg["depth"]]
    exp = 4 if block == "bottleneck" else 1
    hw = cfg["image"] // 2                       # stem, stride 2
    macs = hw * hw * 7 * 7 * cfg["channels"] * 64
    hw //= 2                                     # max pool, stride 2
    n_in = 64
    for width, count, stride in zip(ref.WIDTHS, counts, (1, 2, 2, 2)):
        for i in range(count):
            s = stride if i == 0 else 1
            out_hw, n_out = hw // s, width * exp
            if block == "bottleneck":
                macs += hw * hw * n_in * width             # 1x1, before stride
                macs += out_hw * out_hw * 9 * width * width
                macs += out_hw * out_hw * width * n_out
            else:
                macs += out_hw * out_hw * 9 * n_in * width
                macs += out_hw * out_hw * 9 * width * width
            if n_in != n_out:
                macs += out_hw * out_hw * n_in * n_out
            hw, n_in = out_hw, n_out
    macs += n_in * cfg["classes"]
    return 3.0 * 2.0 * macs
