"""Plain reference of the ImageNet ResNet (He et al., arXiv:1512.03385,
Table 1): the training-mode forward pass and loss in straightforward
``jax.numpy``, float32, ``precision=highest``.  No kernel, no fusion, no
program code.

Departures from the paper, because the system under test has them
(``bigdl_tpu/models/resnet.py``, after BigDL's ``ResNet.scala``): every
convolution carries a bias; the stride of a bottleneck sits on its 3x3
convolution; shortcuts are type B (1x1 convolution and batch norm where the
shape changes).  Batch norm normalises with the batch's biased variance,
eps 1e-5.

Parameters are a list of layers in forward order, each ``{"b", "w"}``:
convolution weights HWIO, batch-norm ``w`` = gamma and ``b`` = beta, the
classifier's weight ``(classes, features)``.  Inside a residual unit the
branch's layers come first, then the shortcut's.  ``remat`` recomputes each
residual unit in the backward pass, so that batch 256 in float32 fits one
chip; it changes no number.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.common import HIGHEST, matmul, result, rounded

STAGES = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
          50: ("bottleneck", (3, 4, 6, 3)), 101: ("bottleneck", (3, 4, 23, 3))}
WIDTHS = (64, 128, 256, 512)
EPS = 1e-5


def layer_shapes(cfg) -> list:
    """[(kind, weight shape)] in forward order; kind is conv | bn | fc."""
    block, counts = STAGES[cfg["depth"]]
    exp = 4 if block == "bottleneck" else 1
    out = [("conv", (7, 7, 3, 64)), ("bn", (64,))]
    n_in = 64
    for width, count, stride in zip(WIDTHS, counts, (1, 2, 2, 2)):
        for i in range(count):
            n_out = width * exp
            if block == "bottleneck":
                out += [("conv", (1, 1, n_in, width)), ("bn", (width,)),
                        ("conv", (3, 3, width, width)), ("bn", (width,)),
                        ("conv", (1, 1, width, n_out)), ("bn", (n_out,))]
            else:
                out += [("conv", (3, 3, n_in, width)), ("bn", (width,)),
                        ("conv", (3, 3, width, width)), ("bn", (width,))]
            if n_in != n_out:
                out += [("conv", (1, 1, n_in, n_out)), ("bn", (n_out,))]
            n_in = n_out
    out.append(("fc", (cfg["classes"], n_in)))
    return out


def init_params(cfg, key) -> list:
    """Seeded weights in the paper's scheme (``ResNet.scala:100-129``): MSRA
    normal for convolutions (std sqrt(2 / (kh kw out))), gamma 1, beta 0,
    classifier uniform in +-1/sqrt(fan_in), every bias 0."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = []
    for k, (kind, shape) in zip(keys, shapes):
        if kind == "conv":
            std = (2.0 / (shape[0] * shape[1] * shape[3])) ** 0.5
            params.append({"b": jnp.zeros((shape[3],), jnp.float32),
                           "w": std * jax.random.normal(k, shape,
                                                        jnp.float32)})
        elif kind == "bn":
            params.append({"b": jnp.zeros(shape, jnp.float32),
                           "w": jnp.ones(shape, jnp.float32)})
        else:
            lim = 1.0 / shape[1] ** 0.5
            params.append({"b": jnp.zeros((shape[0],), jnp.float32),
                           "w": jax.random.uniform(k, shape, jnp.float32,
                                                   -lim, lim)})
    return params


def _conv(x, p, stride, pad, prec):
    y = lax.conv_general_dilated(
        rounded(x, prec), rounded(p["w"], prec), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return result(y, prec) + p["b"]


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * p["w"] + p["b"]


def _unit(x, layers, block, stride, prec):
    """One residual unit; ``layers`` are its own, branch first."""
    if block == "bottleneck":
        y = jax.nn.relu(_bn(_conv(x, layers[0], 1, 0, prec), layers[1]))
        y = jax.nn.relu(_bn(_conv(y, layers[2], stride, 1, prec), layers[3]))
        y = _bn(_conv(y, layers[4], 1, 0, prec), layers[5])
        rest = layers[6:]
    else:
        y = jax.nn.relu(_bn(_conv(x, layers[0], stride, 1, prec), layers[1]))
        y = _bn(_conv(y, layers[2], 1, 1, prec), layers[3])
        rest = layers[4:]
    if rest:
        x = _bn(_conv(x, rest[0], stride, 0, prec), rest[1])
    return jax.nn.relu(y + x)


def logits(cfg, params, x, prec: str = "f32", remat: bool = True):
    """[N, 224, 224, 3] float images -> [N, classes], batch statistics."""
    block, counts = STAGES[cfg["depth"]]
    per_unit = 6 if block == "bottleneck" else 4
    exp = 4 if block == "bottleneck" else 1
    x = x.astype(jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, params[0], 2, 3, prec), params[1]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    at, n_in = 2, 64
    for width, count, stride in zip(WIDTHS, counts, (1, 2, 2, 2)):
        for i in range(count):
            n = per_unit + (2 if n_in != width * exp else 0)
            s = stride if i == 0 else 1
            unit = lambda x_, l_, s=s: _unit(x_, l_, block, s, prec)
            if remat:
                unit = jax.checkpoint(unit)
            x = unit(x, params[at:at + n])
            at, n_in = at + n, width * exp
    x = jnp.mean(x, axis=(1, 2))           # 7x7 average pool, then flatten
    return matmul(x, params[at]["w"].T, prec) + params[at]["b"]


def loss(cfg, params, x, y, prec: str = "f32"):
    """Mean cross-entropy of the logits against 0-based labels."""
    logp = jax.nn.log_softmax(logits(cfg, params, x, prec), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y.astype(jnp.int32).reshape(-1, 1),
                                         axis=1))
