"""Activation layers.

Reference: one file per activation under BigDL `nn/` — ReLU.scala, ReLU6.scala,
PReLU.scala, RReLU.scala, LeakyReLU.scala, ELU.scala, Tanh.scala, TanhShrink.scala,
Sigmoid.scala, SoftMax.scala, SoftMin.scala, SoftPlus.scala, SoftSign.scala,
SoftShrink.scala, HardShrink.scala, HardTanh.scala, Threshold.scala,
LogSoftMax.scala, LogSigmoid.scala.

TPU-native notes: every activation is a pure elementwise map that XLA fuses into the
surrounding matmul/conv — there is no per-op dispatch to a vendor library as in the
reference's MKL VML path (tensor/TensorNumeric.scala:229-312).  `inplace` flags from
the reference are meaningless under XLA (buffer reuse is the compiler's job) and are
accepted-and-ignored for API parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import Module

__all__ = ["ReLU", "ReLU6", "PReLU", "RReLU", "LeakyReLU", "ELU", "Tanh",
           "TanhShrink", "Sigmoid", "SoftMax", "SoftMin", "SoftPlus", "SoftSign",
           "SoftShrink", "HardShrink", "HardTanh", "Threshold", "LogSoftMax",
           "LogSigmoid", "SiLU"]


class ReLU(Module):
    def __init__(self, ip: bool = False):
        super().__init__()

    def _apply(self, params, x):
        return jax.nn.relu(x)


class ReLU6(Module):
    def __init__(self, ip: bool = False):
        super().__init__()

    def _apply(self, params, x):
        return jnp.clip(x, 0.0, 6.0)


class PReLU(Module):

    PARAM_ROLES = {"weight": "elementwise"}
    """Learnable leaky slope; n_output_plane=0 means one shared scalar
    (nn/PReLU.scala)."""

    def __init__(self, n_output_plane: int = 0):
        super().__init__()
        self.n_output_plane = n_output_plane

    def _init(self, rng):
        n = max(self.n_output_plane, 1)
        return {"weight": jnp.full((n,), 0.25, jnp.float32)}

    def _apply(self, params, x):
        w = params["weight"]
        if self.n_output_plane == 0:
            a = w[0]
        else:
            a = w.reshape((1,) * (x.ndim - 1) + (-1,))  # per-channel, NHWC
        return jnp.where(x >= 0, x, a * x)


class RReLU(Module):
    """Randomized leaky ReLU (nn/RReLU.scala): slope ~ U(lower, upper) in training,
    fixed mean slope in eval."""

    def __init__(self, lower: float = 1.0 / 8, upper: float = 1.0 / 3,
                 ip: bool = False):
        super().__init__()
        self.lower, self.upper = lower, upper

    def apply(self, params, state, x, *, training=False, rng=None):
        if training and rng is not None:
            a = jax.random.uniform(rng, x.shape, x.dtype, self.lower, self.upper)
        else:
            a = (self.lower + self.upper) / 2.0
        return jnp.where(x >= 0, x, a * x), state


class LeakyReLU(Module):
    def __init__(self, negval: float = 0.01, inplace: bool = False):
        super().__init__()
        self.negval = negval

    def _apply(self, params, x):
        return jnp.where(x >= 0, x, self.negval * x)


class ELU(Module):
    def __init__(self, alpha: float = 1.0, inplace: bool = False):
        super().__init__()
        self.alpha = alpha

    def _apply(self, params, x):
        return jnp.where(x > 0, x, self.alpha * jnp.expm1(x))


class Tanh(Module):
    def _apply(self, params, x):
        return jnp.tanh(x)


class TanhShrink(Module):
    def _apply(self, params, x):
        return x - jnp.tanh(x)


class Sigmoid(Module):
    def _apply(self, params, x):
        return jax.nn.sigmoid(x)


class SoftMax(Module):
    """Softmax over the last (feature) axis (nn/SoftMax.scala)."""

    def _apply(self, params, x):
        return jax.nn.softmax(x, axis=-1)


class SoftMin(Module):
    def _apply(self, params, x):
        return jax.nn.softmax(-x, axis=-1)


class SoftPlus(Module):
    def __init__(self, beta: float = 1.0):
        super().__init__()
        self.beta = beta

    def _apply(self, params, x):
        return jax.nn.softplus(self.beta * x) / self.beta


class SoftSign(Module):
    def _apply(self, params, x):
        return x / (1.0 + jnp.abs(x))


class SoftShrink(Module):
    def __init__(self, lam: float = 0.5):
        super().__init__()
        self.lam = lam

    def _apply(self, params, x):
        return jnp.where(x > self.lam, x - self.lam,
                         jnp.where(x < -self.lam, x + self.lam, 0.0))


class HardShrink(Module):
    def __init__(self, lam: float = 0.5):
        super().__init__()
        self.lam = lam

    def _apply(self, params, x):
        return jnp.where(jnp.abs(x) > self.lam, x, 0.0)


class HardTanh(Module):
    def __init__(self, min_value: float = -1.0, max_value: float = 1.0,
                 inplace: bool = False):
        super().__init__()
        self.min_value, self.max_value = min_value, max_value

    def _apply(self, params, x):
        return jnp.clip(x, self.min_value, self.max_value)


class Threshold(Module):
    """x if x > th else value (nn/Threshold.scala)."""

    def __init__(self, th: float = 1e-6, v: float = 0.0, ip: bool = False):
        super().__init__()
        # ip is semantically a no-op here (functional framework), but it is
        # part of the reference wire format — keep it so save/load through
        # interop.bigdl round-trips the flag for JVM consumers
        self.th, self.v, self.ip = th, v, bool(ip)

    def _apply(self, params, x):
        return jnp.where(x > self.th, x, self.v)


class LogSoftMax(Module):
    def _apply(self, params, x):
        return jax.nn.log_softmax(x, axis=-1)


class LogSigmoid(Module):
    def _apply(self, params, x):
        return jax.nn.log_sigmoid(x)


class SiLU(Module):
    """x * sigmoid(x): the activation of a gated MLP
    (``ConcatTable(Sequential(Linear, SiLU), Linear)`` -> ``CMulTable``)."""

    def _apply(self, params, x):
        return jax.nn.silu(x)


class GELU(Module):
    """Gaussian-error linear unit (net-new vs the 2017 reference; the
    transformer MLP activation — companion to nn/attention and
    nn.LayerNorm)."""

    def _apply(self, params, x):
        return jax.nn.gelu(x)
