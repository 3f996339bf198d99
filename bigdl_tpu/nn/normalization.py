"""Normalization layers.

Reference: BigDL `nn/BatchNormalization.scala` (747 LoC of hand-rolled mean/var
loops + running-stat EMA), `nn/SpatialBatchNormalization.scala`,
`nn/SpatialCrossMapLRN.scala`, `nn/SpatialWithinChannelLRN.scala`,
`nn/Normalize.scala`, `nn/SpatialDivisiveNormalization.scala`,
`nn/SpatialSubtractiveNormalization.scala`, `nn/SpatialContrastiveNormalization.scala`.

TPU-native notes: batch-norm is a fused reduce+scale XLA graph; running statistics
live in the module's `state` pytree (the functional analog of the reference's
mutable runningMean/runningVar tensors), updated only when training=True.  Under
the default jit/GSPMD data-parallel path the reductions run over the GLOBAL
logical batch — XLA inserts a (cheap, per-channel-vector) cross-device
all-reduce — i.e. sync-BN semantics out of the box.  This differs from the
reference, where each model replica normalizes over only its local sub-batch
(DistriOptimizer.scala:165-183); global stats are the statistically stronger
behavior and the natural GSPMD lowering, so it is the default here.  The
explicit `sync_axis=` + `lax.pmean` path exists for `shard_map` contexts
(bigdl_tpu.parallel), where reductions really are per-shard unless synced.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..common import get_policy
from .module import Module

__all__ = ["BatchNormalization", "SpatialBatchNormalization", "Normalize",
           "RMSNorm",
           "SpatialCrossMapLRN", "SpatialWithinChannelLRN",
           "SpatialSubtractiveNormalization", "SpatialDivisiveNormalization",
           "SpatialContrastiveNormalization"]


class BatchNormalization(Module):
    """BN over the last (feature) axis; all leading axes are reduction axes.

    Reference: nn/BatchNormalization.scala (eps/momentum/affine semantics,
    runningMean/runningVar EMA: new = (1-momentum)*old + momentum*batch).

    Training has one path, the one `resnet50.train` runs: mean and variance
    in float32 whatever the compute dtype, then one multiply-add in the
    input's dtype; the backward is autodiff's (what a hand-written backward
    and Pallas kernels were worth against it: docs/benchmarking.md).
    """

    PARAM_ROLES = {"weight": "norm_scale", "bias": "norm_scale"}

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, sync_axis: str = None):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.sync_axis = sync_axis  # mesh axis name for cross-replica sync-BN

    def _init(self, rng):
        if not self.affine:
            return {}
        dt = get_policy().param_dtype
        winit = self.weight_initializer
        w = (winit(rng, (self.n_output,), self.n_output, self.n_output, dt)
             if winit else jnp.ones((self.n_output,), dt))
        return {"weight": w, "bias": jnp.zeros((self.n_output,), dt)}

    def _init_state(self):
        dt = get_policy().param_dtype
        return {"running_mean": jnp.zeros((self.n_output,), dt),
                "running_var": jnp.ones((self.n_output,), dt)}

    def apply(self, params, state, x, *, training=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if training:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            meansq = jnp.mean(jnp.square(xf), axis=axes)
            n = x.size // x.shape[-1]
            if self.sync_axis is not None:
                # both moments, then the variance: the mean of the shards'
                # variances leaves out the variance of their means
                mean = lax.pmean(mean, self.sync_axis)
                meansq = lax.pmean(meansq, self.sync_axis)
                n = n * lax.psum(1, self.sync_axis)  # global element count
            var = meansq - jnp.square(mean)
            new_state = self._ema_update(state, mean, var, n)
        else:
            mean = state["running_mean"]
            var = state["running_var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        if self.affine:
            scale = params["weight"] * inv
            shift = params["bias"] - mean * scale
        else:
            scale = inv
            shift = -mean * inv
        y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
        return y, new_state

    def _ema_update(self, state, mean, var, n):
        """Torch-lineage convention (reference BatchNormalization.scala,
        torch BN): normalize with the BIASED batch var, but accumulate the
        UNBIASED one into the running EMA.  `n` is the element count the
        stats were computed over (per-shard or global)."""
        m = self.momentum
        unbiased = var * (n / jnp.maximum(n - 1, 1))
        dt = state["running_mean"].dtype
        return {
            "running_mean": (1 - m) * state["running_mean"]
            + m * lax.stop_gradient(mean).astype(dt),
            "running_var": (1 - m) * state["running_var"]
            + m * lax.stop_gradient(unbiased).astype(dt),
        }


class SpatialBatchNormalization(BatchNormalization):
    """BN over NHWC images: reduces over (N, H, W), per-channel stats
    (nn/SpatialBatchNormalization.scala).  Identical code path — the feature axis
    is last either way."""


class LayerNorm(Module):
    """Layer normalization over the last axis (net-new vs the 2017
    reference — required by the transformer/long-context capability,
    SURVEY.md §7; companion to nn/attention.MultiHeadAttention).  Stats in
    f32 regardless of the compute dtype, per-feature affine like BN."""

    PARAM_ROLES = {"weight": "norm_scale", "bias": "norm_scale"}

    def __init__(self, n_output: int, eps: float = 1e-5,
                 affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.affine = affine

    def _init(self, rng):
        if not self.affine:
            return {}
        dt = get_policy().param_dtype
        return {"weight": jnp.ones((self.n_output,), dt),
                "bias": jnp.zeros((self.n_output,), dt)}

    def _apply(self, params, x):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["weight"].astype(jnp.float32) + \
                params["bias"].astype(jnp.float32)
        return y.astype(x.dtype)


class RMSNorm(Module):
    """x / sqrt(mean(x^2) + eps) * weight over the last axis: LayerNorm
    without the mean and the shift.  Statistics in float32 whatever the
    compute dtype, like LayerNorm.  ``plus_one``: the zero-centred form,
    ``... * (1 + weight)`` with the weight zero at the start (the Qwen3-Next
    and Gemma families'); the parameter keeps its name and shape."""

    PARAM_ROLES = {"weight": "norm_scale"}

    def __init__(self, n_output: int, eps: float = 1e-6,
                 plus_one: bool = False):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.plus_one = plus_one

    def _init(self, rng):
        make = jnp.zeros if self.plus_one else jnp.ones
        return {"weight": make((self.n_output,), get_policy().param_dtype)}

    def _apply(self, params, x):
        return rms_norm(x, params["weight"], self.eps, self.plus_one)


def rms_norm(x, weight, eps: float, plus_one: bool = False):
    """RMSNorm's arithmetic, for layers that norm a projection inside
    themselves (nn/attention.LatentAttention, and MultiHeadAttention's
    ``qk_norm``)."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    return (y * (1.0 + w if plus_one else w)).astype(x.dtype)


class Normalize(Module):
    """L_p-normalize along the feature axis (nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def _apply(self, params, x):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(x) ** self.p, axis=-1, keepdims=True) ** (1.0 / self.p)
        return x / (norm + self.eps)


class SpatialCrossMapLRN(Module):
    """Local response normalization across channels (nn/SpatialCrossMapLRN.scala):
    y = x / (k + alpha/size * sum_{local} x^2)^beta over NHWC channels."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def _apply(self, params, x):
        half = self.size // 2
        sq = jnp.square(x)
        # sum over a sliding window along the channel axis
        summed = lax.reduce_window(
            sq, 0.0, lax.add,
            window_dimensions=(1,) * (x.ndim - 1) + (self.size,),
            window_strides=(1,) * x.ndim,
            padding=((0, 0),) * (x.ndim - 1) + ((half, self.size - half - 1),))
        denom = (self.k + self.alpha / self.size * summed) ** self.beta
        return x / denom


def _gaussian_kernel(size: int, dtype=jnp.float32):
    half = (size - 1) / 2.0
    xs = jnp.arange(size, dtype=dtype) - half
    sigma = size / 4.0 if size > 1 else 1.0
    k = jnp.exp(-jnp.square(xs) / (2 * sigma * sigma))
    return k / jnp.sum(k)


class SpatialWithinChannelLRN(Module):
    """LRN within each channel over a spatial window
    (nn/SpatialWithinChannelLRN.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75):
        super().__init__()
        self.size, self.alpha, self.beta = size, alpha, beta

    def _apply(self, params, x):
        half = self.size // 2
        pad = (half, self.size - half - 1)
        mean_sq = lax.reduce_window(
            jnp.square(x), 0.0, lax.add,
            window_dimensions=(1, self.size, self.size, 1),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), pad, pad, (0, 0))) / (self.size * self.size)
        return x / (1.0 + self.alpha * mean_sq) ** self.beta


class _GaussianBlur(Module):
    """Depthwise gaussian smoothing helper for the subtractive/divisive norms."""

    def __init__(self, size: int, n_channels: int):
        super().__init__()
        self.size, self.n_channels = size, n_channels

    def blur(self, x):
        k1 = _gaussian_kernel(self.size, x.dtype)
        kern = jnp.outer(k1, k1)[..., None, None]           # (s, s, 1, 1)
        kern = jnp.tile(kern, (1, 1, 1, x.shape[-1]))        # depthwise
        half = self.size // 2
        pad = (half, self.size - half - 1)
        return lax.conv_general_dilated(
            x, kern, (1, 1), [pad, pad],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1])


class SpatialSubtractiveNormalization(_GaussianBlur):
    """Subtract the local (gaussian-weighted) mean
    (nn/SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel_size: int = 9):
        super().__init__(kernel_size, n_input_plane)

    def _apply(self, params, x):
        # blur() is per-channel normalized; the mean over channels completes
        # the cross-plane local mean (sum over planes / nInputPlane)
        return x - jnp.mean(self.blur(x), axis=-1, keepdims=True)


class SpatialDivisiveNormalization(_GaussianBlur):
    """Divide by the local standard deviation
    (nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel_size: int = 9,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__(kernel_size, n_input_plane)
        self.threshold, self.thresval = threshold, thresval

    def _apply(self, params, x):
        local_sq = self.blur(jnp.square(x))
        std = jnp.sqrt(jnp.maximum(
            jnp.mean(local_sq, axis=-1, keepdims=True), 0.0))
        std = jnp.where(std < self.threshold, self.thresval, std)
        return x / std


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalization
    (nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel_size: int = 9,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel_size)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel_size,
                                                threshold, thresval)

    def _apply(self, params, x):
        return self.div._apply({}, self.sub._apply({}, x))
