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

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..common import get_policy
from ..utils import config
from .module import Module

__all__ = ["BatchNormalization", "SpatialBatchNormalization", "Normalize",
           "RMSNorm",
           "SpatialCrossMapLRN", "SpatialWithinChannelLRN",
           "SpatialSubtractiveNormalization", "SpatialDivisiveNormalization",
           "SpatialContrastiveNormalization"]


def _bn_train_fwd(eps, x, weight, bias):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
    meansq = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
    var = meansq - jnp.square(mean)
    inv = lax.rsqrt(var + eps)
    scale = weight * inv
    shift = bias - mean * scale
    y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
    return y, (mean, var)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_bn_train(eps, x, weight, bias):
    """Training-mode BN with a hand-written backward.

    The autodiff backward through the explicit stat graph and this canonical
    closed form (dx = scale * (dy - mean(dy) - xhat * mean(dy*xhat))) compute
    the same values; the hand-written version pins the pass structure to one
    fused (x, dy) reduction pass plus one dx pass and saves only per-channel
    vectors (mean, inv) — x is the layer's input and already live.  Measured
    on the v5e chip via bigdl_tpu.tools.bn_experiment; enabled by
    BIGDL_TPU_BN_FUSED_VJP (see BatchNormalization).
    """
    y, _ = _bn_train_fwd(eps, x, weight, bias)
    return y


def _fused_bn_fwd_res(eps, x, weight, bias):
    y, (mean, var) = _bn_train_fwd(eps, x, weight, bias)
    inv = lax.rsqrt(var + eps)
    return y, (x, mean, inv, weight)


def _fused_bn_bwd(eps, res, dy):
    x, mean, inv, weight = res
    axes = tuple(range(x.ndim - 1))
    n = 1
    for ax in axes:
        n *= x.shape[ax]
    xhat = (x.astype(jnp.float32) - mean) * inv
    dyf = dy.astype(jnp.float32)
    sum_dy = jnp.sum(dyf, axis=axes)
    sum_dy_xhat = jnp.sum(dyf * xhat, axis=axes)
    scale = (weight * inv).astype(x.dtype)
    dx = scale * (dy
                  - (sum_dy / n).astype(x.dtype)
                  - xhat.astype(x.dtype) * (sum_dy_xhat / n).astype(x.dtype))
    return dx, sum_dy_xhat.astype(weight.dtype), sum_dy.astype(weight.dtype)


_fused_bn_train.defvjp(_fused_bn_fwd_res, _fused_bn_bwd)


class BatchNormalization(Module):

    PARAM_ROLES = {"weight": "norm_scale", "bias": "norm_scale"}
    """BN over the last (feature) axis; all leading axes are reduction axes.

    Reference: nn/BatchNormalization.scala (eps/momentum/affine semantics,
    runningMean/runningVar EMA: new = (1-momentum)*old + momentum*batch).

    Training-mode stat machinery is the measured MFU bottleneck on TPU
    (docs/benchmarking.md), so the implementation is selectable via the
    config tier (SURVEY §5.6) for `bigdl_tpu.tools.bn_experiment` to race:

    - BIGDL_TPU_BN_FUSED_VJP=1 — `_fused_bn_train`'s hand-written backward
      instead of autodiff; identical numerics, different pass structure.
    - BIGDL_TPU_BN_IMPL=pallas — the hand-scheduled Pallas kernels
      (ops/batchnorm: 2 reads + 1 write per direction, stats resident in
      VMEM).  Single device uses the fused two-phase kernel (`bn_train`);
      on a mesh the layer wraps the per-shard stat kernels in `shard_map`
      over the Engine data axis with psum'd per-channel stats
      (`bn_train_sync`) — identical sync-BN semantics to the GSPMD
      default.  `pallas_interpret` runs the kernels in interpret mode
      (CPU tests); any non-TPU backend interprets automatically.
    - BIGDL_TPU_BN_STAT_ROWS=k — ghost-batch statistics: mean/var from the
      first k rows of the batch only (shuffled batches make this a random
      subsample), cutting the stat pass's HBM reads by N/k.  Normalization
      and gradients still cover every row; stats are a biased-to-the-subset
      estimate, the same trade ghost batch norm makes deliberately.
    """

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
            impl = config.get_str("BN_IMPL", "")
            if impl.startswith("pallas") and self.affine:
                # GSPMD cannot partition the opaque pallas_call, so the
                # multi-device routes split the kernel at the cross-chip
                # reduction: per-shard Pallas stat kernels + psum of the
                # per-channel vectors (ops/batchnorm.bn_train_sync) —
                # identical sync-BN semantics to the default GSPMD path.
                out = self._route_pallas(params, state, x, axes, impl)
                if out is not None:
                    return out
            stat_rows = config.get_int("BN_STAT_ROWS", 0)
            xs = x[:stat_rows] if 0 < stat_rows < x.shape[0] else x
            xf = xs.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
            if (self.affine and self.sync_axis is None
                    and config.get_bool("BN_FUSED_VJP") and xs is x):
                return self._apply_fused(params, state, x, mean, var, axes)
            if self.sync_axis is not None:
                mean = lax.pmean(mean, self.sync_axis)
                var = lax.pmean(var, self.sync_axis)
            n = 1
            for ax in axes:
                n *= xs.shape[ax]
            if self.sync_axis is not None:
                n = n * lax.psum(1, self.sync_axis)  # global element count
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

    def _route_pallas(self, params, state, x, axes, impl):
        """Pick the Pallas BN route; None = no route applies (caller falls
        through to the jnp paths)."""
        backend = jax.default_backend()
        # interpret mode: explicit request (tests) or the CPU backend (the
        # CPU-mesh dryrun/conftest runs the same kernels simulated).  Other
        # non-TPU backends (GPU) get the jnp path instead — silently
        # simulating the kernels there would pessimize training under a
        # flag whose whole point is performance.
        if backend not in ("tpu", "cpu") and impl != "pallas_interpret":
            return None
        interpret = impl == "pallas_interpret" or backend == "cpu"
        assert not (interpret and backend == "tpu"), (
            "BIGDL_TPU_BN_IMPL=pallas_interpret on a TPU would interpret "
            "the kernels on the chip's host; use BN_IMPL=pallas there")
        if self.sync_axis is not None:
            # already inside a shard_map body (bigdl_tpu.parallel): reduce
            # over the caller's axis with psum directly
            return self._apply_pallas_sync(params, state, x,
                                           self.sync_axis, interpret)
        # mesh route FIRST (matching ConvBN.apply): under an explicit
        # pallas_interpret opt-in on a multi-device data mesh, the layer
        # must still wrap the kernel in shard_map — the single-device
        # pallas_call is opaque to GSPMD and would be all-gathered onto
        # every chip inside a multi-device jit
        if jax.device_count() > 1:
            from ..utils.engine import Engine
            mesh = Engine._mesh
            if self.shardmap_route_engages(mesh, x.shape[0]):
                return self._apply_pallas_shardmap(params, state, x, mesh,
                                                   interpret)
        if impl == "pallas_interpret" or jax.device_count() == 1:
            return self._apply_pallas(params, state, x, axes, interpret)
        return None

    @staticmethod
    def shardmap_route_engages(mesh, batch_rows: int) -> bool:
        """True when the kernel-in-shard_map route applies: a DATA-ONLY
        mesh whose data axis divides the batch.  On a multi-axis (TP) mesh
        the route's in_specs P('data', None, ...) would force the
        activation replicated over every other axis — channel-sharded
        activations would be all-gathered over 'model', worse than the jnp
        path where GSPMD keeps stats channel-sharded with zero activation
        traffic.  Shared with tools/bn_experiment's fail-loud guard so the
        two cannot drift."""
        from ..utils.engine import Engine
        return (mesh is not None and Engine.DATA_AXIS in mesh.axis_names
                and mesh.shape[Engine.DATA_AXIS] == mesh.size
                and batch_rows % mesh.shape[Engine.DATA_AXIS] == 0)

    def _apply_pallas(self, params, state, x, axes, interpret):
        from ..ops.batchnorm import bn_train
        y, mean, var = bn_train(x, params["weight"], params["bias"],
                                self.eps, 1024, interpret)
        n = 1
        for ax in axes:
            n *= x.shape[ax]
        return y, self._ema_update(state, mean, var, n)

    def _apply_pallas_sync(self, params, state, x, axis_name, interpret):
        from ..ops.batchnorm import bn_train_sync
        y, mean, var = bn_train_sync(x, params["weight"], params["bias"],
                                     self.eps, axis_name, 1024, interpret)
        n = 1
        for d in x.shape[:-1]:
            n *= d
        n = n * lax.psum(1, axis_name)
        return y, self._ema_update(state, mean, var, n)

    def _apply_pallas_shardmap(self, params, state, x, mesh, interpret):
        """Kernel-inside-shard_map sync-BN over the mesh data axis: the
        per-shard stat kernels run on each chip's local rows; the only
        cross-chip traffic is the psum of per-channel (sum, sumsq) /
        (sum dy, sum dy*xhat) vectors — the same collective the GSPMD
        lowering of the jnp path inserts."""
        from jax.sharding import PartitionSpec as P

        from ..ops.batchnorm import bn_train_sync
        from ..utils.compat import shard_map_unchecked
        from ..utils.engine import Engine

        axis = Engine.DATA_AXIS
        xspec = P(axis, *([None] * (x.ndim - 1)))
        def body(xl, w, b):  # custom_vjp: nondiff args must be positional
            return bn_train_sync(xl, w, b, self.eps, axis, 1024, interpret)
        y, mean, var = shard_map_unchecked(
            body, mesh=mesh, in_specs=(xspec, P(None), P(None)),
            out_specs=(xspec, P(None), P(None)))(
            x, params["weight"], params["bias"])
        n = 1
        for d in x.shape[:-1]:  # x is the global array here
            n *= d
        return y, self._ema_update(state, mean, var, n)

    def _apply_fused(self, params, state, x, mean, var, axes):
        n = 1
        for ax in axes:
            n *= x.shape[ax]
        y = _fused_bn_train(self.eps, x, params["weight"], params["bias"])
        return y, self._ema_update(state, mean, var, n)


class SpatialBatchNormalization(BatchNormalization):
    """BN over NHWC images: reduces over (N, H, W), per-channel stats
    (nn/SpatialBatchNormalization.scala).  Identical code path — the feature axis
    is last either way."""


class LayerNorm(Module):

    PARAM_ROLES = {"weight": "norm_scale", "bias": "norm_scale"}
    """Layer normalization over the last axis (net-new vs the 2017
    reference — required by the transformer/long-context capability,
    SURVEY.md §7; companion to nn/attention.MultiHeadAttention).  Stats in
    f32 regardless of the compute dtype, per-feature affine like BN."""

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
