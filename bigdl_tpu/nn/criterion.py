"""Loss functions (criterions).

Reference: BigDL's 26 criterions, one file each under `nn/` (SURVEY.md §2.3):
AbsCriterion, BCECriterion, ClassNLLCriterion, ClassSimplexCriterion,
CosineDistanceCriterion, CosineEmbeddingCriterion, CrossEntropyCriterion,
DiceCoefficientCriterion, DistKLDivCriterion, HingeEmbeddingCriterion, L1Cost,
L1HingeEmbeddingCriterion, L1Penalty, MarginCriterion, MarginRankingCriterion,
MSECriterion, MultiCriterion, MultiLabelMarginCriterion,
MultiLabelSoftMarginCriterion, MultiMarginCriterion, ParallelCriterion,
SmoothL1Criterion, SmoothL1CriterionWithWeights, SoftMarginCriterion,
SoftmaxWithCriterion, TimeDistributedCriterion.

TPU-native notes: each criterion's core is a pure `loss(output, target)` scalar
function; `backward` is `jax.grad` of it (the reference hand-writes every
updateGradInput).  `size_average=True` (the Torch default) mean-reduces over the
batch.  Class labels are 0-based int arrays (reference uses 1-based Torch floats;
pass `one_based=True` where offered for data parity).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import Criterion

__all__ = [
    "AbsCriterion", "BCECriterion", "ClassNLLCriterion", "ClassSimplexCriterion",
    "CosineDistanceCriterion", "CosineEmbeddingCriterion", "CrossEntropyCriterion",
    "DiceCoefficientCriterion", "DistKLDivCriterion", "HingeEmbeddingCriterion",
    "L1Cost", "L1HingeEmbeddingCriterion", "L1Penalty", "MarginCriterion",
    "MarginRankingCriterion", "MSECriterion", "MultiCriterion",
    "MultiLabelMarginCriterion", "MultiLabelSoftMarginCriterion",
    "MultiMarginCriterion", "ParallelCriterion", "SmoothL1Criterion",
    "SmoothL1CriterionWithWeights", "SoftMarginCriterion", "SoftmaxWithCriterion",
    "TimeDistributedCriterion",
]


def _reduce(x, size_average: bool):
    return jnp.mean(x) if size_average else jnp.sum(x)


class AbsCriterion(Criterion):
    """mean |x - y| (nn/AbsCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        return _reduce(jnp.abs(output - target), self.size_average)


class MSECriterion(Criterion):
    """mean (x - y)^2 (nn/MSECriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        return _reduce(jnp.square(output - target), self.size_average)


class BCECriterion(Criterion):
    """Binary cross-entropy on probabilities, optional per-element weights
    (nn/BCECriterion.scala)."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = weights
        self.size_average = size_average

    def loss(self, output, target):
        eps = 1e-12
        o = jnp.clip(output, eps, 1.0 - eps)
        l = -(target * jnp.log(o) + (1.0 - target) * jnp.log1p(-o))
        if self.weights is not None:
            l = l * self.weights
        return _reduce(l, self.size_average)


class ClassNLLCriterion(Criterion):
    """Negative log-likelihood over log-probabilities (nn/ClassNLLCriterion.scala).
    Expects LogSoftMax output (batch, classes) and integer labels (batch,).
    Optional per-class `weights`; mean is weight-normalized like the reference.

    Labels are 0-based by default (idiomatic JAX); pass ``one_based=True`` for
    BigDL/Torch-style 1-based labels.  Negative labels are treated as padding
    and excluded from the loss (the standard ignore-index; the reference's
    1-based labels made 0 the natural pad sentinel — 0-based labels need an
    explicit one).  An out-of-range-high label yields NaN loss (JAX gathers
    fill out-of-bounds with NaN) — the reference instead threw
    `curTarget >= 1 && curTarget <= nClasses`; watch the logged loss.

    `label_smoothing=eps` (net-new vs the reference) mixes the one-hot
    target with the uniform distribution: loss = (1-eps)*NLL(target) +
    eps*mean over classes of -log p — the standard regularizer for
    large-vocab/ImageNet training.  Incompatible with per-class weights."""

    def __init__(self, weights=None, size_average: bool = True,
                 one_based: bool = False, label_smoothing: float = 0.0):
        super().__init__()
        self.weights = weights
        self.size_average = size_average
        self.one_based = one_based
        if not 0.0 <= label_smoothing < 1.0:
            raise ValueError(f"label_smoothing {label_smoothing}")
        if label_smoothing and weights is not None:
            raise ValueError("label_smoothing with per-class weights is "
                             "not supported")
        self.label_smoothing = label_smoothing

    def loss(self, output, target):
        t = target.astype(jnp.int32).reshape(-1)
        if self.one_based:
            t = t - 1
        valid = t >= 0
        picked = jnp.take_along_axis(output, jnp.maximum(t, 0)[:, None],
                                     axis=1)[:, 0]
        # the loss is reduced in float32 at least, whatever the compute
        # dtype: summed in bfloat16 it saturates (TimeDistributedCriterion
        # over 512 steps of a loss near 10 stops growing at 4096, and the
        # LM's first loss read 8.0 on the v5e where ln(vocab) is 10.37)
        picked = picked.astype(jnp.promote_types(picked.dtype, jnp.float32))
        if self.label_smoothing:
            eps = self.label_smoothing
            uniform = -jnp.mean(output, axis=-1,  # -E_uniform[log p]
                                dtype=picked.dtype)
            smoothed = jnp.where(valid,
                                 (1 - eps) * (-picked) + eps * uniform, 0.0)
            if self.size_average:
                return jnp.sum(smoothed) / jnp.maximum(jnp.sum(valid), 1)
            return jnp.sum(smoothed)
        if self.weights is not None:
            w = jnp.take(self.weights, jnp.maximum(t, 0)) * valid
            total = -jnp.sum(w * picked)
            return (total / jnp.maximum(jnp.sum(w), 1e-12)
                    if self.size_average else total)
        masked = jnp.where(valid, -picked, 0.0)
        if self.size_average:
            return jnp.sum(masked) / jnp.maximum(jnp.sum(valid), 1)
        return jnp.sum(masked)


class CrossEntropyCriterion(Criterion):
    """LogSoftMax + ClassNLL fused (nn/CrossEntropyCriterion.scala). Expects raw
    logits."""

    def __init__(self, weights=None, size_average: bool = True,
                 one_based: bool = False, label_smoothing: float = 0.0):
        super().__init__()
        self._nll = ClassNLLCriterion(weights, size_average, one_based,
                                      label_smoothing)

    def loss(self, output, target):
        return self._nll.loss(jax.nn.log_softmax(output, axis=-1), target)


class ClassSimplexCriterion(Criterion):
    """MSE against a regular-simplex embedding of the labels
    (nn/ClassSimplexCriterion.scala)."""

    def __init__(self, n_classes: int, size_average: bool = True,
                 one_based: bool = False):
        super().__init__()
        self.n_classes = n_classes
        self.size_average = size_average
        self.one_based = one_based
        self.simplex = self._build_simplex(n_classes)

    @staticmethod
    def _build_simplex(n):
        # unit-norm regular-simplex vertices: centered identity, row-normalized
        import numpy as np
        eye = np.eye(n, dtype=np.float32)
        centered = eye - eye.mean(axis=0, keepdims=True)
        norms = np.linalg.norm(centered, axis=1, keepdims=True)
        return jnp.asarray(centered / norms)

    def loss(self, output, target):
        t = target.astype(jnp.int32).reshape(-1)
        if self.one_based:
            t = t - 1
        goal = jnp.take(self.simplex, t, axis=0)
        return _reduce(jnp.square(output - goal), self.size_average)


class CosineDistanceCriterion(Criterion):
    """1 - cos(x, y) per row (nn/CosineDistanceCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        o = output / (jnp.linalg.norm(output, axis=-1, keepdims=True) + 1e-12)
        t = target / (jnp.linalg.norm(target, axis=-1, keepdims=True) + 1e-12)
        return _reduce(1.0 - jnp.sum(o * t, axis=-1), self.size_average)


class CosineEmbeddingCriterion(Criterion):
    """Input [x1, x2], target ±1 (nn/CosineEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 0.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def loss(self, output, target):
        x1, x2 = output[0], output[1]
        cos = (jnp.sum(x1 * x2, axis=-1) /
               (jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1) + 1e-12))
        t = jnp.reshape(target, cos.shape)
        l = jnp.where(t > 0, 1.0 - cos, jnp.maximum(0.0, cos - self.margin))
        return _reduce(l, self.size_average)


class DiceCoefficientCriterion(Criterion):
    """1 - dice overlap (nn/DiceCoefficientCriterion.scala)."""

    def __init__(self, size_average: bool = True, epsilon: float = 1.0):
        super().__init__()
        self.size_average = size_average
        self.epsilon = epsilon

    def loss(self, output, target):
        o = output.reshape(output.shape[0], -1)
        t = target.reshape(target.shape[0], -1)
        inter = jnp.sum(o * t, axis=1)
        denom = jnp.sum(o, axis=1) + jnp.sum(t, axis=1)
        dice = (2.0 * inter + self.epsilon) / (denom + self.epsilon)
        return _reduce(1.0 - dice, self.size_average)


class DistKLDivCriterion(Criterion):
    """KL(target || exp(output)): target * (log(target) - output)
    (nn/DistKLDivCriterion.scala; output is log-prob)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        l = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-12))
                                            - output), 0.0)
        if self.size_average:
            return jnp.sum(l) / output.shape[0]
        return jnp.sum(l)


class HingeEmbeddingCriterion(Criterion):
    """x if y==1 else max(0, margin - x) (nn/HingeEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def loss(self, output, target):
        l = jnp.where(target > 0, output,
                      jnp.maximum(0.0, self.margin - output))
        return _reduce(l, self.size_average)


class L1Cost(Criterion):
    """sum |x| (nn/L1Cost.scala); target ignored."""

    def loss(self, output, target=None):
        return jnp.sum(jnp.abs(output))


class L1HingeEmbeddingCriterion(Criterion):
    """L1 distance hinge on pairs [x1, x2], target ±1
    (nn/L1HingeEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 1.0):
        super().__init__()
        self.margin = margin

    def loss(self, output, target):
        d = jnp.sum(jnp.abs(output[0] - output[1]), axis=-1)
        t = jnp.reshape(target, d.shape)
        l = jnp.where(t > 0, d, jnp.maximum(0.0, self.margin - d))
        return jnp.mean(l)


class L1Penalty(Criterion):
    """L1 activation penalty pass-through (nn/L1Penalty.scala). As a criterion:
    l1weight * sum|x|."""

    def __init__(self, l1weight: float = 1.0, size_average: bool = False,
                 provide_output: bool = True):
        super().__init__()
        self.l1weight = l1weight
        self.size_average = size_average

    def loss(self, output, target=None):
        return self.l1weight * _reduce(jnp.abs(output), self.size_average)


class MarginCriterion(Criterion):
    """Hinge: max(0, margin - y*x) (nn/MarginCriterion.scala); squared variant
    gives L2-SVM."""

    def __init__(self, margin: float = 1.0, size_average: bool = True,
                 squared: bool = False):
        super().__init__()
        self.margin = margin
        self.size_average = size_average
        self.squared = squared

    def loss(self, output, target):
        l = jnp.maximum(0.0, self.margin - target * output)
        if self.squared:
            l = jnp.square(l)
        return _reduce(l, self.size_average)


class MarginRankingCriterion(Criterion):
    """max(0, -y*(x1-x2) + margin) on input [x1, x2]
    (nn/MarginRankingCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def loss(self, output, target):
        d = output[0] - output[1]
        t = jnp.reshape(target, d.shape) if hasattr(target, "shape") else target
        return _reduce(jnp.maximum(0.0, -t * d + self.margin), self.size_average)


class MultiCriterion(Criterion):
    """Weighted sum of criterions on the SAME (output, target)
    (nn/MultiCriterion.scala)."""

    def __init__(self):
        super().__init__()
        self.criterions = []
        self.weights = []

    def add(self, criterion: Criterion, weight: float = 1.0):
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def loss(self, output, target):
        return sum(w * c.loss(output, target)
                   for c, w in zip(self.criterions, self.weights))


class ParallelCriterion(Criterion):
    """Weighted sum of criterions, i-th criterion on i-th (output, target) pair
    (nn/ParallelCriterion.scala)."""

    def __init__(self, repeat_target: bool = False):
        super().__init__()
        self.criterions = []
        self.weights = []
        self.repeat_target = repeat_target

    def add(self, criterion: Criterion, weight: float = 1.0):
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def loss(self, output, target):
        total = 0.0
        for i, (c, w) in enumerate(zip(self.criterions, self.weights)):
            t = target if self.repeat_target else target[i]
            total = total + w * c.loss(output[i], t)
        return total


class MultiLabelMarginCriterion(Criterion):
    """Multi-class multi-label hinge (nn/MultiLabelMarginCriterion.scala).
    Target: (batch, n) 0-based label indices padded with -1 (reference pads with
    0 in 1-based space)."""

    def __init__(self, size_average: bool = True, one_based: bool = False):
        super().__init__()
        self.size_average = size_average
        self.one_based = one_based

    def loss(self, output, target):
        t = target.astype(jnp.int32)
        if self.one_based:
            t = t - 1  # padding 0 -> -1
        n = output.shape[-1]
        valid = t >= 0
        t_safe = jnp.maximum(t, 0)
        is_target = jnp.zeros_like(output, dtype=bool)
        batch_idx = jnp.arange(output.shape[0])[:, None]
        # .max, not .set: padding slots all scatter to index 0 and a False
        # write must not clobber a genuine class-0 True (duplicate-index
        # scatter order is unspecified)
        is_target = is_target.at[batch_idx, t_safe].max(valid)
        tgt_scores = jnp.take_along_axis(output, t_safe, axis=1)  # (b, n)
        # hinge of every non-target against every valid target
        margins = 1.0 - tgt_scores[:, :, None] + output[:, None, :]  # (b, tgt, cls)
        mask = valid[:, :, None] & (~is_target[:, None, :])
        l = jnp.sum(jnp.where(mask, jnp.maximum(0.0, margins), 0.0), axis=(1, 2)) / n
        return _reduce(l, self.size_average)


class MultiLabelSoftMarginCriterion(Criterion):
    """Sigmoid + BCE per class (nn/MultiLabelSoftMarginCriterion.scala); expects
    raw scores."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = weights
        self.size_average = size_average

    def loss(self, output, target):
        l = (jax.nn.softplus(-output) * target
             + jax.nn.softplus(output) * (1.0 - target))
        if self.weights is not None:
            l = l * self.weights
        l = jnp.mean(l, axis=-1)
        return _reduce(l, self.size_average)


class MultiMarginCriterion(Criterion):
    """Multi-class hinge (nn/MultiMarginCriterion.scala)."""

    def __init__(self, p: int = 1, weights=None, margin: float = 1.0,
                 size_average: bool = True, one_based: bool = False):
        super().__init__()
        self.p, self.weights, self.margin = p, weights, margin
        self.size_average = size_average
        self.one_based = one_based

    def loss(self, output, target):
        t = target.astype(jnp.int32).reshape(-1)
        if self.one_based:
            t = t - 1
        n = output.shape[-1]
        tgt = jnp.take_along_axis(output, t[:, None], axis=1)
        m = jnp.maximum(0.0, self.margin - tgt + output) ** self.p
        if self.weights is not None:
            m = m * jnp.take(self.weights, t)[:, None]
        onehot = jax.nn.one_hot(t, n, dtype=bool)
        l = jnp.sum(jnp.where(onehot, 0.0, m), axis=-1) / n
        return _reduce(l, self.size_average)


class SmoothL1Criterion(Criterion):
    """Huber with delta=1 (nn/SmoothL1Criterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        d = jnp.abs(output - target)
        l = jnp.where(d < 1.0, 0.5 * jnp.square(d), d - 0.5)
        return _reduce(l, self.size_average)


class SmoothL1CriterionWithWeights(Criterion):
    """Smooth-L1 with inside/outside weights and sigma, as used by Fast-RCNN
    (nn/SmoothL1CriterionWithWeights.scala). Target is a table
    [t, inside_w, outside_w] (weights optional)."""

    def __init__(self, sigma: float = 1.0, num: int = 0):
        super().__init__()
        self.sigma2 = sigma * sigma
        self.num = num

    def loss(self, output, target):
        if isinstance(target, (list, tuple)):
            t = target[0]
            in_w = target[1] if len(target) > 1 else 1.0
            out_w = target[2] if len(target) > 2 else 1.0
        else:
            t, in_w, out_w = target, 1.0, 1.0
        d = in_w * (output - t)
        ad = jnp.abs(d)
        l = jnp.where(ad < 1.0 / self.sigma2,
                      0.5 * self.sigma2 * jnp.square(d),
                      ad - 0.5 / self.sigma2)
        l = out_w * l
        total = jnp.sum(l)
        return total / self.num if self.num > 0 else total


class SoftMarginCriterion(Criterion):
    """log(1 + exp(-y*x)) (nn/SoftMarginCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def loss(self, output, target):
        return _reduce(jax.nn.softplus(-target * output), self.size_average)


class SoftmaxWithCriterion(Criterion):
    """Caffe-style SoftmaxWithLoss over NHWC spatial maps
    (nn/SoftmaxWithCriterion.scala): per-pixel cross-entropy with optional
    ignore_label; normalize_mode in {'valid','batch_size','full','none'}."""

    def __init__(self, ignore_label: int = None, normalize_mode: str = "valid",
                 one_based: bool = False):
        super().__init__()
        self.ignore_label = ignore_label
        self.normalize_mode = normalize_mode
        self.one_based = one_based

    def loss(self, output, target):
        logp = jax.nn.log_softmax(output, axis=-1)
        t = target.astype(jnp.int32)
        if self.one_based:
            t = t - 1
        valid = jnp.ones_like(t, dtype=bool) if self.ignore_label is None \
            else t != (self.ignore_label - (1 if self.one_based else 0))
        t_safe = jnp.where(valid, t, 0)
        picked = jnp.take_along_axis(logp, t_safe[..., None], axis=-1)[..., 0]
        total = -jnp.sum(jnp.where(valid, picked, 0.0))
        if self.normalize_mode == "valid":
            return total / jnp.maximum(jnp.sum(valid), 1)
        if self.normalize_mode == "batch_size":
            return total / output.shape[0]
        if self.normalize_mode == "full":
            return total / t.size
        return total


class TimeDistributedCriterion(Criterion):
    """Apply a criterion at every time step of (batch, time, ...) output
    (nn/TimeDistributedCriterion.scala)."""

    def __init__(self, critrn: Criterion, size_average: bool = False):
        super().__init__()
        self.critrn = critrn
        self.size_average = size_average

    def loss(self, output, target):
        T = output.shape[1]
        # lax.scan, not a Python loop: the body traces ONCE, so a T=512 LM
        # criterion does not unroll 512 slice+gather+mean subgraphs (plus
        # their VJPs) into the compiled train step.  A flattened single
        # call would be cheaper still but changes semantics when padding
        # varies per timestep (per-step means vs one global mean) — the
        # reference applies the criterion per step (TimeDistributed
        # Criterion.scala), so scan preserves that exactly.
        o_t = jnp.moveaxis(output, 1, 0)
        t_t = jnp.moveaxis(target, 1, 0)

        def body(acc, ot):
            o, t = ot
            return acc + self.critrn.loss(o, t), None

        # carry dtype follows the inner loss (f64 under jax_enable_x64,
        # custom criterions) — a pinned f32 carry would make scan reject
        # the promoted acc + loss
        loss_aval = jax.eval_shape(
            self.critrn.loss,
            jax.ShapeDtypeStruct(o_t.shape[1:], o_t.dtype),
            jax.ShapeDtypeStruct(t_t.shape[1:], t_t.dtype))
        total, _ = jax.lax.scan(body, jnp.zeros((), loss_aval.dtype),
                                (o_t, t_t))
        return total / T if self.size_average else total
