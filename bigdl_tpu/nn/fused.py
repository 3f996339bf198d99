"""Cross-layer fusion rewrites (opt-in).

`ConvBN` fuses an adjacent (1x1 stride-1 SpatialConvolution,
SpatialBatchNormalization) pair so the BN batch statistics are accumulated
in the producing matmul's epilogue (ops/convbn.py), deleting the separate
stat read of the conv output — the round-4 verdict's untried HBM lever for
the BN-bound ResNet-50 train MFU.

`ConvBNAddReLU` widens the same fusion to the ResNet residual tail:
ConcatTable(branch ending conv1x1+BN, shortcut) -> CAddTable -> ReLU
collapses to one `ops.convbn.fused_conv_bn_add_relu_train` call, so the
block's closing matmul, BN stats, shortcut add, and ReLU — plus their
backward — are a single kernel + elementwise epilogue instead of four
module boundaries each re-reading the activation.

The reference performs analogous whole-graph rewrites for its quantized
path (bigdl/nn/Module.scala `quantize()`, replacing Conv/Linear with
quantized twins in place); here the rewrite is `fuse_conv_bn(container)`,
walking containers and substituting `ConvBN(conv, bn)` for eligible pairs.
Run it BEFORE `build()`/loading: the fusion nests the pair's two param
entries one level deeper, so param trees built before the rewrite do not
line up.

ConvBN subclasses Sequential, so its params/state are exactly the pair's
[conv, bn] list entries and every container facility (get_parameters,
checkpoint traversal, repr) works unchanged.  When the fused path cannot
engage (eval mode, GPU backend, a multi-axis/TP mesh, non-affine BN)
it falls back to the children's own apply — numerics are identical
either way (parity-tested in tests/test_convbn.py).  On a DATA-ONLY
mesh the kernel runs per shard inside shard_map with psum'd epilogue
stats (same construction as BatchNormalization's pallas mesh route).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils import config
from .containers import ConcatTable, Sequential
from .conv import SpatialConvolution
from .module import Container
from .normalization import SpatialBatchNormalization

__all__ = ["ConvBN", "ConvBNAddReLU", "fuse_conv_bn"]


def _fusable(conv, bn) -> bool:
    return (isinstance(conv, SpatialConvolution)
            and type(conv) is SpatialConvolution  # not Map/Share subclasses
            and isinstance(bn, SpatialBatchNormalization)
            and conv.kernel == (1, 1) and conv.stride == (1, 1)
            and conv.pad == (0, 0) and conv.n_group == 1
            and bn.affine and bn.sync_axis is None
            and conv.n_output_plane == bn.n_output)


def _engagement(training: bool, batch_rows: int):
    """Shared fused-path gate for ConvBN / ConvBNAddReLU: returns
    (engaged, mesh, interpret).  Engagement mirrors
    BatchNormalization._route_pallas.  Off-TPU the kernels would run in
    interpret mode — orders of magnitude slower — so that needs the
    explicit BN_IMPL=pallas_interpret opt-in (tests/CPU smoke), never
    silence."""
    backend = jax.default_backend()
    interpret_req = config.get_str("BN_IMPL", "") == "pallas_interpret"
    multi = jax.device_count() > 1
    mesh = None
    if multi and (interpret_req or backend == "tpu"):
        # multi-device: the opaque pallas_call cannot be partitioned by
        # GSPMD directly, but on a data-only Engine mesh the kernel
        # runs per shard inside shard_map with psum'd epilogue stats —
        # identical sync-BN semantics, matmul fusion intact.  Other
        # multi-device shapes (TP meshes, no mesh) fall back to the
        # children.
        from ..utils.engine import Engine
        if SpatialBatchNormalization.shardmap_route_engages(
                Engine._mesh, batch_rows):
            mesh = Engine._mesh
    engaged = training and (mesh is not None or interpret_req
                            or (backend == "tpu" and not multi))
    interpret = interpret_req or backend != "tpu"
    assert not (interpret and backend == "tpu"), (
        "BIGDL_TPU_BN_IMPL=pallas_interpret on a TPU would interpret the "
        "kernels on the chip's host; use BN_IMPL=pallas there")
    return engaged, mesh, interpret


class ConvBN(Sequential):
    """Fused 1x1-conv + training-mode BN (see module docstring)."""

    def __init__(self, conv: SpatialConvolution,
                 bn: SpatialBatchNormalization):
        assert _fusable(conv, bn), (conv, bn)
        super().__init__(conv, bn)

    def apply(self, params, state, x, *, training=False, rng=None):
        conv, bn = self.modules
        engaged, mesh, interpret = _engagement(training, x.shape[0])
        if not engaged:
            return super().apply(params, state, x, training=training,
                                 rng=rng)
        from ..common import get_policy
        from ..ops.convbn import fused_conv_bn_train

        conv_p, bn_p = params
        n, h, w_, k = x.shape
        c = get_policy().compute_dtype  # same cast the unfused conv makes
        w2 = conv_p["weight"].reshape(k, conv.n_output_plane).astype(c)

        def run(xl, w2, cbias, gamma, beta, axis):
            r = xl.shape[0] * h * w_
            z2, mean, var = fused_conv_bn_train(
                xl.reshape(r, k).astype(c), w2, cbias, gamma, beta,
                bn.eps, interpret, axis)
            return z2.reshape(xl.shape[0], h, w_, -1), mean, var

        args = (x, w2, conv_p.get("bias"), bn_p["weight"], bn_p["bias"])
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            from ..utils.compat import shard_map_unchecked
            from ..utils.engine import Engine
            axis = Engine.DATA_AXIS
            xspec = P(axis, None, None, None)
            vspec = P(None)
            z, mean, var = shard_map_unchecked(
                lambda *a: run(*a, axis),
                mesh=mesh,
                in_specs=(xspec, vspec, vspec, vspec, vspec),
                out_specs=(xspec, vspec, vspec))(*args)
        else:
            z, mean, var = run(*args, None)
        new_bn_state = bn._ema_update(state[1], mean, var, n * h * w_)
        return z, [state[0], new_bn_state]


class ConvBNAddReLU(Container):
    """Fused residual-unit tail: the branch's closing (1x1 conv, BN) plus
    the shortcut add and block ReLU, lowered through
    `ops.convbn.fused_conv_bn_add_relu_train` so the whole tail is one
    matmul + one elementwise epilogue (stats in the matmul, relu mask
    recomputed in the backward).

    Children (in param order): [head, conv, bn, shortcut] — `head` is the
    branch minus its last conv+bn pair, `shortcut` the residual path; both
    run unfused.  Rewritten in by `fuse_conv_bn` from the reference block
    shape ConcatTable(branch, shortcut) -> CAddTable -> ReLU
    (models/resnet.py `_residual`).  When the fused path cannot engage
    (eval mode, CPU without the interpret opt-in, TP meshes, or a shortcut
    whose output shape does not match the conv's) it computes the exact
    unfused composition: relu(bn(conv(head(x))) + shortcut(x)).
    """

    def __init__(self, head: Sequential, conv: SpatialConvolution,
                 bn: SpatialBatchNormalization, shortcut):
        assert _fusable(conv, bn), (conv, bn)
        super().__init__(head, conv, bn, shortcut)

    def apply(self, params, state, x, *, training=False, rng=None):
        head, conv, bn, shortcut = self.modules
        rngs = self._split_rng(rng)
        h, new_sh = head.apply(params[0], state[0], x, training=training,
                               rng=rngs[0])
        r, new_ssc = shortcut.apply(params[3], state[3], x,
                                    training=training, rng=rngs[3])
        n, hh, ww, k = h.shape
        engaged, mesh, interpret = _engagement(training, h.shape[0])
        if engaged and tuple(r.shape) != (n, hh, ww, conv.n_output_plane):
            engaged = False  # type-A shortcuts can disagree mid-rewrite
        if not engaged:
            y, new_sc = conv.apply(params[1], state[1], h,
                                   training=training, rng=rngs[1])
            y, new_sb = bn.apply(params[2], state[2], y,
                                 training=training, rng=rngs[2])
            z = jax.nn.relu(y + r)  # CAddTable -> ReLU, verbatim
            return z, [new_sh, new_sc, new_sb, new_ssc]
        from ..common import get_policy
        from ..ops.convbn import fused_conv_bn_add_relu_train

        conv_p, bn_p = params[1], params[2]
        c = get_policy().compute_dtype
        w2 = conv_p["weight"].reshape(k, conv.n_output_plane).astype(c)

        def run(hl, rl, w2, cbias, gamma, beta, axis):
            rows = hl.shape[0] * hh * ww
            z2, mean, var = fused_conv_bn_add_relu_train(
                hl.reshape(rows, k).astype(c), w2, cbias, gamma, beta,
                rl.reshape(rows, conv.n_output_plane).astype(c),
                bn.eps, interpret, axis)
            return z2.reshape(hl.shape[0], hh, ww, -1), mean, var

        args = (h, r, w2, conv_p.get("bias"), bn_p["weight"], bn_p["bias"])
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            from ..utils.compat import shard_map_unchecked
            from ..utils.engine import Engine
            axis = Engine.DATA_AXIS
            xspec = P(axis, None, None, None)
            vspec = P(None)
            z, mean, var = shard_map_unchecked(
                lambda *a: run(*a, axis),
                mesh=mesh,
                in_specs=(xspec, xspec, vspec, vspec, vspec, vspec),
                out_specs=(xspec, vspec, vspec))(*args)
        else:
            z, mean, var = run(*args, None)
        new_bn_state = bn._ema_update(state[2], mean, var, n * hh * ww)
        return z, [new_sh, state[1], new_bn_state, new_ssc]


def fuse_conv_bn(module):
    """Recursively replace eligible adjacent (conv, bn) pairs inside every
    container with ConvBN.  Mutates and returns `module`; run before
    build()/load (the rewrite re-nests the pair's param entries)."""
    if getattr(module, "params", None) is not None:
        raise ValueError(
            "fuse_conv_bn must run BEFORE build()/load: the rewrite "
            "re-nests the fused pairs' param entries, so an already-built "
            "param tree would no longer line up with the modules")
    return _fuse(module)


def _residual_tail(kids, i):
    """Match ConcatTable(branch ... conv1x1, bn; shortcut) -> CAddTable ->
    ReLU at kids[i] (models/resnet.py `_residual`); return the
    ConvBNAddReLU replacement or None."""
    from .activation import ReLU
    from .table_ops import CAddTable
    if i + 2 >= len(kids):
        return None
    ct, add, relu = kids[i], kids[i + 1], kids[i + 2]
    if not (isinstance(ct, ConcatTable) and len(ct.modules) == 2
            and type(add) is CAddTable and type(relu) is ReLU):
        return None
    branch, shortcut = ct.modules
    if not (isinstance(branch, Sequential) and len(branch.modules) >= 2
            and _fusable(branch.modules[-2], branch.modules[-1])):
        return None
    head = _fuse(Sequential(*branch.modules[:-2]))
    return ConvBNAddReLU(head, branch.modules[-2], branch.modules[-1],
                         _fuse(shortcut))


def _fuse(module):
    if isinstance(module, (ConvBN, ConvBNAddReLU)):
        return module
    if isinstance(module, Container):
        kids = module.modules
        if isinstance(module, Sequential):
            fused, i = [], 0
            while i < len(kids):
                tail = _residual_tail(kids, i)
                if tail is not None:
                    fused.append(tail)
                    i += 3
                elif i + 1 < len(kids) and _fusable(kids[i], kids[i + 1]):
                    fused.append(ConvBN(kids[i], kids[i + 1]))
                    i += 2
                else:
                    fused.append(_fuse(kids[i]))
                    i += 1
            module.modules = fused
        else:
            module.modules = [_fuse(m) for m in kids]
    return module
