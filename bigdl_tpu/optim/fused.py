"""Multi-tensor fused optimizer arithmetic (apex `multi_tensor_apply` style).

The compiled train step's optimizer update is a pytree of per-leaf
elementwise ops: on a ResNet-50 that is ~160 parameters x ~3 slot trees of
tiny kernels, each paying its own launch/loop overhead and HBM round trip.
The reference hits the same shape with its flat-Tensor contract — BigDL
compacts every layer's weights/gradients into ONE contiguous pair before
`OptimMethod.optimize` runs (`AbstractModule.getParameters`,
reference Module.scala:284: "weights and gradients of this module will be
compacted to one storage"), so the update is a single vector op.  This
module is that idea under jit: the grad/param/slot trees are flattened into
a few dtype-homogeneous 1-D fused buffers, the unchanged `update` rule runs
over the fused pytree (a handful of large kernels), and the results are
split back.

Because every shipped update rule (SGD/Adam/Adagrad/Adadelta/Adamax/
RMSprop/EMA) is `jax.tree.map` of elementwise lambdas, running it over
concatenated buffers computes the identical scalar expression per element —
the fused path agrees with the per-leaf path to float tolerance (pinned by
tests/test_fused_update.py; bit-identical only where XLA happens to
contract both programs' multiply-adds alike, which jax 0.9.0's does not).  L-BFGS opts out (`supports_fused = False`):
its state ravels the parameter pytree itself, so re-fusing would reorder
the flat history vectors.

Opt-in via ``BIGDL_TPU_FUSED_UPDATE=1`` (read by `Optimizer._build_step`)
or by calling `OptimMethod.update_fused` directly.  Under ZeRO
(`ShardedDataParallel`) the fused buffers carry a `with_sharding_constraint`
over the data axis (`ShardingStrategy.fused_buffer_spec`) so the big
buffers live in 1/N slices like the per-leaf slots they replace.
"""

from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

__all__ = ["FusedLayout", "plan", "fuse", "unfuse", "fused_update"]


class FusedLayout:
    """How one parameter pytree maps onto dtype-homogeneous fused buffers.

    `groups[g]` is the ordered tuple of leaf indices fused into buffer g
    (leaf order preserved within a group, first-seen dtype order across
    groups); `shapes`/`sizes` are per-leaf.  The layout is derived from the
    PARAM tree and reused for grads and every param-shaped slot tree, so
    all of them split/concatenate identically.
    """

    def __init__(self, params):
        leaves, self.treedef = jax.tree.flatten(params)
        self.shapes = [tuple(leaf.shape) for leaf in leaves]
        self.sizes = [int(leaf.size) for leaf in leaves]
        self.dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves]
        by_dtype: dict = {}
        for i, dt in enumerate(self.dtypes):
            by_dtype.setdefault(str(dt), []).append(i)
        self.groups = tuple(tuple(v) for v in by_dtype.values())

    @property
    def n_leaves(self) -> int:
        return len(self.sizes)

    def matches(self, tree) -> bool:
        """True when `tree` has this layout's structure AND leaf shapes —
        i.e. it is a param-shaped slot tree safe to fuse with this plan.
        (Structure alone is not enough: when params are a single leaf, a
        scalar step counter is also 'one leaf' but must not be fused.)"""
        if jax.tree.structure(tree) != self.treedef:
            return False
        return all(tuple(getattr(leaf, "shape", ())) == shape
                   for leaf, shape in zip(jax.tree.leaves(tree),
                                          self.shapes))


def plan(params) -> FusedLayout:
    """Build the fused-buffer layout for a parameter pytree."""
    return FusedLayout(params)


def fuse(layout: FusedLayout, tree,
         constraint: Optional[Callable] = None) -> List[jax.Array]:
    """Flatten `tree` (params, grads, or a param-shaped slot tree) into the
    layout's fused 1-D buffers.  `constraint` (e.g. a ZeRO
    with_sharding_constraint) is applied per buffer."""
    leaves = jax.tree.leaves(tree)
    bufs = []
    for idxs in layout.groups:
        parts = [leaves[i].reshape(-1) for i in idxs]
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if constraint is not None:
            buf = constraint(buf)
        bufs.append(buf)
    return bufs


def unfuse(layout: FusedLayout, bufs: List[jax.Array]):
    """Split fused buffers back into the original tree."""
    leaves = [None] * layout.n_leaves
    for idxs, buf in zip(layout.groups, bufs):
        off = 0
        for i in idxs:
            n = layout.sizes[i]
            leaves[i] = jax.lax.slice(buf, (off,), (off + n,)).reshape(
                layout.shapes[i])
            off += n
    return jax.tree.unflatten(layout.treedef, leaves)


def _fuse_state(layout, state, constraint, path=()):
    """Replace every param-shaped subtree of an opt_state pytree with its
    fused representation, returning (fused_state, fused_paths).  Scalars
    (Adam's `t`) and any non-param-shaped leaves pass through untouched.
    The recorded paths let `_unfuse_state` undo the exact substitutions —
    update rules preserve the state scaffold (same keys, same positions),
    which every shipped method does by construction."""
    if layout.matches(state):
        return fuse(layout, state, constraint), {path}
    if isinstance(state, dict):
        out, paths = {}, set()
        for k, v in state.items():
            out[k], p = _fuse_state(layout, v, constraint, path + (k,))
            paths |= p
        return out, paths
    if isinstance(state, (list, tuple)):
        vals, paths = [], set()
        for i, v in enumerate(state):
            fv, p = _fuse_state(layout, v, constraint, path + (i,))
            vals.append(fv)
            paths |= p
        return type(state)(vals), paths
    return state, set()


def _unfuse_state(layout, state, fused_paths, path=()):
    if path in fused_paths:
        return unfuse(layout, state)
    if isinstance(state, dict):
        return {k: _unfuse_state(layout, v, fused_paths, path + (k,))
                for k, v in state.items()}
    if isinstance(state, (list, tuple)):
        return type(state)(_unfuse_state(layout, v, fused_paths, path + (i,))
                           for i, v in enumerate(state))
    return state


def fused_update(method, grads, params, state, lr,
                 constraint: Optional[Callable] = None):
    """Run `method.update` over fused buffers; the generic engine behind
    `OptimMethod.update_fused`.  Falls back to the per-leaf update when
    there is nothing to fuse (every dtype group is a single leaf — fusing
    would only add reshapes)."""
    layout = plan(params)
    if layout.n_leaves <= len(layout.groups):
        return method.update(grads, params, state, lr)
    fp = fuse(layout, params, constraint)
    fg = fuse(layout, grads, constraint)
    fs, fused_paths = _fuse_state(layout, state, constraint)
    new_fp, new_fs = method.update(fg, fp, fs, lr)
    return (unfuse(layout, new_fp),
            _unfuse_state(layout, new_fs, fused_paths))
