"""MeshLayout: named data/fsdp/tp/pipe/expert mesh axes + role-based
PartitionSpecs.

The reference BigDL only ever scales out with synchronous data-parallel
SGD over the Spark block manager: every node holds a FULL parameter
replica (parameters/AllReduceParameter.scala), so the largest trainable
model is whatever fits one node.  This module is the unlocking refactor
(ROADMAP item 2): a first-class mesh/layout subsystem in the shape of
the MLPerf TPU-pods recipe (PAPERS.md; SNIPPETS.md [2]/[3]) —

- a :class:`MeshLayout` config naming the three canonical axes
  ``data x fsdp x tp`` with their sizes.  ``(W, 1, 1)`` degrades to
  today's pure data parallelism; ``(1, 1, 1)`` is the single-device CPU
  case, so tier-1 covers every code path.
- a canonical table of per-ROLE PartitionSpecs (``kernel_out`` /
  ``kernel_in`` / ``conv_kernel`` / ``embedding_row`` / ``bias`` /
  ``norm_scale`` / ``elementwise`` / ``scalar``).  Modules declare
  roles, not specs: ``Linear``/``Conv``/``LookupTable``/
  ``BatchNormalization``/the recurrent cells each carry a
  ``PARAM_ROLES`` map from parameter name to role string
  (nn/module.Module.param_roles), and :func:`assign_specs` resolves
  every leaf of the param tree to a spec by walking the module tree in
  parallel — failing LOUDLY (:class:`UnannotatedParameterError`) on any
  leaf whose module never declared a role, instead of silently
  replicating a 10 GB embedding table.

Semantics of the axes (all composed in ONE jit/GSPMD program, like the
existing strategies — parallel/sharding.py):

- ``data``: pure data parallelism.  The batch shards over it; params
  replicate across it.
- ``fsdp``: ZeRO-3/FSDP.  Params (and their optimizer slots, which
  inherit the param shardings through
  ``ShardingStrategy.opt_state_sharding``) live in 1/N shards along a
  per-role axis; GSPMD all-gathers them at use and reduce-scatters the
  gradients back.  The BATCH also shards over ``fsdp`` (it is a second
  data axis — each fsdp group sees different rows), which is what makes
  per-device parameter+slot memory drop by ~N while the global batch
  scales.
- ``tp``: Megatron-style tensor parallelism.  Wide ``Linear`` output
  axes and ``LookupTable`` rows split over it; the batch REPLICATES
  across it (every tp shard sees the same rows and computes a slice of
  the features).
- ``pipe``: GPipe-style pipeline stages (parallel/pipeline).  A
  ``GPipeSequential``'s stacked per-stage parameters shard their
  leading stage axis over it (role ``pipeline_stage``); the batch
  replicates across it and flows through the stages microbatched.
- ``expert``: expert parallelism (parallel/expert).  ``MoEFFN``'s
  stacked per-expert tables shard their leading expert axis over it
  (role ``expert_table``); tokens reach their experts via the
  all-to-all GSPMD inserts for the dispatch/combine einsums.

``pipe`` and ``expert`` default to 1 and a layout with both at 1 builds
the SAME 3-axis ``(data, fsdp, tp)`` mesh as before — every existing
code path, test, and AOT fingerprint is unchanged until an axis is
actually requested.

Because sharding under GSPMD never changes program semantics — only
layout and collective placement — a role assignment is always CORRECT;
divisibility is checked per leaf and any axis that does not divide
simply drops out of the spec (that leaf replicates along it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import config as _config

__all__ = ["MeshLayout", "UnannotatedParameterError", "MeshReformError",
           "assign_specs", "assign_shardings", "role_tree", "ROLES",
           "fsdp_min_size"]

#: canonical axis names, in mesh order
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TP_AXIS = "tp"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

#: the canonical roles (documented in docs/parallelism.md).  Each maps to
#: (tp_axis_index, fsdp_axis_index) into the LEAF's shape — None = the
#: role never uses that mesh axis; negative indices are python-style.
#: ``embedding_row`` is special-cased in _spec_for: its first axis takes
#: BOTH fsdp and tp (rows shard over fsdp x tp, SNIPPETS.md [2]).
ROLES: Dict[str, Tuple[Optional[int], Optional[int]]] = {
    # out-major kernels, e.g. Linear's (out, in): tp splits the output
    # features (column-parallel), fsdp slices the trailing axis
    "kernel_out": (0, -1),
    # in-major kernels, e.g. RNN/attention (in, out): tp splits the
    # trailing output axis, fsdp slices the input axis before it
    "kernel_in": (-1, -2),
    # in-major kernels that every tensor-parallel share applies whole (a
    # latent projection all heads read, a router): never split over tp,
    # fsdp slices the input axis
    "kernel_whole": (None, -2),
    # HWIO/DHWIO conv kernels (.., cin, cout): tp on cout, fsdp on cin
    "conv_kernel": (-1, -2),
    # (vocab, emb) tables: rows over fsdp x tp together (see _spec_for)
    "embedding_row": (None, 0),
    # small per-feature vectors: replicated everywhere
    "bias": (None, None),
    "norm_scale": (None, None),
    "elementwise": (None, None),
    "scalar": (None, None),
    # stacked per-stage params [n_stages, ...]: leading axis over 'pipe'
    # (parallel/pipeline.GPipeSequential; see _spec_for special case)
    "pipeline_stage": (None, None),
    # stacked per-expert tables [E, ...]: leading axis over 'expert' the
    # way embedding_row shards LookupTable rows, with an fsdp fallback on
    # the remaining axes (parallel/expert.MoEFFN; _spec_for special case)
    "expert_table": (None, None),
    # decode KV caches [slots, cache_len, heads x head_dim]: slots shard
    # over data x fsdp like batch rows, the last axis (the heads side by
    # side, as the k/v projections give them) over tp to match the
    # column-parallel q/k/v kernels (models/decode.py, serve/decode.py;
    # _spec_for special case — never min_size-gated: a cache that stops
    # matching its attention kernels' sharding forces a resharding
    # collective per decode step)
    "kv_cache": (None, None),
    # decode caches with no head axis, [slots, cache_len, width]: what all
    # heads of a latent-attention layer share (nn/attention.LatentAttention).
    # Slots over data x fsdp as for kv_cache; every tp share holds the whole
    # latent, as it applies the latent projections whole
    "latent_cache": (None, None),
    # a recurrent layer's state of fixed size a row, [slots, heads, ...]
    # (nn/mamba.Mamba2Mixer ``ssm``): slots as for kv_cache, the heads'
    # axis, the second, over tp
    "ssm_state": (None, None),
}


class UnannotatedParameterError(TypeError):
    """A parameter leaf reached the layout assigner without a declared
    role: the owning Module neither sets ``PARAM_ROLES`` nor overrides
    ``param_roles()``.  Deliberately loud — a silently replicated leaf
    defeats the whole memory claim of FSDP/TP (a 10 GB table would
    quietly land on every chip)."""


class MeshReformError(RuntimeError):
    """An elastic re-form — shrink after a host loss OR grow when a
    returning host is admitted — cannot keep the layout's ``fsdp x tp``
    (x pipe x expert) block intact on the new device set (device count
    is not a multiple of the non-data block).  Typed so the elastic
    retry loop can distinguish 'unrecoverable topology' from transient
    faults."""


def fsdp_min_size() -> int:
    """``BIGDL_TPU_FSDP_MIN_SIZE``: leaves smaller than this many
    elements stay replicated instead of fsdp-sharded (tiny shards cost
    more in collective latency than they save in HBM)."""
    return _config.get_int("FSDP_MIN_SIZE", 2 ** 12)


@dataclass(frozen=True)
class MeshLayout:
    """Axis names + sizes of the canonical ``data x fsdp x tp x pipe x
    expert`` mesh.

    ``(W, 1, 1)`` is today's pure data parallelism; ``(1, 1, 1)`` the
    single-device case — size-1 axes still EXIST in the mesh (specs can
    always name them; sharding over a 1-axis is the identity), so the
    same compiled-step code path covers every configuration.  ``pipe``
    and ``expert`` default to 1 and STAY OUT of the built mesh then
    (the mesh is the 3-axis triple, byte-for-byte the pre-pipeline
    behavior — same AOT fingerprints); any 5-axis layout builds the
    full 5-axis mesh, with size-1 axes present so specs can name them.
    """

    data: int = 1
    fsdp: int = 1
    tp: int = 1
    pipe: int = 1
    expert: int = 1

    AXES = (DATA_AXIS, FSDP_AXIS, TP_AXIS, PIPE_AXIS, EXPERT_AXIS)
    LEGACY_AXES = (DATA_AXIS, FSDP_AXIS, TP_AXIS)

    @property
    def sizes(self) -> Tuple[int, ...]:
        """Sizes matching :meth:`axis_names` (3-tuple at
        pipe=expert=1, else the full 5-tuple)."""
        if self.pipe == 1 and self.expert == 1:
            return (self.data, self.fsdp, self.tp)
        return (self.data, self.fsdp, self.tp, self.pipe, self.expert)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return self.LEGACY_AXES if len(self.sizes) == 3 else self.AXES

    @property
    def size(self) -> int:
        return self.data * self.fsdp * self.tp * self.pipe * self.expert

    def __post_init__(self):
        if min(self.data, self.fsdp, self.tp, self.pipe, self.expert) < 1:
            raise ValueError(f"MeshLayout axis sizes must be >= 1: {self}")

    @classmethod
    def parse(cls, text: str) -> "MeshLayout":
        """'2,2,1' (data,fsdp,tp) or '1,1,1,2,1' (data,fsdp,tp,pipe,
        expert) -> MeshLayout — the string spelling
        (`TopologyRouter(layout=...)`).  3-tuples stay valid: absent axes
        default to 1."""
        parts = [int(p) for p in str(text).replace("x", ",").split(",")]
        if len(parts) not in (3, 5):
            raise ValueError(
                f"layout {text!r}: expected 'data,fsdp,tp' (3 ints) or "
                "'data,fsdp,tp,pipe,expert' (5 ints)")
        return cls(*parts)

    @classmethod
    def of_mesh(cls, mesh: Mesh) -> Optional["MeshLayout"]:
        """Recover the layout from a mesh built by build_mesh (axis
        names are the canonical triple or quintuple); None for legacy
        meshes."""
        names = tuple(mesh.axis_names)
        if names not in (cls.AXES, cls.LEGACY_AXES):
            return None
        return cls(*(int(mesh.shape[a]) for a in names))

    def build_mesh(self, devices: Optional[Sequence] = None) -> Mesh:
        """The jax Mesh: `devices` (default jax.devices()) reshaped to
        the layout's axis sizes.  Extra devices beyond the layout's size
        are left out (a (2,2,1) layout on an 8-device host uses 4)."""
        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < self.size:
            raise ValueError(
                f"MeshLayout {self.sizes} needs {self.size} devices, "
                f"have {len(devs)}")
        arr = np.array(devs[: self.size]).reshape(self.sizes)
        return Mesh(arr, self.axis_names)

    def install(self, devices: Optional[Sequence] = None) -> Mesh:
        """Build the mesh and make it the Engine's process-wide mesh."""
        from ..utils.engine import Engine
        mesh = self.build_mesh(devices)
        Engine.set_mesh(mesh)
        return mesh

    # -- spec resolution ------------------------------------------------

    def batch_spec(self) -> P:
        """Batch rows shard over data x fsdp (fsdp is a second data
        axis); tp, pipe, and expert replicate the batch."""
        return P((DATA_AXIS, FSDP_AXIS))

    def spec_for(self, role: str, shape: Sequence[int],
                 min_size: Optional[int] = None) -> P:
        """The canonical PartitionSpec for one leaf: the role's table
        entry, pruned per-leaf for divisibility (an axis that does not
        divide the assigned dimension drops out — correctness never
        depends on the spec, only placement does)."""
        if role not in ROLES:
            raise KeyError(
                f"unknown parameter role {role!r}; known roles: "
                f"{sorted(ROLES)} (extend parallel/layout.ROLES)")
        shape = tuple(int(d) for d in shape)
        ndim = len(shape)
        size = int(np.prod(shape)) if shape else 1
        if min_size is None:
            min_size = fsdp_min_size()
        parts: list = [None] * ndim

        def norm(ax: Optional[int]) -> Optional[int]:
            if ax is None or ndim == 0:
                return None
            ax = ax if ax >= 0 else ndim + ax
            return ax if 0 <= ax < ndim else None

        tp_ax, fsdp_ax = ROLES[role]
        if role == "pipeline_stage" and ndim >= 1:
            # the stacked per-stage leading axis over 'pipe'; a 1-wide
            # (or legacy) layout leaves the stack replicated — the GPipe
            # wrapper then runs its stages sequentially, same math
            if self.pipe > 1 and shape[0] % self.pipe == 0:
                parts[0] = PIPE_AXIS
            return P(*parts)
        if role == "expert_table" and ndim >= 1:
            # stacked expert tables [E, ...]: experts over 'expert' the
            # way embedding_row shards vocab rows; the per-expert slices
            # can additionally fsdp-shard over a remaining divisible
            # axis (largest first) so a fsdp x expert layout stacks both
            # memory wins
            if self.expert > 1 and shape[0] % self.expert == 0 and \
                    size >= min_size:
                parts[0] = EXPERT_AXIS
            if self.fsdp > 1 and size >= min_size:
                for ax in sorted(range(ndim), key=lambda i: -shape[i]):
                    if parts[ax] is None and shape[ax] % self.fsdp == 0:
                        parts[ax] = FSDP_AXIS
                        break
            return P(*parts)
        if role in ("kv_cache", "latent_cache", "ssm_state") and ndim >= 2:
            # [slots, cache_len, width]: slots ride the batch axes (data
            # x fsdp, degrading like embedding_row when the slot count
            # does not divide the product); a kv_cache's width is its
            # heads side by side and rides tp, so each device holds
            # exactly the columns its column-parallel k/v kernels
            # produce.  No min_size gate.
            if self.data * self.fsdp > 1:
                if shape[0] % (self.data * self.fsdp) == 0:
                    parts[0] = (DATA_AXIS, FSDP_AXIS)
                elif self.data > 1 and shape[0] % self.data == 0:
                    parts[0] = DATA_AXIS
                elif self.fsdp > 1 and shape[0] % self.fsdp == 0:
                    parts[0] = FSDP_AXIS
            # (a kv_cache of fewer key-value heads than tp shares is split
            # inside a head: still each device's columns of k and v)
            tp_ax = {"kv_cache": ndim - 1, "ssm_state": 1}.get(role)
            if tp_ax is not None and self.tp > 1 \
                    and shape[tp_ax] % self.tp == 0:
                parts[tp_ax] = TP_AXIS
            return P(*parts)
        if role == "embedding_row" and ndim >= 1:
            # rows over fsdp x tp together — folding 'expert' in too when
            # it exists and divides (a wide-embedding recommender under
            # an expert layout has no reason to replicate tables across
            # the expert axis); degrade to fsdp x tp, then fsdp alone,
            # then tp alone, when the vocab axis does not divide
            if self.fsdp * self.tp > 1 and size >= min_size:
                if self.expert > 1 and \
                        shape[0] % (self.fsdp * self.tp * self.expert) == 0:
                    parts[0] = (FSDP_AXIS, TP_AXIS, EXPERT_AXIS)
                elif shape[0] % (self.fsdp * self.tp) == 0:
                    parts[0] = (FSDP_AXIS, TP_AXIS)
                elif shape[0] % self.fsdp == 0 and self.fsdp > 1:
                    parts[0] = FSDP_AXIS
                elif shape[0] % self.tp == 0 and self.tp > 1:
                    parts[0] = TP_AXIS
            return P(*parts)
        tp_ax = norm(tp_ax)
        if tp_ax is not None and self.tp > 1 and \
                shape[tp_ax] % self.tp == 0 and size >= min_size:
            parts[tp_ax] = TP_AXIS
        # roles with NO designated fsdp axis (bias/norm_scale/...) are
        # replicated by contract — the fallback search below is only for
        # kernel-class roles whose designated axis fails divisibility
        if fsdp_ax is not None and self.fsdp > 1 and size >= min_size:
            fsdp_ax = norm(fsdp_ax)
            # the role's designated axis first, then any other free axis
            # largest-first (the ShardedDataParallel fallback) so big
            # leaves with an awkward designated axis still shard
            candidates = ([fsdp_ax] if fsdp_ax is not None else []) + \
                sorted((i for i in range(ndim)), key=lambda i: -shape[i])
            for ax in candidates:
                if parts[ax] is None and shape[ax] % self.fsdp == 0:
                    parts[ax] = FSDP_AXIS
                    break
        return P(*parts)


# ---------------------------------------------------------------------------
# the name+role-based assigner: module tree -> role tree -> spec tree
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    """Last string key on a tree path ('' for pure-index paths)."""
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return ""


def role_tree(module, params):
    """Mirror `params` with the ROLE of every leaf, resolved from the
    owning module's annotations.

    The walk follows the Container/Graph convention (nn/module): a
    module with a ``modules`` list keeps child params list-aligned, so
    recursion pairs each child with its slot (the `_walk_scales`
    pattern).  Within a leaf module, roles come from
    ``Module.param_roles()`` keyed by the leaf's dict name (nested
    dicts resolve by their innermost name; ``"*"`` is a wildcard).
    Any leaf without a role raises :class:`UnannotatedParameterError`
    naming the module and parameter.
    """
    def walk(mod, p):
        children = getattr(mod, "modules", None)
        if children is not None and isinstance(p, list) and \
                len(children) == len(p):
            return [walk(c, cp) for c, cp in zip(children, p)]
        roles = mod.param_roles() if hasattr(mod, "param_roles") else None

        def f(path, leaf):
            name = _leaf_name(path)
            if roles is not None:
                if name in roles:
                    return roles[name]
                if "*" in roles:
                    return roles["*"]
            raise UnannotatedParameterError(
                f"{type(mod).__name__} parameter {name or path!r} "
                f"(shape {tuple(getattr(leaf, 'shape', ()))}) has no "
                "declared role: set PARAM_ROLES on the module class "
                "(e.g. {'weight': 'kernel_out', 'bias': 'bias'}) or "
                "override param_roles() — see docs/parallelism.md. "
                "Refusing to guess: a silently replicated leaf defeats "
                "the FSDP/TP memory claim.")

        return jax.tree_util.tree_map_with_path(f, p)

    return walk(module, params)


def assign_specs(module, params, layout: MeshLayout,
                 min_size: Optional[int] = None):
    """params-shaped tree of PartitionSpecs (role table applied)."""
    roles = role_tree(module, params)
    return jax.tree.map(
        lambda leaf, role: layout.spec_for(role, getattr(leaf, "shape", ()),
                                           min_size=min_size),
        params, roles)


def assign_shardings(module, params, mesh: Mesh,
                     layout: Optional[MeshLayout] = None,
                     min_size: Optional[int] = None):
    """params-shaped tree of NamedShardings over `mesh`.  The layout is
    recovered from the mesh's canonical axes when not given; a legacy
    ('data',)-only mesh resolves to pure replication, preserving today's
    behavior."""
    if layout is None:
        layout = MeshLayout.of_mesh(mesh)
    if layout is None:
        # legacy mesh (no fsdp/tp axes): replicate — DataParallel shape
        rep = NamedSharding(mesh, P())
        return jax.tree.map(lambda _: rep, params)
    specs = assign_specs(module, params, layout, min_size=min_size)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
