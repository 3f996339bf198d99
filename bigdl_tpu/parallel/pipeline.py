"""Pipeline parallelism: microbatched stages over the 'pipe' axis.

Net-new capability vs the reference (SURVEY.md §2.5: BigDL has no PP).
TPU-native design: the model is a stack of stages (the standard
SPMD-pipeline restriction — structurally identical blocks, e.g. N
transformer layers).  Stage parameters are stacked along a leading axis
sharded over the mesh 'pipe' axis, so each device owns its slice of the
stack.  One `shard_map`-wrapped function runs the schedule: microbatches
flow through the stages, activations hop stage-to-stage with
`jax.lax.ppermute` over ICI.

Two schedules (``BIGDL_TPU_PIPE_SCHEDULE``, default ``gpipe``):

- **gpipe** — the whole schedule is pure jax (scan + ppermute), so
  `jax.grad` differentiates straight through it: the backward pass is
  automatically the reverse pipeline (ppermute transposes to the reverse
  ring).  Simple, but `jax.grad` of the scan IS the all-forward-then-
  all-backward order — every microbatch's activations stay live until
  the backward, so activation memory grows with the microbatch count m
  and the warmup/cooldown bubble is ``(n-1)/(m+n-1)``.
- **1f1b** — one-forward-one-backward (PipeDream-flush), explicitly
  staged from a precomputed per-tick table (`parallel/schedule.py`):
  each stage application is split into a forward that saves its stage
  *input* and a hand-applied VJP (`jax.vjp`) that recomputes the stage
  and pulls the cotangent back, driven tick by tick inside the same
  `shard_map` + `ppermute` machinery.  Steady state interleaves F and B
  so at most ~n microbatch activations are in flight per device
  (instead of m) — the schedule's stash IS the bound, sized by the
  table.  Stage grads accumulate in the table's deterministic order;
  parity vs gpipe is pinned at the documented reassociation tolerance
  (different accumulation order + recompute — same contract as ZeRO's
  fused buffers).  Cost: forwards run twice (once for the output, once
  recomputed in the backward schedule) — the full-rematerialization
  1F1B configuration, which is what makes the O(n) memory claim real.

**Interleaved virtual stages** (``BIGDL_TPU_PIPE_VIRTUAL_STAGES=v``):
each device owns v non-contiguous stage slices (global stage s on
device ``s mod n`` — the Megatron placement), so a microbatch rings the
mesh v times and the 1F1B warmup/cooldown bubble drops by ~1/v.  The
stacked stage axis is ``n*v`` rows in device-major order
(`schedule.stack_index`), role ``pipeline_stage`` unchanged.

MeshLayout promotion (ISSUE 12): :class:`GPipeSequential` wraps the
schedule as a Module whose stacked per-stage params carry the
``pipeline_stage`` role (leading stage axis sharded ``P('pipe')`` by
LayoutSharding), so the whole existing Optimizer machinery — the jitted
step, fused update, bf16 wire, donation, AOT cache, compile cards,
elastic reform — applies to the pipelined step unchanged.
:func:`partition_pipeline` builds one from any ``Sequential`` (or
linear-chain ``Graph``) whose children split into structurally identical
stages.  On a mesh without a >1 ``pipe`` axis the wrapper runs its
stages sequentially off the stacked axis — same math, no schedule — so
legacy meshes and single-device tier-1 cover the code path.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..nn.module import Module
from ..utils import config as _config
from ..utils.compat import shard_map
from . import schedule as schedule_mod
from .schedule import (build_schedule, bubble_fraction, stack_index,
                       stage_of_stack_index)

logger = logging.getLogger("bigdl_tpu")

__all__ = ["pipeline_apply", "pipeline_apply_scheduled", "stack_stage_params",
           "GPipeSequential", "partition_pipeline", "PipelinePartitionError",
           "pipe_microbatches", "pipe_schedule", "pipe_virtual_stages",
           "bubble_fraction"]


class PipelinePartitionError(TypeError):
    """A model cannot be partitioned into pipeline stages (children do
    not split into structurally identical groups, a stage carries
    running state, or the stage count disagrees with the mesh's 'pipe'
    axis).  Deliberately typed and loud: a silently unpartitioned model
    would train replicated and defeat the pipeline memory claim."""


def pipe_microbatches() -> int:
    """``BIGDL_TPU_PIPE_MICROBATCHES``: microbatches per schedule tick
    loop (default 4).  More microbatches shrink the pipeline bubble —
    fraction (n-1)/(m+n-1) under gpipe — at the cost of smaller
    per-tick matmuls (docs/parallelism.md "Choosing a schedule")."""
    return max(1, _config.get_int("PIPE_MICROBATCHES", 4))


def pipe_schedule() -> str:
    """``BIGDL_TPU_PIPE_SCHEDULE``: ``gpipe`` (default — autodiff
    through the scan, all-fwd-then-all-bwd) or ``1f1b`` (explicitly
    staged one-forward-one-backward, O(n) in-flight activations)."""
    val = _config.get_str("PIPE_SCHEDULE", "gpipe").strip().lower() or "gpipe"
    if val not in schedule_mod.SCHEDULES:
        raise ValueError(
            f"BIGDL_TPU_PIPE_SCHEDULE={val!r}: expected one of "
            f"{schedule_mod.SCHEDULES}")
    return val


def pipe_virtual_stages() -> int:
    """``BIGDL_TPU_PIPE_VIRTUAL_STAGES``: stage slices per device
    (default 1).  v>1 assigns each device v non-contiguous slices of
    the stage stack (Megatron interleaving), cutting the 1F1B bubble by
    ~1/v at the cost of v ring traversals per microbatch."""
    return max(1, _config.get_int("PIPE_VIRTUAL_STAGES", 1))


def _active_mesh() -> Optional[Mesh]:
    """The mesh in scope: the `with mesh:` context if any, else the
    Engine's already-built mesh (never triggers device discovery)."""
    try:  # private fallback, guarded like ring_attention._current_mesh
        env = jax._src.mesh.thread_resources.env
        if env.physical_mesh and not env.physical_mesh.empty:
            return env.physical_mesh
    except AttributeError:
        pass
    from ..utils.engine import Engine
    return Engine._mesh


def stack_stage_params(param_list):
    """Stack per-stage param pytrees (identical structure) along a new leading
    stage axis — the axis that shards over 'pipe'."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)


def _pipe_local(stage_params, x, *, stage_fn, axis_name: str,
                num_microbatches: int, remat: bool, vary_axes=()):
    """Inside shard_map.  stage_params: this stage's params (leading stage axis
    of size 1).  x: full local batch [B, ...] (replicated or data-sharded).
    """
    n = jax.lax.psum(1, axis_name)
    stage_id = jax.lax.axis_index(axis_name)
    my_params = jax.tree.map(lambda p: p[0], stage_params)

    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)

    m = num_microbatches
    B = x.shape[0]
    assert B % m == 0, f"batch {B} must divide into {m} microbatches"
    micro = x.reshape(m, B // m, *x.shape[1:])
    ticks = m + n - 1

    perm = [(i, (i + 1) % n) for i in range(n)]
    from .ring_attention import _pvary
    axes = (axis_name,) + tuple(a for a in vary_axes if a != axis_name)
    state0 = _pvary(jnp.zeros_like(micro[0]), axes)
    out_buf0 = _pvary(jnp.zeros_like(micro), axes)
    micro = _pvary(micro, axes)

    def tick(carry, t):
        state, out_buf = carry
        # stage 0 ingests microbatch t (while t < m); other stages use the
        # activation that arrived from the left neighbor
        feed = micro[jnp.minimum(t, m - 1)]
        inp = jnp.where(stage_id == 0, feed, state)
        y = fn(my_params, inp)
        # last stage emits microbatch t-(n-1) at tick t
        emit_idx = t - (n - 1)
        valid = emit_idx >= 0
        out_buf = jax.lax.cond(
            valid,
            lambda b: b.at[jnp.maximum(emit_idx, 0)].set(y),
            lambda b: b,
            out_buf)
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, out_buf), None

    (state, out_buf), _ = jax.lax.scan(
        tick, (state0, out_buf0), jnp.arange(ticks))
    # out_buf is only meaningful on the last stage; broadcast it ring-wise so
    # every stage returns the same tensor (out_specs replicate over 'pipe')
    out = _bcast_from(out_buf, axis_name, n - 1)
    return out.reshape(B, *out.shape[2:])


def _bcast_from(x, axis_name, src):
    """Replicate the value held by `src` to every device on the axis."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def pipeline_apply(stage_fn: Callable, stacked_params, x, *,
                   mesh: Mesh, pipe_axis: str = "pipe",
                   num_microbatches: int = 4,
                   batch_axis: Optional[str] = "data",
                   remat: bool = False):
    """Run x through N pipelined stages (classic GPipe, v=1).

    stage_fn(params_one_stage, microbatch) -> microbatch_out (same shape).
    stacked_params: pytree with leading stage axis == mesh.shape[pipe_axis]
      (see stack_stage_params).
    x: [B, ...]; num_microbatches must divide B.
    """
    n = mesh.shape[pipe_axis]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != n:
        raise ValueError(f"stacked_params leading axis {lead} != |{pipe_axis}|={n}")
    # batch_axis may be one axis name or a tuple (MeshLayout batches shard
    # over data x fsdp); absent axes drop out
    if batch_axis and not isinstance(batch_axis, (list, tuple)):
        batch_axis = (batch_axis,)
    batch = tuple(a for a in (batch_axis or ())
                  if a and a in mesh.axis_names) or None
    pspec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    xspec = P(batch)
    fn = shard_map(
        partial(_pipe_local, stage_fn=stage_fn, axis_name=pipe_axis,
                num_microbatches=num_microbatches, remat=remat,
                vary_axes=batch or ()),
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=xspec)
    return fn(stacked_params, x)


# ---------------------------------------------------------------------------
# table-driven schedules (schedule.py): gpipe x virtual stages, 1F1B
# ---------------------------------------------------------------------------

def _tables_jnp(tbl: schedule_mod.ScheduleTable) -> dict:
    """The table's per-tick int grids as [T, n] device constants."""
    fields = ("act", "slice_idx", "mb", "fwd_feed", "fwd_in_slot",
              "fwd_store_slot", "recv_f_slot", "out_idx", "bwd_feed",
              "bwd_in_slot", "bwd_x_slot", "recv_b_slot", "dx_idx")
    return {k: jnp.asarray(np.asarray(getattr(tbl, k), dtype=np.int32))
            for k in fields}


def _sched_fwd_local(stacked, x, *, tbl, stage_fn, axis_name, vary_axes=()):
    """Inside shard_map: execute a forward-only schedule table.  Pure
    jax (scan + switch + ppermute), so `jax.grad` differentiates
    straight through it — the gpipe-x-virtual-stages path."""
    tb = _tables_jnp(tbl)
    n, m, T = tbl.n_devices, tbl.microbatches, tbl.ticks
    d = jax.lax.axis_index(axis_name)
    B = x.shape[0]
    micro = x.reshape(m, B // m, *x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]
    from .ring_attention import _pvary
    axes = (axis_name,) + tuple(a for a in vary_axes if a != axis_name)
    micro = _pvary(micro, axes)
    zero = _pvary(jnp.zeros_like(micro[0]), axes)
    fstash0 = _pvary(jnp.zeros((tbl.fstash_slots + 1,) + micro.shape[1:],
                               micro.dtype), axes)
    out0 = _pvary(jnp.zeros((m + 1,) + micro.shape[1:], micro.dtype), axes)

    def tick(carry, t):
        fstash, out_buf, y_send = carry
        y_recv = jax.lax.ppermute(y_send, axis_name, perm)
        fstash = fstash.at[tb["recv_f_slot"][t, d]].set(y_recv)
        j, i = tb["slice_idx"][t, d], tb["mb"][t, d]

        def do_idle(fs, ob):
            return zero, fs, ob

        def do_fwd(fs, ob):
            x_in = jnp.where(tb["fwd_feed"][t, d] > 0, micro[i],
                             fs[tb["fwd_in_slot"][t, d]])
            p_j = jax.tree.map(lambda p: p[j], stacked)
            y = stage_fn(p_j, x_in)
            ob = ob.at[tb["out_idx"][t, d]].set(y)
            return y, fs, ob

        y_send, fstash, out_buf = jax.lax.switch(
            tb["act"][t, d], [do_idle, do_fwd], fstash, out_buf)
        return (fstash, out_buf, y_send), None

    (_, out_buf, _), _ = jax.lax.scan(tick, (fstash0, out0, zero),
                                      jnp.arange(T))
    out = _bcast_from(out_buf[:m], axis_name, n - 1)
    return out.reshape(B, *out.shape[2:])


def _sched_fwd_bwd_local(stacked, x, gy, *, tbl, stage_fn, axis_name,
                         vary_axes=()):
    """Inside shard_map: execute the combined 1F1B table — forwards
    recompute stage activations and save stage INPUTS into the bounded
    stash, backwards pop them and hand-apply the stage VJP, cotangents
    ride the reverse ring.  Returns (local stage grads [v, ...], dx).
    Stage-grad accumulation order is the table's — deterministic."""
    tb = _tables_jnp(tbl)
    n, m, T = tbl.n_devices, tbl.microbatches, tbl.ticks
    d = jax.lax.axis_index(axis_name)
    B = x.shape[0]
    micro = x.reshape(m, B // m, *x.shape[1:])
    gy_micro = gy.reshape(m, B // m, *gy.shape[1:])
    perm_f = [(i, (i + 1) % n) for i in range(n)]
    perm_b = [(i, (i - 1) % n) for i in range(n)]
    from .ring_attention import _pvary
    axes = (axis_name,) + tuple(a for a in vary_axes if a != axis_name)
    micro = _pvary(micro, axes)
    gy_micro = _pvary(gy_micro, axes)
    zero = _pvary(jnp.zeros_like(micro[0]), axes)
    fstash0 = _pvary(jnp.zeros((tbl.fstash_slots + 1,) + micro.shape[1:],
                               micro.dtype), axes)
    bstash0 = _pvary(jnp.zeros((tbl.bstash_slots + 1,) + micro.shape[1:],
                               micro.dtype), axes)
    grads0 = jax.tree.map(lambda p: _pvary(jnp.zeros_like(p), axes), stacked)
    dx0 = _pvary(jnp.zeros((m + 1,) + micro.shape[1:], micro.dtype), axes)

    def tick(carry, t):
        fstash, bstash, grads, dx_buf, y_send, g_send = carry
        y_recv = jax.lax.ppermute(y_send, axis_name, perm_f)
        g_recv = jax.lax.ppermute(g_send, axis_name, perm_b)
        fstash = fstash.at[tb["recv_f_slot"][t, d]].set(y_recv)
        bstash = bstash.at[tb["recv_b_slot"][t, d]].set(g_recv)
        j, i = tb["slice_idx"][t, d], tb["mb"][t, d]
        p_j = jax.tree.map(lambda p: p[j], stacked)

        def do_idle(fs, bs, g, dxb):
            return zero, zero, fs, bs, g, dxb

        def do_fwd(fs, bs, g, dxb):
            x_in = jnp.where(tb["fwd_feed"][t, d] > 0, micro[i],
                             fs[tb["fwd_in_slot"][t, d]])
            # stage-0 feeds are stashed at F time (arrivals were stashed
            # on receive); the slot lives until this (stage, mb)'s B
            fs = fs.at[tb["fwd_store_slot"][t, d]].set(x_in)
            y = stage_fn(p_j, x_in)
            return y, zero, fs, bs, g, dxb

        def do_bwd(fs, bs, g, dxb):
            x_saved = fs[tb["bwd_x_slot"][t, d]]
            gy_in = jnp.where(tb["bwd_feed"][t, d] > 0, gy_micro[i],
                              bs[tb["bwd_in_slot"][t, d]])
            _, pull = jax.vjp(stage_fn, p_j, x_saved)
            gp, gx = pull(gy_in)
            g = jax.tree.map(lambda G, a: G.at[j].add(a), g, gp)
            dxb = dxb.at[tb["dx_idx"][t, d]].set(gx)
            return zero, gx, fs, bs, g, dxb

        y_send, g_send, fstash, bstash, grads, dx_buf = jax.lax.switch(
            tb["act"][t, d], [do_idle, do_fwd, do_bwd],
            fstash, bstash, grads, dx_buf)
        return (fstash, bstash, grads, dx_buf, y_send, g_send), None

    (_, _, grads, dx_buf, _, _), _ = jax.lax.scan(
        tick, (fstash0, bstash0, grads0, dx0, zero, zero), jnp.arange(T))
    if axes[1:]:
        # stage params are replicated over the batch axes; each batch
        # shard computed grads from its own rows — reduce them here (the
        # autodiff paths get this from the shard_map transpose)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, axes[1:]), grads)
    dx = _bcast_from(dx_buf[:m], axis_name, 0)
    return grads, dx.reshape(B, *dx.shape[2:])


def pipeline_apply_scheduled(stage_fn: Callable, stacked_params, x, *,
                             mesh: Mesh, schedule: str,
                             virtual_stages: int = 1,
                             pipe_axis: str = "pipe",
                             num_microbatches: int = 4,
                             batch_axis=None, remat: bool = False):
    """Run x through ``n*v`` pipelined stage slices under a table-driven
    schedule (``schedule.py``).

    ``schedule="gpipe"``: the forward-only table executes and `jax.grad`
    supplies the transposed backward (all-fwd-then-all-bwd).
    ``schedule="1f1b"``: a `jax.custom_vjp` pins the backward to the
    combined 1F1B table — the forward pass saves only (params, x) as
    residuals, and the backward re-runs forwards interleaved with
    hand-applied stage VJPs, bounding in-flight activations at the
    table's stash size (~n microbatches/device) instead of m.
    """
    n = int(mesh.shape[pipe_axis])
    v = int(virtual_stages)
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != n * v:
        raise ValueError(f"stacked_params leading axis {lead} != "
                         f"|{pipe_axis}|*virtual = {n}*{v}")
    if batch_axis and not isinstance(batch_axis, (list, tuple)):
        batch_axis = (batch_axis,)
    batch = tuple(a for a in (batch_axis or ())
                  if a and a in mesh.axis_names) or None
    pspec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    xspec = P(batch)
    fwd_fn = stage_fn
    if remat:
        fwd_fn = jax.checkpoint(stage_fn)
    fwd_tbl = build_schedule("gpipe", n, num_microbatches, v)
    fwd_sm = shard_map(
        partial(_sched_fwd_local, tbl=fwd_tbl, stage_fn=fwd_fn,
                axis_name=pipe_axis, vary_axes=batch or ()),
        mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec)
    if schedule == "gpipe":
        return fwd_sm(stacked_params, x)

    bwd_tbl = build_schedule("1f1b", n, num_microbatches, v)
    bwd_sm = shard_map(
        partial(_sched_fwd_bwd_local, tbl=bwd_tbl, stage_fn=stage_fn,
                axis_name=pipe_axis, vary_axes=batch or ()),
        mesh=mesh, in_specs=(pspec, xspec, xspec),
        out_specs=(pspec, xspec))

    @jax.custom_vjp
    def run(stacked, xx):
        return fwd_sm(stacked, xx)

    def run_fwd(stacked, xx):
        # residuals: params + region input only — no per-microbatch
        # activations survive the forward pass (they are recomputed by
        # the 1F1B table's interleaved forwards)
        return fwd_sm(stacked, xx), (stacked, xx)

    def run_bwd(res, gy):
        stacked, xx = res
        return bwd_sm(stacked, xx, gy)

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x)


# ---------------------------------------------------------------------------
# MeshLayout promotion: the pipeline as a first-class Module
# ---------------------------------------------------------------------------

def _stage_signature(module: Module, params):
    """Structural identity of one stage candidate: module class chain +
    the params treedef + leaf shapes/dtypes.  Two stages with equal
    signatures can share one SPMD stage function."""
    def classes(m):
        kids = getattr(m, "modules", None)
        return (type(m).__name__,
                tuple(classes(c) for c in kids) if kids is not None else ())
    leaves, treedef = jax.tree.flatten(params)
    return (classes(module), str(treedef),
            tuple((tuple(l.shape), str(getattr(l, "dtype", "?")))
                  for l in leaves))


class GPipeSequential(Module):
    """Structurally identical stages run as a pipeline over the mesh
    'pipe' axis.

    Params are the stages' param pytrees STACKED along a new leading
    stage axis (role ``pipeline_stage`` -> ``P('pipe')`` under
    LayoutSharding), so each pipe-mesh row owns its slice(s) of the
    stack — the per-device parameter footprint is 1/n of the stage
    stack.  With ``virtual_stages=v`` (or
    ``BIGDL_TPU_PIPE_VIRTUAL_STAGES``) the stack is ``n*v`` rows in
    device-major order (`schedule.stack_index`): each device owns v
    non-contiguous interleaved stage slices.

    The schedule (``schedule=`` or ``BIGDL_TPU_PIPE_SCHEDULE``) is
    ``gpipe`` (autodiff backward) or ``1f1b`` (explicit table-driven
    one-forward-one-backward, in-flight activations capped at the
    schedule stash instead of the microbatch count).  On a mesh whose
    'pipe' axis is absent or 1-wide the stages run sequentially off the
    stacked axis — identical math, so legacy meshes degrade gracefully
    and loss parity holds by construction.

    Restrictions (the standard SPMD-pipeline contract, checked loudly):
    stages must be structurally identical, stateless (no BatchNorm
    running stats), shape-preserving, and free of per-stage randomness
    (dropout inside a stage runs in its eval form).
    """

    PARAM_ROLES = {"*": "pipeline_stage"}

    def __init__(self, stages: Sequence[Module],
                 num_microbatches: Optional[int] = None,
                 pipe_axis: str = "pipe", remat: bool = False,
                 schedule: Optional[str] = None,
                 virtual_stages: Optional[int] = None):
        super().__init__()
        if not stages:
            raise PipelinePartitionError("GPipeSequential needs >= 1 stage")
        self.stages: List[Module] = list(stages)
        self.num_microbatches = num_microbatches
        self.pipe_axis = pipe_axis
        self.remat = remat
        # schedule resolved at apply time (it never changes the param
        # layout); virtual_stages resolved NOW — it fixes the stacking
        # order of init()/partition_pipeline carry-over
        self.schedule = schedule
        self.virtual_stages = int(virtual_stages) if virtual_stages \
            else pipe_virtual_stages()
        if self.virtual_stages < 1:
            raise PipelinePartitionError(
                f"virtual_stages must be >= 1, got {self.virtual_stages}")
        if len(self.stages) % self.virtual_stages:
            raise PipelinePartitionError(
                f"{len(self.stages)} stages cannot split into "
                f"virtual_stages={self.virtual_stages} slices per device "
                "(stage count must be a multiple of virtual_stages)")
        # last microbatch count actually baked into a traced schedule
        # (the configured knob clamped to divide the batch) — the
        # Optimizer's pipe_bubble_fraction counter reads it
        self._last_microbatches: Optional[int] = None
        self._last_schedule: Optional[str] = None
        self._last_bubble: Optional[float] = None
        self._clamp_logged = None
        self._stage_state = None
        self._validate_stages()

    def _validate_stages(self):
        sigs, states = [], []
        for m in self.stages:
            p_shape, s_shape = jax.eval_shape(m.init, jax.random.key(0))
            sigs.append(_stage_signature(m, p_shape))
            states.append(s_shape)
        if any(s != sigs[0] for s in sigs[1:]):
            raise PipelinePartitionError(
                "GPipeSequential stages are not structurally identical "
                "(SPMD pipelining stacks stage params along one axis; "
                "every stage must share the module/param structure): "
                f"{[s[0] for s in sigs]}")
        if jax.tree.leaves(states[0]):
            raise PipelinePartitionError(
                f"pipeline stage {type(self.stages[0]).__name__} carries "
                "running state (e.g. BatchNorm statistics); stages must "
                "be stateless — keep stateful layers outside the "
                "pipelined region")
        # array-free state tree: safe to reuse as the per-stage template
        self._stage_state = states[0]

    def _stack_order(self) -> List[int]:
        """Pipeline-stage index held by each stack row: device-major
        (`schedule.stack_index`) so ``P('pipe')`` hands device d its v
        interleaved slices.  Identity when virtual_stages == 1."""
        v = self.virtual_stages
        n = len(self.stages) // v
        return [stage_of_stack_index(k, n, v) for k in range(len(self.stages))]

    def init(self, rng):
        keys = jax.random.split(rng, len(self.stages))
        ps = [m.init(k)[0] for m, k in zip(self.stages, keys)]
        order = self._stack_order()
        return stack_stage_params([ps[s] for s in order]), {}

    def _apply_sequential(self, params, x, training):
        v = self.virtual_stages
        n = len(self.stages) // v
        y = x
        for s in range(len(self.stages)):
            k = stack_index(s, n, v)
            pi = jax.tree.map(lambda l, _k=k: l[_k], params)
            y, _ = self.stages[0].apply(pi, self._stage_state, y,
                                        training=training, rng=None)
        return y

    def apply(self, params, state, x, *, training=False, rng=None):
        mesh = _active_mesh()
        n = len(self.stages)
        v = self.virtual_stages
        pipe_n = (int(mesh.shape[self.pipe_axis])
                  if mesh is not None and self.pipe_axis in mesh.axis_names
                  else 1)
        if pipe_n <= 1:
            # legacy/1-wide mesh: no schedule, same math
            return self._apply_sequential(params, x, training), state
        if pipe_n * v != n:
            raise PipelinePartitionError(
                f"GPipeSequential has {n} stages but the mesh "
                f"'{self.pipe_axis}' axis is {pipe_n}-wide with "
                f"virtual_stages={v} (needs {pipe_n * v} stages) — "
                f"re-partition the model "
                f"(partition_pipeline(model, {pipe_n * v})) or rebuild "
                "the layout")
        sched = self.schedule or pipe_schedule()
        batch_axes = tuple(a for a in ("data", "fsdp")
                           if a in mesh.axis_names)
        shards = 1
        for a in batch_axes:
            shards *= int(mesh.shape[a])
        local_b = x.shape[0] // max(shards, 1)
        m_req = self.num_microbatches or pipe_microbatches()
        m = m_req
        while local_b % m:  # largest feasible count <= the configured knob
            m -= 1
        if m != m_req and self._clamp_logged != (m_req, m):
            # the silent-clamp satellite (ISSUE 13): say it once, and
            # surface the effective count in step_knobs / compile cards
            # (Optimizer._refresh_pipe_effective) so records match reality
            logger.warning(
                "pipeline: BIGDL_TPU_PIPE_MICROBATCHES=%d does not divide "
                "the local batch %d; clamped to %d microbatches "
                "(bubble %.4f under %s)", m_req, local_b, m,
                bubble_fraction(pipe_n, m, sched, v), sched)
            self._clamp_logged = (m_req, m)
        self._last_microbatches = m
        self._last_schedule = sched
        self._last_bubble = bubble_fraction(pipe_n, m, sched, v)
        stage0, st = self.stages[0], self._stage_state

        def stage_fn(p, xm):
            y, _ = stage0.apply(p, st, xm, training=training, rng=None)
            return y

        if sched == "gpipe" and v == 1:
            # the classic path: pure-jax scan, jax.grad's transpose is
            # the reverse pipeline (unchanged from ISSUE 12 — AOT
            # fingerprints and numerics are byte-for-byte)
            y = pipeline_apply(stage_fn, params, x, mesh=mesh,
                               pipe_axis=self.pipe_axis, num_microbatches=m,
                               batch_axis=batch_axes or None,
                               remat=self.remat)
        else:
            y = pipeline_apply_scheduled(
                stage_fn, params, x, mesh=mesh, schedule=sched,
                virtual_stages=v, pipe_axis=self.pipe_axis,
                num_microbatches=m, batch_axis=batch_axes or None,
                remat=self.remat)
        return y, state


def _chain_modules(model) -> List[Module]:
    """Ordered child modules of a Sequential or a linear-chain Graph."""
    from ..nn.containers import Sequential
    from ..nn.graph import Graph, _InputModule
    if isinstance(model, Sequential):
        return list(model.modules)
    if isinstance(model, Graph):
        if len(model.input_nodes) != 1 or len(model.output_nodes) != 1:
            raise PipelinePartitionError(
                "pipeline partitioning needs a single-input single-output "
                f"Graph; got {len(model.input_nodes)} inputs / "
                f"{len(model.output_nodes)} outputs")
        chain = []
        for node in model.exec_order:
            if len(node.prev_nodes) > 1 or len(node.next_nodes) > 1:
                raise PipelinePartitionError(
                    "pipeline partitioning needs a LINEAR Graph (every "
                    "node one predecessor/successor); node "
                    f"{node.element.name} has {len(node.prev_nodes)} "
                    f"inputs / {len(node.next_nodes)} outputs — wrap "
                    "branches inside a single stage module instead")
            if not isinstance(node.element, _InputModule):
                chain.append(node.element)
        return chain
    raise PipelinePartitionError(
        f"cannot partition a {type(model).__name__} into pipeline stages "
        "(need a Sequential or a linear-chain Graph)")


def partition_pipeline(model, num_stages: int,
                       num_microbatches: Optional[int] = None,
                       remat: bool = False,
                       schedule: Optional[str] = None,
                       virtual_stages: Optional[int] = None):
    """Split a Sequential/Graph model over the 'pipe' axis.

    Finds the longest contiguous run of children that divides into
    `num_stages` structurally identical groups (the repeated-block body
    of a transformer-style model), wraps it in :class:`GPipeSequential`,
    and returns ``Sequential(prelude..., pipeline, postlude...)``.
    ``num_stages`` counts stage SLICES: on an n-wide pipe mesh with
    ``virtual_stages=v`` (or the env knob) partition into ``n*v``.
    Already-built params are carried over (stage groups stacked along
    the new stage axis in the schedule's device-major order), so the
    partitioned model computes exactly what the original did.  Raises
    :class:`PipelinePartitionError` when no such run exists.
    """
    from ..nn.containers import Sequential
    num_stages = int(num_stages)
    if num_stages < 1:
        raise PipelinePartitionError(f"num_stages must be >= 1, "
                                     f"got {num_stages}")
    children = _chain_modules(model)
    shapes = [jax.eval_shape(m.init, jax.random.key(0))[0]
              for m in children]
    sigs = [_stage_signature(m, p) for m, p in zip(children, shapes)]
    L = len(children)
    best = None  # (region_len, start, group_len)
    for g in range(L // num_stages, 0, -1):
        span = g * num_stages
        for start in range(0, L - span + 1):
            groups = [tuple(sigs[start + i * g: start + (i + 1) * g])
                      for i in range(num_stages)]
            if all(gr == groups[0] for gr in groups[1:]):
                cand = (span, start, g)
                if best is None or cand[0] > best[0]:
                    best = cand
        if best is not None:
            break  # g decreases: the first hit is the longest region
    if best is None:
        raise PipelinePartitionError(
            f"cannot split {L} children into {num_stages} structurally "
            "identical contiguous stages — pipeline partitioning needs a "
            "repeated-block body (e.g. N identical transformer blocks); "
            f"child classes: {[type(m).__name__ for m in children]}")
    span, start, g = best
    groups = [children[start + i * g: start + (i + 1) * g]
              for i in range(num_stages)]
    stage_mods = [ms[0] if g == 1 else Sequential(*ms) for ms in groups]
    pipe = GPipeSequential(stage_mods, num_microbatches=num_microbatches,
                           remat=remat, schedule=schedule,
                           virtual_stages=virtual_stages)
    out = Sequential(*children[:start], pipe, *children[start + span:])
    if getattr(model, "params", None) is not None and \
            isinstance(model, Sequential):
        cp = list(model.params)  # child params, list-aligned
        if not (isinstance(cp, list) and len(cp) == L):
            raise PipelinePartitionError(
                "built model params are not child-aligned; rebuild the "
                "model before partitioning")
        stage_params = [cp[start + i * g: start + (i + 1) * g]
                        for i in range(num_stages)]
        if g == 1:
            stage_params = [sp[0] for sp in stage_params]
        order = pipe._stack_order()
        stacked = stack_stage_params([stage_params[s] for s in order])
        out.params = (cp[:start] + [stacked] + cp[start + span:])
        st = list(model.state) if isinstance(model.state, list) else None
        out.state = ((st[:start] + [{}] + st[start + span:])
                     if st is not None and len(st) == L else None)
        if out.state is None:
            _, out.state = out.init(jax.random.key(0))
        out.grads = jax.tree.map(jnp.zeros_like, out.params)
    return out
