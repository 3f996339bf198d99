"""Expert parallelism (EP): capacity-routed mixture-of-experts over the
`expert` mesh axis.

Net-new vs the reference (SURVEY.md §2.5: "TP / PP / SP / EP / CP ...
ABSENT"); the reference's only MoE-shaped construct is the dense
MixtureTable blend (nn/MixtureTable.scala — ours in nn/table_ops.py).
This module adds the real thing, TPU-first, in the GShard/Switch style:

- top-k softmax gating with a fixed per-expert token capacity (static
  shapes — XLA requirement; overflow tokens are dropped by the dispatch
  mask exactly as in Switch/GShard),
- dispatch/combine as einsums against a one-hot [tokens, experts,
  capacity] mask (differentiable w.r.t. the gate through the combine
  weights; the routing itself is piecewise-constant),
- two integration styles:
  * `MoEFFN` — a Module whose math is dense einsum over all experts with
    `with_sharding_constraint` hints on the expert-major buffers, so under
    jit/GSPMD on a mesh with an `expert` axis XLA shards the expert
    matmuls and inserts the all-to-alls itself (composes with the
    Optimizer's compiled step like any other layer);
  * `expert_parallel_ffn` — an explicit shard_map implementation with
    `lax.all_to_all` dispatch→compute→combine, for when the collective
    schedule must be pinned (and as the parity oracle for the GSPMD path).

A third layer, `GatedMoE`, is the dropless one that serving needs: gated
(SwiGLU) or plain two-matrix experts beside shared experts, softmax or
sigmoid scores with group-limited top-k (a selection bias that chooses but
does not weigh, renormalised weights), and a share `held=(first, count)` of
the experts: it routes over all of them and adds the terms of those it
holds.  Tokens are sorted by expert and each held expert multiplies its own
run of rows (ops/grouped.py: `lax.ragged_dot`, or a Pallas grouped matmul
where that one tiles badly), so there is no capacity and no token is dropped at any skew.

The Switch load-balancing auxiliary loss (num_experts * sum(fraction_e *
mean_prob_e)) is exposed via `load_balancing_loss`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..utils.compat import shard_map

from ..common import get_policy
from ..nn.module import Module
from ..ops.grouped import grouped_matmul

__all__ = ["MoEFFN", "expert_parallel_ffn", "top_k_routing",
           "load_balancing_loss", "GatedMoE", "group_limited_top_k"]


def top_k_routing(gate_logits, capacity: int, k: int = 1):
    """Top-k capacity routing (GShard/Switch).

    gate_logits: [T, E].  Returns (combine, dispatch, probs, assign):
      combine  [T, E, C] float — gate prob at the token's buffer slot,
      dispatch [T, E, C] bool-as-float one-hot routing mask,
      probs    [T, E] full softmax (for the aux loss),
      assign   [T, E] PRE-capacity router choices (one-hot sum over the k
               rounds) — the Switch paper's f_e uses these, NOT the
               post-drop dispatch: during heavy overflow the dispatched
               fraction saturates at C/T, which would weaken the
               anti-collapse gradient exactly when collapse is worst.
    Tokens beyond an expert's capacity C are dropped (mask row = 0) in
    priority order of their position in the batch, as in the references.
    """
    T, E = gate_logits.shape
    if k > E:
        raise ValueError(f"top-k routing with k={k} > num_experts={E}")
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    assign = jnp.zeros((T, E), jnp.float32)
    # claimed[e] tracks how many tokens already routed to expert e by
    # higher-priority choices (earlier k, earlier token)
    claimed = jnp.zeros((E,), jnp.int32)
    masked = probs
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                       # [T]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # [T, E]
        # position of each token within its chosen expert's buffer
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)        # [T, E]
        pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32) + \
            jnp.take(claimed, idx)                              # [T]
        keep = pos < capacity
        slot = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                              dtype=jnp.float32)                # [T, C]
        route = onehot[:, :, None] * slot[:, None, :]           # [T, E, C]
        gate_p = jnp.sum(probs * onehot, axis=-1, keepdims=True)  # [T, 1]
        dispatch = dispatch + route
        combine = combine + route * gate_p[:, :, None]
        assign = assign + onehot
        claimed = claimed + jnp.sum(onehot, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)  # exclude already-chosen experts
    return combine, dispatch, probs, assign


def load_balancing_loss(probs, assign):
    """Switch aux loss: E * sum_e(fraction_routed_e * mean_prob_e), with
    the fraction taken from the PRE-capacity router choices (`assign`,
    [T, E]) per the paper's f_e definition."""
    E = probs.shape[-1]
    frac = jnp.mean(assign, axis=0)                       # [E]
    mean_p = jnp.mean(probs, axis=0)                      # [E]
    return E * jnp.sum(frac * mean_p)


def _expert_ffn(x, w1, b1, w2, b2):
    """Per-expert two-layer FFN on expert-major buffers [E, C, D]."""
    h = jnp.einsum("ecd,edh->ech", x, w1) + b1[:, None, :]
    h = jax.nn.relu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


class MoEFFN(Module):
    """Mixture-of-experts FFN block: gate → top-k capacity routing →
    per-expert 2-layer ReLU FFN → combine.

    GSPMD integration: on a mesh with an `expert` axis the expert-major
    dispatch buffers and the stacked expert weights get
    `with_sharding_constraint(P('expert'))` hints and XLA lowers the
    expert matmuls sharded with all-to-all routing; under LayoutSharding
    the stacked tables additionally carry the `expert_table` role so the
    strategy PLACES them 1/E over the axis (parallel/layout — the way
    `embedding_row` shards LookupTable).  On a legacy or 1-wide mesh (no
    `expert` axis) the constraint degrades silently to replicated
    experts with no all-to-all — the same math, dense; single-chip and
    tier-1 runs cover that path.

    capacity_factor: C = ceil(k * T / E * capacity_factor).
    """

    PARAM_ROLES = {"gate": "kernel_in", "w1": "expert_table",
                   "w2": "expert_table", "b1": "expert_table",
                   "b2": "expert_table"}

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 k: int = 1, capacity_factor: float = 1.25,
                 expert_axis: Optional[str] = "expert"):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        self.aux_loss_weight = 0.01
        self.router_jitter = 0.01  # Switch-Transformer jitter epsilon

    def _init(self, rng):
        dt = get_policy().param_dtype
        kg, k1, k2 = jax.random.split(rng, 3)
        E, D, H = self.num_experts, self.d_model, self.d_hidden
        s1 = (2.0 / D) ** 0.5
        s2 = (2.0 / H) ** 0.5
        return {
            # near-uniform initial routing (Switch-Transformer practice):
            # a confident random router at init collapses tokens onto wrong
            # experts and training becomes strongly init-dependent
            "gate": jax.random.normal(kg, (D, E), dt) * 0.02,
            "w1": jax.random.normal(k1, (E, D, H), dt) * s1,
            "b1": jnp.zeros((E, H), dt),
            "w2": jax.random.normal(k2, (E, H, D), dt) * s2,
            "b2": jnp.zeros((E, D), dt),
        }

    def _init_state(self):
        # aux_loss rides the functional state pytree so the Optimizer can
        # add it to the criterion inside the same jit trace (see
        # Optimizer._build_step's collect_aux_losses)
        return {"aux_loss": jnp.float32(0.0)}

    def _capacity(self, T):
        import math
        return max(1, math.ceil(self.k * T / self.num_experts
                                * self.capacity_factor))

    def _constrain(self, v):
        if self.expert_axis is None:
            return v
        from .pipeline import _active_mesh
        mesh = _active_mesh()
        if mesh is not None and (
                self.expert_axis not in mesh.axis_names
                or int(mesh.shape[self.expert_axis]) <= 1):
            # legacy/1-wide mesh: the DOCUMENTED graceful degrade —
            # replicated expert tables, no all-to-all, same math.  Not a
            # warning: every single-chip and pure-DP run lands here.
            return v
        try:
            spec = P(self.expert_axis)
            return lax.with_sharding_constraint(v, spec)
        except (ValueError, RuntimeError) as e:
            # acceptable only when there is genuinely no mesh in scope
            # (single-chip/test runs); a present-but-mismatched mesh must
            # not silently degrade to replicated experts
            if not type(self)._warned_no_mesh:
                type(self)._warned_no_mesh = True
                import logging
                logging.getLogger("bigdl_tpu").warning(
                    "MoEFFN(expert_axis=%r): sharding constraint not "
                    "applied (%s); running with replicated experts — if a "
                    "mesh is active, check the axis name", self.expert_axis,
                    e)
            return v

    _warned_no_mesh = False

    def apply(self, params, state, x, *, training=False, rng=None):
        c = get_policy().compute_dtype
        shape = x.shape
        D = shape[-1]
        xt = x.reshape((-1, D)).astype(c)                       # [T, D]
        T = xt.shape[0]
        gate_in = xt.astype(jnp.float32)
        if training and rng is not None and self.router_jitter > 0:
            # Switch-style input jitter: multiplicative uniform noise on the
            # router input only — exploration + tie-breaking near the
            # uniform init, inert at eval
            e = self.router_jitter
            gate_in = gate_in * jax.random.uniform(
                rng, gate_in.shape, jnp.float32, 1.0 - e, 1.0 + e)
        logits = gate_in @ params["gate"].astype(jnp.float32)
        combine, dispatch, probs, assign = top_k_routing(
            logits, self._capacity(T), self.k)
        # expert-major buffers: sharding over the expert axis makes GSPMD
        # place each expert's tokens+weights on its own devices
        buf = jnp.einsum("tec,td->ecd", dispatch.astype(c), xt)
        buf = self._constrain(buf)
        out = _expert_ffn(buf,
                          self._constrain(params["w1"]).astype(c),
                          self._constrain(params["b1"]).astype(c),
                          self._constrain(params["w2"]).astype(c),
                          self._constrain(params["b2"]).astype(c))
        y = jnp.einsum("tec,ecd->td", combine.astype(c), out)
        aux = (self.aux_loss_weight
               * load_balancing_loss(probs, assign)) if training \
            else state["aux_loss"]
        return y.reshape(shape), {"aux_loss": aux}


def expert_parallel_ffn(mesh, params, x, *, k: int = 1,
                        capacity_factor: float = 1.25,
                        axis: str = "expert"):
    """Explicit-collective EP: tokens sharded over `axis`, experts sharded
    over `axis`; dispatch and combine cross the mesh via lax.all_to_all.

    params: MoEFFN-style dict (gate [D,E], w1 [E,D,H], b1, w2, b2).
    x: [T, D] global tokens, T divisible by the axis size.
    Returns [T, D], numerically matching the dense MoEFFN math whenever no
    token overflows capacity (the parity tests assert this).

    On a legacy/1-wide mesh (no `axis`, or |axis| == 1) this degrades
    gracefully to the dense single-shard math — replicated tables, no
    all-to-all — instead of assuming the axis exists.
    """
    import math

    if axis not in mesh.axis_names or int(mesh.shape[axis]) <= 1:
        cap = max(1, math.ceil(k * x.shape[0] / params["w1"].shape[0]
                               * capacity_factor))
        logits = x.astype(jnp.float32) @ params["gate"].astype(jnp.float32)
        combine, dispatch, _, _ = top_k_routing(logits, cap, k)
        buf = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
        out = _expert_ffn(buf, params["w1"], params["b1"], params["w2"],
                          params["b2"])
        return jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out)

    n = mesh.shape[axis]
    E = params["w1"].shape[0]
    assert E % n == 0, f"num_experts {E} not divisible by mesh axis {n}"
    T = x.shape[0]
    # LOCAL capacity per expert per source shard, so all_to_all blocks are
    # uniform; global per-expert capacity = cap * n
    cap = max(1, math.ceil(k * (T // n) / E * capacity_factor))

    def local(px, pw):  # px: [T_l, D]; pw: expert-sharded params
        gate, w1, b1, w2, b2 = pw
        from .ring_attention import _pvary
        gate = _pvary(gate, (axis,))  # replicated → device-varying
        logits = px.astype(jnp.float32) @ gate.astype(jnp.float32)
        combine, dispatch, _, _ = top_k_routing(logits, cap, k)
        buf = jnp.einsum("tec,td->ecd", dispatch.astype(px.dtype), px)
        # [E, cap, D] → exchange so each device holds its E/n experts'
        # tokens from every source shard: [E/n, n*cap, D]
        buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                             tiled=True)
        out = _expert_ffn(buf, w1, b1, w2, b2)
        out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                             tiled=True)                     # [E, cap, D]
        return jnp.einsum("tec,ecd->td", combine.astype(px.dtype), out)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), (P(), P(axis), P(axis), P(axis), P(axis))),
        out_specs=P(axis))
    pw = (params["gate"], params["w1"], params["b1"], params["w2"],
          params["b2"])
    return fn(x, pw)


# ---------------------------------------------------------------------------
# dropless gated experts with shared experts and a held share
# ---------------------------------------------------------------------------

def group_limited_top_k(scores, n_group: int, topk_group: int, k: int):
    """Group-limited greedy top-k (DeepSeek-V2 ``group_limited_greedy``).

    scores: [T, E] non-negative.  The experts are ``n_group`` runs of ``E /
    n_group``; a group's score is its largest; of the ``topk_group`` best
    groups' experts the ``k`` best are chosen.  Returns (weights [T, k],
    indices [T, k]); the weights are the chosen scores as they are.  Ties go
    to the lower index, among groups and among experts."""
    T, E = scores.shape
    if E % n_group:
        raise ValueError(f"{E} experts do not divide into {n_group} groups")
    per = E // n_group
    if k > topk_group * per:
        raise ValueError(f"k={k} > {topk_group} groups x {per} experts")
    group_best = scores.reshape(T, n_group, per).max(axis=-1)
    _, best = lax.top_k(group_best, topk_group)
    kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32), axis=1)
    allowed = jnp.repeat(kept > 0, per, axis=1)
    # a score outside the kept groups competes as -1: below every score
    return lax.top_k(jnp.where(allowed, scores, -1.0), k)


class GatedMoE(Module):
    """``y = Shared(x) + scale * sum_i s_i Expert_i(x)`` over the chosen
    experts this layer holds (module docstring).

    Every expert and the shared block are gated MLPs, ``W_down(act(W_gate
    x) * W_up x)``, or with ``gated=False`` plain ones, ``W_down act(W_up
    x)``, without biases (a plain expert's ``w_up`` is kept as rows, ``[E,
    d_expert, d_model]``, so that both its tables end in ``d_model``: an
    expert width need not fill whole lanes, as 1,856 does not, and a table
    that ends in one is copied before every product; ops/grouped.py);
    ``act`` is ``"silu"`` or ``"relu2"`` (``relu(x)^2``).
    ``s = softmax(x W_g)`` (``score="softmax"``) or ``sigmoid(x W_g)``
    (``"sigmoid"``) over all ``num_experts`` in float32; the choice is
    ``group_limited_top_k`` of ``s``, or with ``select_bias`` of ``s + b``
    for a per-expert ``b`` that chooses and does not weigh; the weights are
    the chosen ``s``, with ``renormalise`` divided by their sum + 1e-20,
    times ``scale``.  The shared block is ``d_shared`` wide (default
    ``n_shared * d_expert``); with ``shared_gate`` its output is weighed by
    a gate of its own, ``sigmoid(x w_sg)`` for one vector ``w_sg``
    (``shared_score``; the Qwen MoE families').  These are a model's
    architecture, not tuning.

    ``held = (first, count)``: the stacked tables hold experts ``first ..
    first + count - 1`` (default: all).  The router keeps every output, and
    what the absent experts would add is left out: the layer's output is this
    share's term, with the shared block's, of the whole layer's sum.

    The state carries ``expert_tokens``, int32 ``[count + 1]``: how many of
    the call's tokens each held expert took, and last how many choices went
    to experts held elsewhere.  A decoder's walk gets the same vector, of
    the call's real tokens only, from ``decode_prefill`` and
    ``decode_step``: a prompt's pads and a decode batch's idle rows go to no
    expert and count nowhere; their output is the shared experts' alone.
    """

    PARAM_ROLES = {"gate": "kernel_whole", "select_bias": "bias",
                   "w_gate": "expert_table",
                   "w_up": "expert_table", "w_down": "expert_table",
                   "shared_gate": "kernel_in", "shared_up": "kernel_in",
                   "shared_down": "kernel_in", "shared_score": "kernel_whole"}

    _ACTS = {"silu": jax.nn.silu,
             "relu2": lambda x: jnp.square(jax.nn.relu(x))}

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 k: int, n_group: int = 1, topk_group: int = 1,
                 n_shared: int = 0, scale: float = 1.0, held=None,
                 score: str = "softmax", select_bias: bool = False,
                 renormalise: bool = False, gated: bool = True,
                 act: str = "silu", d_shared: Optional[int] = None,
                 shared_gate: bool = False):
        super().__init__()
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {score!r}")
        self.d_model, self.d_expert = d_model, d_expert
        self.num_experts, self.k = num_experts, k
        self.n_group, self.topk_group = n_group, topk_group
        self.n_shared, self.scale = n_shared, float(scale)
        self.score, self.select_bias = score, select_bias
        self.renormalise, self.gated = renormalise, gated
        self.act = self._ACTS[act]
        self.d_shared = n_shared * d_expert if d_shared is None else d_shared
        self.shared_gate = bool(shared_gate and self.d_shared)
        self.first, self.count = held if held is not None \
            else (0, num_experts)
        if not (0 <= self.first and self.count >= 1
                and self.first + self.count <= num_experts):
            raise ValueError(f"held={held} outside 0..{num_experts}")

    def _init(self, rng):
        dt = get_policy().param_dtype
        ks = jax.random.split(rng, 7)
        D, H, E = self.d_model, self.d_expert, self.count
        n = lambda k, shape, fan: jax.random.normal(k, shape, dt) \
            * (1.0 / fan) ** 0.5
        p = {"gate": jax.random.normal(ks[0], (D, self.num_experts), dt)
             * 0.02,
             "w_up": n(ks[2], (E, D, H) if self.gated else (E, H, D), D),
             "w_down": n(ks[3], (E, H, D), H)}
        S = self.d_shared
        if S:
            p.update(shared_up=n(ks[5], (D, S), D),
                     shared_down=n(ks[6], (S, D), S))
        if self.gated:
            p["w_gate"] = n(ks[1], (E, D, H), D)
            if S:
                p["shared_gate"] = n(ks[4], (D, S), D)
        if self.select_bias:
            p["select_bias"] = jnp.zeros((self.num_experts,), dt)
        if self.shared_gate:
            p["shared_score"] = n(jax.random.fold_in(rng, 7), (D, 1), D)
        return p

    def _init_state(self):
        return {"expert_tokens": jnp.zeros((self.count + 1,), jnp.int32)}

    def route(self, params, xt):
        """xt [T, D] -> (weights [T, k] float32 with the scale on, expert
        indices [T, k]).  The router's product runs in float32."""
        logits = jnp.matmul(xt.astype(jnp.float32),
                            params["gate"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        s = jax.nn.softmax(logits, axis=-1) if self.score == "softmax" \
            else jax.nn.sigmoid(logits)
        pick = s + params["select_bias"].astype(jnp.float32) \
            if self.select_bias else s
        w, idx = group_limited_top_k(pick, self.n_group, self.topk_group,
                                     self.k)
        if self.select_bias:          # the bias chooses and does not weigh
            w = jnp.take_along_axis(s, idx, axis=-1)
        if self.renormalise:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * self.scale, idx

    def apply(self, params, state, x, *, training=False, rng=None):
        y, (counts, _chosen) = self._forward(params, x)
        return y, {"expert_tokens": counts}

    # incremental decoding: nothing is kept, but which tokens are real
    # matters (Module.decode_state); the report of a call is (the live
    # tokens' counts, the experts every position's router chose)

    def decode_state(self, rows: int, length: int):
        return {}

    def decode_prefill(self, params, x, cache, slot, length):
        """x [n, P, D]: a group of prompts' positions 0..P-1, of row i the
        first ``length[i]`` real (a scalar: of every row), or (P == 1) each
        row's last real position alone; a row of length 0 fills the program
        up and counts nowhere."""
        return self._forward(
            params, x, jnp.arange(x.shape[1]) < jnp.reshape(length, (-1, 1)))

    def decode_step(self, params, x, cache, pos):
        return self._forward(params, x, (pos >= 0)[:, None])

    def _forward(self, params, x, live=None):
        """x [..., D] -> (y, (counts [count + 1], chosen [..., k] int32));
        ``live`` (boolean, of ``x.shape[:-1]``) marks the real tokens, all
        of them without it.  ``chosen`` are the router's choices of all
        ``num_experts`` as made, pads and idle rows included."""
        c = get_policy().compute_dtype
        f32 = jnp.float32
        D, E, k = self.d_model, self.count, self.k
        # the router sees its input as it comes (a float32 residual stream
        # stays float32 here); the experts multiply in the compute dtype
        xr = x.reshape((-1, D))
        w, idx = self.route(params, xr)
        xt = xr.astype(c)
        T = xt.shape[0]
        local = idx - self.first
        # the sort key: a held expert's own number, E for one held
        # elsewhere, E + 1 for a token that is not live
        key = jnp.where((local >= 0) & (local < E), local, E)
        if live is not None:
            key = jnp.where(live.reshape(T, 1), key, E + 1)
        key = key.reshape(T * k)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=E + 2).astype(jnp.int32)
        held = sizes[:E]
        # rows 0..sum(held)-1 of the sorted tokens are the held experts'
        # runs, one after another; what follows belongs to no group
        xs = jnp.take(xt, order // k, axis=0)
        dot = lambda a, b, rows=False: grouped_matmul(
            a, b.astype(c), held, transposed=rows)
        h = self.act(dot(xs, params["w_gate"])) * dot(xs, params["w_up"]) \
            if self.gated else self.act(dot(xs, params["w_up"], True))
        out = dot(h.astype(c), params["w_down"])             # [T k, D] f32
        # back to token order, weighted; rows past the runs hold nothing
        # that may be read, so they are selected away, not multiplied
        inv = jnp.argsort(order)
        own = (jnp.arange(T * k) < jnp.sum(held))[inv]
        picked = jnp.where(own[:, None], jnp.take(out, inv, axis=0), 0.0)
        y = jnp.sum(picked.reshape(T, k, D) * w[:, :, None], axis=1)
        if self.d_shared:
            mm = lambda a, b: jnp.matmul(a, b.astype(c),
                                         preferred_element_type=f32)
            hs = self.act(mm(xt, params["shared_gate"])) \
                * mm(xt, params["shared_up"]) \
                if self.gated else self.act(mm(xt, params["shared_up"]))
            ys = mm(hs.astype(c), params["shared_down"])
            if self.shared_gate:
                ys = ys * jax.nn.sigmoid(mm(xt, params["shared_score"]))
            y = y + ys
        return y.astype(c).reshape(x.shape), \
            (sizes[:E + 1], idx.reshape(x.shape[:-1] + (k,)).astype(jnp.int32))
