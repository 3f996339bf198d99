"""Plain reference of NVIDIA Nemotron-3-Nano-30B-A3B (``model_type``
``nemotron_h``; the equations are those of the published ``config.json``'s
keys and of the family's ``modeling_nemotron_h.py``), as one chip's share of a
layer holds it: in straightforward ``jax.numpy``, float32,
``precision=highest``.  No kernel, no cache, no chunking, no batching, no
program code: the recurrence runs position by position.

``h`` is the residual stream, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.

* Block: ``h = h + Mixer(RMSNorm(h))``, one mixer a block, chosen by a
  character of ``hybrid_override_pattern``: ``M``, ``E`` or ``*``.  After the
  last block ``RMSNorm`` and an untied head without bias.  No positions
  anywhere.
* ``M``, Mamba-2 (``H`` heads of width ``P``, ``G`` groups of state ``N``,
  head ``h`` reads group ``h // (H / G)``, ``K`` taps): ``[z, xBC, dt] = u
  W_in`` (widths ``H P``, ``H P + 2 G N``, ``H``); ``xBC = silu(conv_K(xBC)
  + b)``, causal, a channel at a time; ``[x, B, C] = split(xBC)``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t[h] = exp(dt_t[h]
  A[h]) S_(t-1)[h] + dt_t[h] x_t[h] (outer) B_t[g]``; ``y_t[h] = S_t[h]
  C_t[g] + D[h] x_t[h]``; ``y = y * silu(z)``; ``y = y * rsqrt(mean over
  each group's H P / G channels of y^2 + eps) * w``; ``out = y W_out``.
* ``*``, attention: ``q``, ``k``, ``v``, ``o`` without bias; query head ``i``
  reads key head ``i // (heads / kv_heads)``; causal softmax of ``q . k *
  head_dim^-0.5``; no rotary and no learned positions.
* ``E``, experts: ``s = sigmoid(x W_r)`` over all routed experts; the choice
  is the ``k`` largest of ``s + b`` (ties to the lower index; one group, so
  no group limit); the weights are the chosen ``s`` (without ``b``) over
  their sum + 1e-20, times ``routed_scaling_factor``; an expert is ``W_down
  relu(W_up x)^2``; ``y = Shared(x) + sum_i w_i Expert_i(x)``, the shared
  expert of the same form.  Here every held expert is applied to every token
  and weighted by the routing, zero where it was not chosen.
* The share: the first ``mamba heads`` heads with their groups (columns of
  ``W_in``, channels of the convolution, rows of ``W_out``), the first
  ``heads`` / ``kv_heads`` of attention, ``held = (first, count)`` experts
  (the router keeps every output); what the absent parts would add is left
  out.  With everything held this is the whole layer.

Parameters are a list that flattens in the program's order: ``[{weight}
embedding, block..., {weight} final norm, {weight} head (out, in)]`` with
``block = [{weight} norm, mixer]``, ``mixer`` one of ``{A_log D conv_bias
conv_weight dt_bias in_proj norm out_proj}``, ``{wk wo wq wv}``, ``{gate
select_bias shared_down shared_up w_down w_up}`` (an expert's ``w_up`` is
kept as rows, ``(out, in)``, like the head's; its ``w_down`` and the shared
expert's two are applied ``x @ w``).  They are the published
dtype's values: made from the seed in float32 and kept in ``param_dtype``;
the reference widens each to float32 where it is used, which is exact.  Rows
go one at a time (``lax.map``), experts one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.common import matmul

F32 = jnp.float32


def sizes(cfg) -> dict:
    """The sizes as run: what the configuration's keys give, by short name."""
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        m_heads=cfg["mamba_num_heads"], m_dim=cfg["mamba_head_dim"],
        m_groups=cfg["n_groups"], state=cfg["ssm_state_size"],
        taps=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_shared_expert_intermediate_size"],
        held=(cfg["held"]["first_expert"], cfg["n_routed_experts"]),
        routed=cfg["held"]["router_outputs"], k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        scale=cfg["routed_scaling_factor"], eps=cfg["norm_eps"],
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
        dt_floor=cfg["time_step_floor"], std=cfg["initializer_range"],
        bias_std=cfg["select_bias_std"],
        # the whole layer's counts, of which the held ones are this share
        whole={k: cfg["published"][k] for k in (
            "mamba_num_heads", "n_groups", "num_attention_heads",
            "num_key_value_heads")})


def conv_dim(z) -> int:
    return z["m_heads"] * z["m_dim"] + 2 * z["m_groups"] * z["state"]


def init_params(cfg, key) -> list:
    """Seeded weights: every matrix and the embedding N(0, std); norms 1;
    ``A`` uniform in [1, 16], ``dt`` log-uniform in [dt_min, dt_max]
    floored at dt_floor and put through the inverse softplus, ``D`` ones
    (the family's initialisation); the convolution uniform in +-K^-0.5
    (weight and bias); the selection bias N(0, bias_std)."""
    z = sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 2 + 6 * len(z["pattern"])))
    normal = lambda *shape: (z["std"] * jax.random.normal(
        next(keys), shape, F32)).astype(dt)
    ones = lambda n: {"weight": jnp.ones((n,), dt)}
    d = z["hidden"]
    params = [{"weight": normal(z["vocab"], d)}]
    for kind in z["pattern"]:
        if kind == "M":
            h, inner, c, taps = (z["m_heads"], z["m_heads"] * z["m_dim"],
                                 conv_dim(z), z["taps"])
            step = jnp.exp(jax.random.uniform(next(keys), (h,), F32)
                           * (math.log(z["dt_max"]) - math.log(z["dt_min"]))
                           + math.log(z["dt_min"]))
            step = jnp.maximum(step, z["dt_floor"])
            bound = taps ** -0.5
            mixer = {
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), F32, 1.0, 16.0)).astype(dt),
                "D": jnp.ones((h,), dt),
                "conv_bias": jax.random.uniform(
                    next(keys), (c,), F32, -bound, bound).astype(dt),
                "conv_weight": jax.random.uniform(
                    next(keys), (taps, c), F32, -bound, bound).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "in_proj": normal(d, inner + c + h),
                "norm": jnp.ones((inner,), dt),
                "out_proj": normal(inner, d)}
        elif kind == "*":
            q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
            mixer = {"wk": normal(d, kv), "wo": normal(q, d),
                     "wq": normal(d, q), "wv": normal(d, kv)}
        elif kind == "E":
            e, w, s = z["held"][1], z["expert"], z["shared"]
            mixer = {"gate": normal(d, z["routed"]),
                     "select_bias": (z["bias_std"] * jax.random.normal(
                         next(keys), (z["routed"],), F32)).astype(dt),
                     "shared_down": normal(s, d), "shared_up": normal(d, s),
                     "w_down": normal(e, w, d), "w_up": normal(e, w, d)}
        else:
            raise ValueError(f"layer pattern: {kind!r}")
        params.append([ones(d), mixer])
    params.append(ones(d))
    params.append({"weight": normal(z["vocab"], d)})
    return params


# --------------------------------------------------------------- the layers


def _wide(w):
    return w.astype(F32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _wide(g)


def mamba(z, p, u, prec, state=None):
    """u [T, hidden] -> this share's term of the mixer's output, position by
    position.  ``state`` (tests): ``(S [H, P, N], last K - 1 inputs [K - 1,
    channels])`` to start from; returns the output alone without it, else
    (output, state after the last position)."""
    h, pd, g, n, taps = (z["m_heads"], z["m_dim"], z["m_groups"], z["state"],
                         z["taps"])
    inner, c = h * pd, conv_dim(z)
    proj = matmul(u, _wide(p["in_proj"]), prec)
    gate, xbc, dt = proj[:, :inner], proj[:, inner:inner + c], \
        proj[:, inner + c:]
    a = -jnp.exp(_wide(p["A_log"]))
    s0, w0 = state if state is not None else (
        jnp.zeros((h, pd, n), F32), jnp.zeros((taps - 1, c), F32))

    def step(carry, at):
        s, window = carry
        xbc_t, dt_t = at
        window = jnp.concatenate([window, xbc_t[None]], axis=0)   # K inputs
        v = jax.nn.silu(jnp.sum(window * _wide(p["conv_weight"]), axis=0)
                        + _wide(p["conv_bias"]))
        x = v[:inner].reshape(h, pd)
        b = jnp.repeat(v[inner:inner + g * n].reshape(g, n), h // g, axis=0)
        cm = jnp.repeat(v[inner + g * n:].reshape(g, n), h // g, axis=0)
        step_ = jax.nn.softplus(dt_t + _wide(p["dt_bias"]))          # [H]
        s = jnp.exp(step_ * a)[:, None, None] * s \
            + (step_[:, None] * x)[:, :, None] * b[:, None, :]
        y = jnp.sum(s * cm[:, None, :], axis=-1) \
            + _wide(p["D"])[:, None] * x
        return (s, window[1:]), y.reshape(inner)

    last, y = jax.lax.scan(step, (s0, w0), (xbc, dt))
    y = y * jax.nn.silu(gate)
    y = rms_norm(y.reshape(-1, g, inner // g), jnp.ones((), F32), z["eps"]) \
        .reshape(-1, inner) * _wide(p["norm"])
    out = matmul(y, _wide(p["out_proj"]), prec)
    return out if state is None else (out, last)


def attention(z, p, x, prec):
    """x [T, hidden] -> this share's term of the attention output."""
    t, h, kv, d = x.shape[0], z["heads"], z["kv_heads"], z["head_dim"]
    q = matmul(x, _wide(p["wq"]), prec).reshape(t, h, d)
    k = matmul(x, _wide(p["wk"]), prec).reshape(t, kv, d)
    v = matmul(x, _wide(p["wv"]), prec).reshape(t, kv, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    heads = []
    for i in range(h):
        j = i // (h // kv)
        s = matmul(q[:, i], k[:, j].T, prec) * d ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        heads.append(matmul(w, v[:, j], prec))
    return matmul(jnp.concatenate(heads, axis=-1), _wide(p["wo"]), prec)


def router_logits(gate, x):
    """x [T, hidden] -> x W_r over all routed experts.  Float32 at highest,
    whatever the control's precision: the published router runs so."""
    return jnp.matmul(x, _wide(gate), precision=jax.lax.Precision.HIGHEST)


def routing(z, p, x, forced=None):
    """x [T, hidden] -> [T, routed] weights: of the k experts with the
    largest ``s + b`` the scores ``s`` over their sum + 1e-20, times the
    scaling factor; zero elsewhere.  ``forced`` (int32 [T, k]): where its
    first entry is not negative, these are the chosen experts instead (the
    choices a served run made, ``logits``); the scores stay this
    function's own."""
    s = jax.nn.sigmoid(router_logits(p["gate"], x))
    t = s.shape[0]
    left = s + _wide(p["select_bias"])
    chosen = jnp.zeros_like(s, bool)
    for _ in range(z["k"]):
        i = jnp.argmax(left, axis=-1)                 # the first of equals
        chosen = chosen.at[jnp.arange(t), i].set(True)
        left = left.at[jnp.arange(t), i].set(-jnp.inf)
    if forced is not None:
        given = jnp.zeros_like(s, bool).at[
            jnp.arange(t)[:, None], jnp.maximum(forced, 0)].set(True)
        chosen = jnp.where(forced[:, :1] >= 0, given, chosen)
    w = jnp.where(chosen, s, 0.0)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * z["scale"]


def held_choice_decided(z, p, logit, width: float):
    """logit [T, routed] router logits -> [T] bool: whether the choice among
    the *held* experts is decided by ``width``, that is, whether no change
    of each logit by less than ``width / 2`` (so of every difference of two
    by less than ``width``) changes which held experts are chosen.

    Routing is discrete.  Where a chosen expert leads one left out by less
    than the rounding of the activations that reach the router, another
    precision chooses the other one and neither choice is wrong; if one of
    the two is held here, the layer's output then differs by a whole
    expert's term.  The key of the choice is ``sigmoid(logit) + b``.  An
    expert is surely chosen if fewer than ``k`` others' highest possible
    keys pass its lowest, surely left out if at least ``k`` others' lowest
    pass its highest.  Decided: every held expert is one or the other."""
    b = _wide(p["select_bias"])
    hi = jax.nn.sigmoid(logit + width / 2) + b
    lo = jax.nn.sigmoid(logit - width / 2) + b
    # the others that may beat e, and those that surely do
    other = ~jnp.eye(logit.shape[-1], dtype=bool)
    may = jnp.sum((hi[:, None, :] > lo[:, :, None]) & other, axis=-1)
    surely = jnp.sum((lo[:, None, :] > hi[:, :, None]) & other, axis=-1)
    first, count = z["held"]
    return ((may < z["k"]) | (surely >= z["k"]))[:, first:first + count] \
        .all(axis=-1)


def plain_mlp(x, w_up, w_down, prec):
    """W_down relu(W_up x)^2, matrices applied ``x @ w``."""
    return matmul(jnp.square(jax.nn.relu(matmul(x, w_up, prec))), w_down,
                  prec)


def moe(z, p, x, prec, forced=None):
    first, count = z["held"]
    w = jax.lax.dynamic_slice_in_dim(routing(z, p, x, forced), first, count,
                                     axis=1)

    def step(acc, a):
        wu, wd, we = a
        return acc + plain_mlp(x, _wide(wu).T, _wide(wd), prec) \
            * we[:, None], None

    y, _ = jax.lax.scan(step, jnp.zeros_like(x),
                        (p["w_up"], p["w_down"], w.T))
    return y + plain_mlp(x, _wide(p["shared_up"]), _wide(p["shared_down"]),
                         prec)


def mixer(z, p, x, prec, forced=None):
    if "in_proj" in p:
        return mamba(z, p, x, prec)
    if "wq" in p:
        return attention(z, p, x, prec)
    return moe(z, p, x, prec, forced)


def logits(cfg, params, tokens, prec: str = "f32", widths=None, forced=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits (before the
    program's log-softmax).  With ``widths`` (a tuple of router-logit
    widths) also a dict of what every expert layer's router did:
    ``router`` its logits (float32 [B, layers, T, routed]), ``chosen`` the
    experts it chose of its own (bool, the same shape) and ``decided``
    whether the choice among the held experts is decided by each width
    (``held_choice_decided``, bool [B, layers, len(widths), T]).

    ``forced`` (int32 [B, layers, T, k], -1 where there is none): the
    experts another computation of the model chose at each position of
    each expert layer (a served run, the control).  Routing is discrete:
    where two experts score nearly alike a bfloat16 program and this
    float32 reference choose differently, neither is wrong, and in a model
    whose layers mix positions one such difference moves every later
    position's router (PERF.md, PR 32).  Given the choices that were made,
    the reference computes the model *with those choices* in float32:
    every weight is the reference's own score; ``chosen`` is then what the
    reference would itself have chosen at each position, given the forced
    choices everywhere before it."""
    z = sizes(cfg)

    def row(a):
        toks, given = a
        x = _wide(params[0]["weight"][toks.astype(jnp.int32)])
        router, chosen, decided = [], [], []
        n = 0
        for norm, p in params[1:-2]:
            seen = rms_norm(x, norm["weight"], z["eps"])
            if widths is not None and "gate" in p:
                router.append(router_logits(p["gate"], seen))
                chosen.append(routing(z, p, seen) > 0)
                decided.append(jnp.stack([
                    held_choice_decided(z, p, router[-1], w)
                    for w in widths]) if widths
                    else jnp.zeros((0,) + toks.shape, bool))
            here = None
            if "gate" in p:
                here, n = (None if given is None else given[n]), n + 1
            x = x + mixer(z, p, seen, prec, here)
        x = rms_norm(x, params[-2]["weight"], z["eps"])
        out = matmul(x, _wide(params[-1]["weight"]).T, prec)
        if widths is None:
            return out
        return out, {"router": jnp.stack(router), "chosen": jnp.stack(chosen),
                     "decided": jnp.stack(decided)}

    if forced is None:
        return jax.lax.map(lambda toks: row((toks, None)), tokens)
    return jax.lax.map(row, (tokens, forced))
