"""Plain reference of DeepSeek-V2 (DeepSeek-AI, arXiv:2405.04434; the
equations are those of the published ``modeling_deepseek.py`` beside
``config.json``), as one chip's share of a layer holds it: in straightforward
``jax.numpy``, float32, ``precision=highest``.  No kernel, no cache, no
batching, no program code.

``h`` is the residual stream, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.

* Block: ``h = h + Attn(RMSNorm(h))``; ``h = h + FFN(RMSNorm(h))``; after the
  last block ``RMSNorm`` and an untied head without bias.  No learned
  positions.
* Attention (MLA), the expanded form: ``c_Q = RMSNorm(x W_DQ)``; a head's
  ``[q_nope, q_rope] = c_Q W_UQ``; ``[c_KV, k_r] = x W_DKV``, ``c_KV =
  RMSNorm(c_KV)``; a head's ``[k_nope, v] = c_KV W_UKV``; ``k_rope =
  RoPE(k_r)`` is one vector for all heads; scores ``(q_nope . k_nope +
  RoPE(q_rope) . k_rope) * s`` with ``s = (nope + rope)^-0.5 * m^2``, ``m =
  0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; ``o =
  concat_heads(P v) W_O``.  RoPE turns the pairs ``(x[2i], x[2i+1])`` by
  ``p * f_i`` and leaves first members then second members, as the published
  code does; ``f`` is YaRN's blend of ``1 / base^(2i/d)`` and that over
  ``factor``, by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` turns over the original positions; the
  cos/sin scale ``m(mscale) / m(mscale_all_dim)`` is 1.
* FFN of the first ``first_k_dense_replace`` blocks: ``W_down(SiLU(W_gate x)
  * W_up x)``.  Of the others: ``s = softmax(x W_g)`` over all routed
  experts; a group's score is its largest ``s``; the ``topk_group`` best
  groups are kept; of their experts the ``num_experts_per_tok`` best are
  chosen (ties to the lower index); the weights are those ``s``, not
  renormalised, times ``routed_scaling_factor``; ``y = Shared(x) + sum_i w_i
  Expert_i(x)``.  Here every held expert is applied to every token and
  weighted by the routing, zero where it was not chosen.
* The share: ``held = (first, count)`` experts are held, the router keeps
  every output, and what the absent experts would add is left out;
  ``heads`` heads are held of ``W_UQ``, ``W_UKV`` and ``W_O``.  With
  ``held`` all and ``heads`` all this is the whole layer.

Parameters are a list that flattens in the program's order: ``[{weight}
embedding, block..., {weight} final norm, {weight} head (out, in)]`` with
``block = [{weight} norm, {kv_norm q_norm wdkv wdq wo wukv wuq} attention,
{weight} norm, ffn]``, ``ffn = [{weight} gate, {weight} up, {weight} down]``
(each ``(out, in)``) or ``{gate shared_down shared_gate shared_up w_down
w_gate w_up}``.  They are the published dtype's values: made from the seed
in float32 (N(0, initializer_range), norms 1) and kept in ``param_dtype``;
the reference widens each to float32 where it is used, which is exact, so
a chip that holds 9 GB of them has room left to compute.  Rows go one at a
time (``lax.map``), a long row's attention one head at a time, experts one
at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.common import matmul

F32 = jnp.float32


def sizes(cfg) -> dict:
    """The sizes as run: what the configuration's keys give, by short name."""
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], dense=cfg["intermediate_size"],
        expert=cfg["moe_intermediate_size"],
        held=(cfg["held"]["first_expert"], cfg["n_routed_experts"]),
        routed=cfg["held"]["router_outputs"], k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        shared=cfg["n_shared_experts"], scale=cfg["routed_scaling_factor"],
        first_dense=cfg["first_k_dense_replace"], eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"], scaling=cfg["rope_scaling"],
        std=cfg["initializer_range"])


def init_params(cfg, key) -> list:
    z = sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    n_moe = z["layers"] - z["first_dense"]
    keys = iter(jax.random.split(key, 2 + 8 * z["layers"] + 4 * n_moe))
    normal = lambda *shape: (z["std"] * jax.random.normal(
        next(keys), shape, F32)).astype(dt)
    ones = lambda n: {"weight": jnp.ones((n,), dt)}
    d, h, e = z["hidden"], z["heads"], z["held"][1]
    params = [{"weight": normal(z["vocab"], d)}]
    for layer in range(z["layers"]):
        attn = {"kv_norm": jnp.ones((z["kv_lora"],), dt),
                "q_norm": jnp.ones((z["q_lora"],), dt),
                "wdkv": normal(d, z["kv_lora"] + z["rope"]),
                "wdq": normal(d, z["q_lora"]),
                "wo": normal(h * z["v"], d),
                "wukv": normal(z["kv_lora"], h * (z["nope"] + z["v"])),
                "wuq": normal(z["q_lora"], h * (z["nope"] + z["rope"]))}
        if layer < z["first_dense"]:
            ffn = [{"weight": normal(z["dense"], d)},
                   {"weight": normal(z["dense"], d)},
                   {"weight": normal(d, z["dense"])}]
        else:
            s, w = z["shared"] * z["expert"], z["expert"]
            ffn = {"gate": normal(d, z["routed"]),
                   "shared_down": normal(s, d), "shared_gate": normal(d, s),
                   "shared_up": normal(d, s), "w_down": normal(e, w, d),
                   "w_gate": normal(e, d, w), "w_up": normal(e, d, w)}
        params.append([ones(d), attn, ones(d), ffn])
    params.append(ones(d))
    params.append({"weight": normal(z["vocab"], d)})
    return params


# --------------------------------------------------------------- the layers


def _wide(w):
    return w.astype(F32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _wide(g)


def yarn_inv_freq(dim: int, base: float, sc: dict) -> np.ndarray:
    """Per pair: 1/f where the ramp is 0 (fast), 1/(factor f) where it is 1
    (slow), blended between the two correction dimensions."""
    f = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (ramp / (sc["factor"] * f) + (1.0 - ramp) / f).astype(np.float32)


def score_scale(z) -> float:
    sc = z["scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 \
        if sc["factor"] > 1 else 1.0
    return (z["nope"] + z["rope"]) ** -0.5 * m * m


def rope(x, inv_freq):
    """x [T, ..., dim] at positions 0..T-1."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (-1,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(z, p, x, prec):
    """x [T, hidden] -> this share's term of the attention output."""
    t, h = x.shape[0], z["heads"]
    inv_freq = yarn_inv_freq(z["rope"], z["theta"], z["scaling"])
    cq = rms_norm(matmul(x, _wide(p["wdq"]), prec), p["q_norm"], z["eps"])
    q = matmul(cq, _wide(p["wuq"]), prec).reshape(
        t, h, z["nope"] + z["rope"])
    kv = matmul(x, _wide(p["wdkv"]), prec)
    c_kv = rms_norm(kv[:, :z["kv_lora"]], p["kv_norm"], z["eps"])
    k_rope = rope(kv[:, z["kv_lora"]:], inv_freq)              # [T, rope]
    q_rope = rope(q[..., z["nope"]:], inv_freq)                # [T, H, rope]
    up = matmul(c_kv, _wide(p["wukv"]), prec).reshape(
        t, h, z["nope"] + z["v"])
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(a):
        qn, qr, kn, v = a
        s = (matmul(qn, kn.T, prec) + matmul(qr, k_rope.T, prec)) \
            * score_scale(z)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return matmul(w, v, prec)

    o = jax.lax.map(head, tuple(
        a.transpose(1, 0, 2) for a in (q[..., :z["nope"]], q_rope,
                                       up[..., :z["nope"]],
                                       up[..., z["nope"]:])))
    o = o.transpose(1, 0, 2).reshape(t, h * z["v"])
    return matmul(o, _wide(p["wo"]), prec)


def gated(x, w_gate, w_up, w_down, prec):
    """W_down(SiLU(W_gate x) * W_up x), matrices applied ``x @ w``."""
    return matmul(jax.nn.silu(matmul(x, w_gate, prec))
                  * matmul(x, w_up, prec), w_down, prec)


def router_logits(gate, x):
    """x [T, hidden] -> x W_g over all routed experts.  Float32 at highest,
    whatever the control's precision: the published router runs so."""
    return jnp.matmul(x, _wide(gate), precision=jax.lax.Precision.HIGHEST)


def scores(z, gate, x):
    """x [T, hidden] -> softmax(x W_g) over all routed experts."""
    return jax.nn.softmax(router_logits(gate, x), axis=-1)


def _kept_groups(z, s):
    t, e = s.shape
    per = e // z["n_group"]
    group = s.reshape(t, z["n_group"], per).max(axis=-1)
    kept = jnp.zeros_like(group, bool)
    g = group
    for _ in range(z["topk_group"]):
        i = jnp.argmax(g, axis=-1)                  # the first of equals
        kept = kept.at[jnp.arange(t), i].set(True)
        g = g.at[jnp.arange(t), i].set(-1.0)
    return group, jnp.repeat(kept, per, axis=1)


def routing(z, gate, x):
    """x [T, hidden] -> [T, routed] weights: the chosen experts' softmax
    scores times the scaling factor, zero elsewhere."""
    s = scores(z, gate, x)
    t = s.shape[0]
    _group, allowed = _kept_groups(z, s)
    left = jnp.where(allowed, s, -1.0)
    chosen = jnp.zeros_like(s, bool)
    for _ in range(z["k"]):
        i = jnp.argmax(left, axis=-1)
        chosen = chosen.at[jnp.arange(t), i].set(True)
        left = left.at[jnp.arange(t), i].set(-2.0)
    return jnp.where(chosen, s, 0.0) * z["scale"]


def held_choice_decided(z, logit, width: float):
    """logit [T, routed] router logits -> [T] bool: whether the choice
    among the *held* experts is decided by ``width``, that is, whether no
    change of the logits that moves every difference of two of them by less
    than ``width`` changes which held experts are chosen.

    Routing is discrete.  Where a chosen expert leads one left out by less
    than the rounding of the activations that reach the router, another
    precision chooses the other one and neither choice is wrong; if one of
    the two is held here, the layer's output then differs by a whole
    expert's term.  (A swap of two experts held elsewhere changes nothing
    here.)  A group is surely kept if it is kept and leads the first group
    left out by ``width``; it may be kept if it is less than ``width``
    behind the last kept.  A held expert is surely chosen if its group is
    surely kept and, among the experts of all groups that may be kept (the
    strongest competition it can meet), it is one of the ``k`` best and
    leads the ``k + 1``-th by ``width``; it is surely left out if its group
    cannot be kept or the ``k``-th best expert of the surely kept groups
    alone leads it by ``width``.  Decided: every held expert is one or the
    other."""
    t, e = logit.shape
    per, k = e // z["n_group"], z["k"]
    group = logit.reshape(t, z["n_group"], per).max(axis=-1)
    if z["topk_group"] < z["n_group"]:
        best = -jnp.sort(-group, axis=-1)
        last_in = best[:, z["topk_group"] - 1, None]
        first_out = best[:, z["topk_group"], None]
        sure = group >= jnp.maximum(last_in, first_out + width)
        may = group > last_in - width
    else:
        sure = may = jnp.ones_like(group, bool)
    sure, may = jnp.repeat(sure, per, axis=1), jnp.repeat(may, per, axis=1)

    def best_of(allowed, n):
        """The n-th largest logit among the allowed experts."""
        return -jnp.sort(-jnp.where(allowed, logit, -jnp.inf),
                         axis=-1)[:, n - 1, None]

    sure_in = sure & (logit >= best_of(may, k)) \
        & (logit - best_of(may, k + 1) >= width)
    sure_out = ~may | (best_of(sure, k) - logit >= width)
    first, count = z["held"]
    return (sure_in | sure_out)[:, first:first + count].all(axis=-1)


def moe(z, p, x, prec):
    first, count = z["held"]
    w = jax.lax.dynamic_slice_in_dim(routing(z, p["gate"], x), first, count,
                                     axis=1)

    def one(a):
        wg, wu, wd, we = a
        return gated(x, _wide(wg), _wide(wu), _wide(wd), prec) * we[:, None]

    def step(acc, a):
        return acc + one(a), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], w.T))
    if z["shared"]:
        y = y + gated(x, _wide(p["shared_gate"]), _wide(p["shared_up"]),
                      _wide(p["shared_down"]), prec)
    return y


def ffn(z, p, x, prec):
    if isinstance(p, dict):
        return moe(z, p, x, prec)
    return gated(x, _wide(p[0]["weight"]).T, _wide(p[1]["weight"]).T,
                 _wide(p[2]["weight"]).T, prec)


def logits(cfg, params, tokens, prec: str = "f32", widths=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits (before the
    program's log-softmax).  With ``widths`` (a tuple of router-logit
    widths) also a dict of what every expert layer's router did:
    ``router`` its logits (float32 [B, layers, T, routed]), ``chosen`` the
    experts it chose (bool, the same shape) and ``decided`` whether the
    choice among the held experts is decided by each width
    (``held_choice_decided``, bool [B, layers, len(widths), T])."""
    z = sizes(cfg)

    def row(toks):
        x = _wide(params[0]["weight"][toks.astype(jnp.int32)])
        router, chosen, decided = [], [], []
        for n1, at, n2, f in params[1:-2]:
            x = x + attention(z, at, rms_norm(x, n1["weight"], z["eps"]),
                              prec)
            seen = rms_norm(x, n2["weight"], z["eps"])
            if widths is not None and isinstance(f, dict):
                router.append(router_logits(f["gate"], seen))
                chosen.append(routing(z, f["gate"], seen) > 0)
                decided.append(jnp.stack([
                    held_choice_decided(z, router[-1], w) for w in widths]))
            x = x + ffn(z, f, seen, prec)
        x = rms_norm(x, params[-2]["weight"], z["eps"])
        out = matmul(x, _wide(params[-1]["weight"]).T, prec)
        if widths is None:
            return out
        return out, {"router": jnp.stack(router), "chosen": jnp.stack(chosen),
                     "decided": jnp.stack(decided)}

    return jax.lax.map(row, tokens)
