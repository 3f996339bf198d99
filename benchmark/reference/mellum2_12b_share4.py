"""Plain reference of Mellum2-12B-A2.5B (``model_type`` ``mellum``; the
equations are those of the published ``config.json``'s keys: window and full
attention by ``layer_types``, rotary positions by ``rope_parameters``, routed
experts in every block), as one chip's share of a layer holds it: in
straightforward ``jax.numpy``, float32, ``precision=highest``.  No kernel, no
cache, no ring, no blocks, no batching, no program code: every attention
layer is one ``[T, T]`` mask.

``h`` is the residual stream, ``N(x) = x / sqrt(mean(x^2) + eps) * w``.

* Block ``l`` (from 0): ``h = h + Attn_l(N(h))``, ``h = h + MoE(N(h))``.
  After the last block ``N`` and an untied head without bias.
* Attention (``H`` query heads on ``G`` key-value heads of width ``D``, no
  bias): ``q_i = x W_q``, ``k_j = x W_k``, ``v_j = x W_v``; every ``q_i`` and
  ``k_j`` at position ``p`` is turned whole, pairs ``(x[m], x[m + D / 2])``
  by the angle ``p f_m``, and cos and sin are multiplied by ``a``; ``o_i =
  softmax(q_i k_j^T D^-0.5, mask) v_j`` with ``j = i // (H / G)``; ``out =
  concat_i(o_i) W_o``.
  ``layer_types[l] == "sliding_attention"``: ``f_m = theta^(-2 m / D)``, ``a
  = 1``, and query ``p`` sees keys ``p - sliding_window + 1 .. p`` (the
  source's mask: ``sliding_window`` keys with the query's own).
  ``"full_attention"``: every key ``<= p``; ``f_m`` is YaRN's blend of
  ``theta^(-2 m / D)`` and that over ``factor`` (``yarn_inv_freq``: fast
  pairs keep theirs, pairs that turn less than ``beta_slow`` times over
  ``original_max_position_embeddings`` take the stretched one, a linear ramp
  between the two correction dimensions), and ``a = attention_factor``
  (given; ``0.1 ln(factor) + 1``).
* MoE: ``p = softmax(x W_g)`` over all routed experts; the ``k`` largest are
  kept (ties to the lower index) and weigh ``p / sum of the kept``; an
  expert is ``W_down (silu(W_gate x) * W_up x)``; ``y = sum_kept w_e
  Expert_e(x)``; no shared expert.  Here every held expert is applied to
  every token and weighted by the routing, zero where it was not chosen.
* The share: the first ``heads`` / ``kv_heads`` of attention, ``held =
  (first, count)`` experts (the router keeps every output); what the absent
  parts would add is left out.  With everything held this is the whole
  layer.  The heads of a layer are read off its parameters' shapes, so the
  same functions compute any share (``tests/test_mellum.py`` adds four up).

Parameters are a list that flattens in the program's order: ``[{weight}
embedding, block..., {weight} final norm, {weight} head (out, in)]`` with
``block = [{weight} norm, {wk wo wq wv}], [{weight} norm, {gate w_down
w_gate w_up}]`` (every matrix applied ``x @ w``).  They are the published
dtype's values: made from the seed in float32 and kept in ``param_dtype``;
the reference widens each to float32 where it is used, which is exact.  Rows
go one at a time (``lax.map``), experts one at a time, heads one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.common import HIGHEST, matmul

F32 = jnp.float32


def sizes(cfg) -> dict:
    """The sizes as run: what the configuration's keys give, by short name."""
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        kinds=list(cfg["layer_types"]),
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        rope=cfg["rope_parameters"], expert=cfg["moe_intermediate_size"],
        held=(cfg["held"]["first_expert"], cfg["num_experts"]),
        routed=cfg["held"]["router_outputs"], k=cfg["num_experts_per_tok"],
        eps=cfg["rms_norm_eps"], std=cfg["initializer_range"],
        norm_std=cfg["norm_weight_std"],
        # the whole layer's counts, of which the held ones are this share
        whole={k: cfg["published"][k] for k in (
            "num_attention_heads", "num_key_value_heads")})


def init_params(cfg, key) -> list:
    """Seeded weights: every matrix and the embedding N(0, std); every
    norm's weight N(1, norm_std), so that a norm left out, or one whose
    weight is, shows."""
    z = sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 4 + 10 * len(z["kinds"])))
    normal = lambda *shape, std=z["std"]: (std * jax.random.normal(
        next(keys), shape, F32)).astype(dt)
    norm = lambda n: {"weight": (1.0 + z["norm_std"] * jax.random.normal(
        next(keys), (n,), F32)).astype(dt)}
    d = z["hidden"]
    q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    e, w = z["held"][1], z["expert"]
    params = [{"weight": normal(z["vocab"], d)}]
    for _kind in z["kinds"]:
        params.append([norm(d), {"wk": normal(d, kv), "wo": normal(q, d),
                                 "wq": normal(d, q), "wv": normal(d, kv)}])
        params.append([norm(d), {"gate": normal(d, z["routed"]),
                                 "w_down": normal(e, w, d),
                                 "w_gate": normal(e, d, w),
                                 "w_up": normal(e, d, w)}])
    params.append(norm(d))
    params.append({"weight": normal(z["vocab"], d)})
    return params


# --------------------------------------------------------------- the layers


def _wide(w):
    return w.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _wide(w)


def yarn_inv_freq(dim: int, group: dict) -> np.ndarray:
    """The ``dim / 2`` angular frequencies of one ``rope_parameters`` group:
    ``theta^(-2 m / dim)`` (``rope_type`` default), or YaRN's blend: pair
    ``m`` keeps its frequency where the ramp is 0, takes it over ``factor``
    where the ramp is 1; the ramp rises linearly from the pair that makes
    ``beta_fast`` turns over the original positions (rounded down) to the one
    that makes ``beta_slow`` (rounded up)."""
    base = group["rope_theta"]
    f = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if group.get("rope_type", "default") == "default":
        return (1.0 / f).astype(np.float32)
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r}")

    def correction_dim(turns):
        return dim * math.log(group["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(group["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(group["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (ramp / (group["factor"] * f) + (1.0 - ramp) / f) \
        .astype(np.float32)


def rotate(x, pos, inv_freq, factor: float):
    """x [T, heads, D] at positions pos [T]: every head turned whole (pairs
    ``(x[m], x[m + D / 2])``), cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq)     # [T, half]
    cos = jnp.cos(ang)[:, None, :] * factor
    sin = jnp.sin(ang)[:, None, :] * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(z, p, x, prec, kind: str):
    """x [T, hidden] -> this share's term of the attention output; the
    heads held are read off the parameters."""
    t, d = x.shape[0], z["head_dim"]
    h, kv = p["wo"].shape[0] // d, p["wk"].shape[1] // d
    group = z["rope"][kind]
    inv_freq = yarn_inv_freq(d, group)
    factor = float(group.get("attention_factor", 1.0))
    pos = jnp.arange(t)
    q = rotate(matmul(x, _wide(p["wq"]), prec).reshape(t, h, d), pos,
               inv_freq, factor)
    k = rotate(matmul(x, _wide(p["wk"]), prec).reshape(t, kv, d), pos,
               inv_freq, factor)
    v = matmul(x, _wide(p["wv"]), prec).reshape(t, kv, d)
    back = pos[:, None] - pos[None, :]                 # query - key
    mask = back >= 0
    if kind == "sliding_attention":
        mask = mask & (back < z["window"])
    heads = []
    for i in range(h):
        j = i // (h // kv)
        s = matmul(q[:, i], k[:, j].T, prec) * d ** -0.5
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        heads.append(matmul(w, v[:, j], prec))
    return matmul(jnp.concatenate(heads, axis=-1), _wide(p["wo"]), prec)


def router_logits(gate, x):
    """x [T, hidden] -> x W_g over all routed experts.  Float32 at highest,
    whatever the control's precision: the published router runs so."""
    return jnp.matmul(x, _wide(gate), precision=HIGHEST)


def routing(z, p, x, forced=None):
    """x [T, hidden] -> [T, routed] weights: of the k experts with the
    largest softmax score the scores over their sum; zero elsewhere.
    ``forced`` (int32 [T, k]): where its first entry is not negative, these
    are the chosen experts instead (the choices a served run made,
    ``logits``); the scores stay this function's own."""
    s = jax.nn.softmax(router_logits(p["gate"], x), axis=-1)
    t = s.shape[0]
    left = s
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
    return w / jnp.sum(w, axis=-1, keepdims=True)


def gated_mlp(x, w_gate, w_up, w_down, prec):
    """W_down (silu(W_gate x) * W_up x), matrices applied ``x @ w``."""
    return matmul(jax.nn.silu(matmul(x, w_gate, prec))
                  * matmul(x, w_up, prec), w_down, prec)


def moe(z, p, x, prec, forced=None, held=None):
    """x [T, hidden] -> this share's term of the expert layer's output: the
    held experts' (``held = (first, count)``, default the
    configuration's)."""
    first, count = held or z["held"]
    w = jax.lax.dynamic_slice_in_dim(routing(z, p, x, forced), first, count,
                                     axis=1)

    def step(acc, a):
        wg, wu, wd, we = a
        return acc + gated_mlp(x, _wide(wg), _wide(wu), _wide(wd), prec) \
            * we[:, None], None

    y, _ = jax.lax.scan(step, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], w.T))
    return y


def logits(cfg, params, tokens, prec: str = "f32", routers: bool = False,
           forced=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits (before the
    program's log-softmax).  With ``routers`` also ``chosen``: the experts
    every expert layer's router chose of its own (bool [B, layers, T,
    routed]).

    ``forced`` (int32 [B, layers, T, k], -1 where there is none): the
    experts another computation of the model chose at each position of
    each expert layer (a served run, the control), as the siblings'
    (``qwen3_next_share4.logits``): the reference computes the model *with
    those choices* in float32, every weight its own score; ``chosen`` is
    then what the reference would itself have chosen at each position,
    given the forced choices everywhere before it."""
    z = sizes(cfg)

    def row(a):
        toks, given = a
        x = _wide(params[0]["weight"][toks.astype(jnp.int32)])
        chosen = []
        blocks = params[1:-2]
        for n, kind in enumerate(z["kinds"]):
            (norm, p), (norm_e, pe) = blocks[2 * n], blocks[2 * n + 1]
            x = x + attention(z, p, rms_norm(x, norm["weight"], z["eps"]),
                              prec, kind)
            seen = rms_norm(x, norm_e["weight"], z["eps"])
            if routers:
                chosen.append(routing(z, pe, seen) > 0)
            x = x + moe(z, pe, seen, prec,
                        None if given is None else given[n])
        x = rms_norm(x, params[-2]["weight"], z["eps"])
        out = matmul(x, _wide(params[-1]["weight"]).T, prec)
        return (out, jnp.stack(chosen)) if routers else out

    if forced is None:
        return jax.lax.map(lambda toks: row((toks, None)), tokens)
    return jax.lax.map(row, (tokens, forced))
