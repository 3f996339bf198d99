"""Plain reference of Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``; the
equations are those of the published ``config.json``'s keys and of the
family's ``modeling_qwen3_next.py``), as one chip's share of a layer holds
it: in straightforward ``jax.numpy``, float32, ``precision=highest``.  No
kernel, no cache, no chunking, no batching, no program code: the linear
layers' recurrence runs position by position.

``h`` is the residual stream, ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(the zero-centred form).

* Block ``l`` (from 0): ``h = h + Mixer_l(N(h))``, ``h = h + MoE(N(h))``;
  the mixer is *full* when ``(l + 1) % full_attention_interval == 0``, else
  *linear*.  After the last block ``N`` and an untied head without bias.
* full (``H`` query heads on ``G`` key-value heads of width ``D``, no bias):
  ``[q_i | gate_i] = x W_q`` (a head's columns: ``D + D``), ``k_j = x W_k``,
  ``v_j = x W_v``; ``q_i = N_q(q_i)``, ``k_j = N_k(k_j)`` over the ``D``;
  the first ``D * partial_rotary_factor`` values of ``q_i`` and ``k_j`` are
  turned by the position (pairs ``(x[m], x[m + r / 2])``, angle ``p *
  theta^(-2 m / r)``), the rest left; ``o_i = softmax(q_i k_j^T D^-0.5,
  causal) v_j`` with ``j = i // (H / G)``; ``out = concat_i(o_i *
  sigmoid(gate_i)) W_o``.
* linear (``Hk`` key heads of ``dk``, ``Hv`` value heads of ``dv``, value
  head ``h`` reads key head ``h // (Hv / Hk)``, ``K`` taps): ``[q | k | v |
  z] = x W_qkvz``, ``[b | a] = x W_ba``; ``[q | k | v] = silu(conv_K([q | k
  | v]))``, causal, a channel at a time, no bias; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; ``q = l2norm(q) dk^-0.5``, ``k
  = l2norm(k)`` (``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``); a value head,
  from ``S = 0 [dk, dv]``: ``S' = exp(g_t) S``, ``S = S' + k_t (beta_t (v_t
  - S'^T k_t))^T``, ``o_t = S^T q_t``; ``out = concat_h(o_t / sqrt(mean
  o_t^2 + eps) * w_n * silu(z_t)) W_out`` (the norm over a head's ``dv``;
  ``w_n`` a plain weight).
* MoE: ``p = softmax(x W_g)`` over all routed experts; the ``k`` largest are
  kept (ties to the lower index) and weigh ``p / sum of the kept``; an
  expert is ``W_down (silu(W_gate x) * W_up x)``; ``y = sum_kept w_e
  Expert_e(x) + sigmoid(x w_sg) Shared(x)``, the shared expert of the same
  form.  Here every held expert is applied to every token and weighted by
  the routing, zero where it was not chosen.
* The share: the first value heads of a linear layer with their key heads
  (columns of ``W_qkvz`` and ``W_ba``, channels of the convolution, rows of
  ``W_out``), the first ``heads`` / ``kv_heads`` of attention, ``held =
  (first, count)`` experts (the router keeps every output); what the absent
  parts would add is left out.  With everything held this is the whole
  layer.  The sizes of a layer are read off its parameters' shapes, so the
  same functions compute any share (``tests/test_qwen3_next.py`` adds four
  up).

Parameters are a list that flattens in the program's order: ``[{weight}
embedding, block..., {weight} final norm, {weight} head (out, in)]`` with
``block = [{weight} norm, mixer], [{weight} norm, experts]``, ``mixer`` one
of ``{A_log conv_weight dt_bias in_ba in_qkvz norm out_proj}``, ``{k_norm
q_norm wk wo wq wv}``, and ``experts = {gate shared_down shared_gate
shared_score shared_up w_down w_gate w_up}`` (every matrix applied ``x @
w``).  They are the published dtype's values: made from the seed in float32
and kept in ``param_dtype``; the reference widens each to float32 where it
is used, which is exact.  Rows go one at a time (``lax.map``), experts one at
a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.common import HIGHEST, matmul

F32 = jnp.float32


def sizes(cfg) -> dict:
    """The sizes as run: what the configuration's keys give, by short name."""
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        interval=cfg["full_attention_interval"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        rotary=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        theta=cfg["rope_theta"],
        k_heads=cfg["linear_num_key_heads"],
        v_heads=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"], chunk=cfg["chunk_size"],
        expert=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        held=(cfg["held"]["first_expert"], cfg["num_experts"]),
        routed=cfg["held"]["router_outputs"], k=cfg["num_experts_per_tok"],
        eps=cfg["rms_norm_eps"], std=cfg["initializer_range"],
        norm_std=cfg["norm_weight_std"], a_range=cfg["gdn_a_range"],
        # the whole layer's counts, of which the held ones are this share
        whole={k: cfg["published"][k] for k in (
            "linear_num_key_heads", "linear_num_value_heads",
            "num_attention_heads", "num_key_value_heads")})


def is_full(z, layer: int) -> bool:
    return (layer + 1) % z["interval"] == 0


def conv_dim(z) -> int:
    return 2 * z["k_heads"] * z["dk"] + z["v_heads"] * z["dv"]


def init_params(cfg, key) -> list:
    """Seeded weights: every matrix and the embedding N(0, std); the
    zero-centred norms' ``w`` N(0, norm_std) (so that ``1 + w`` against
    ``w`` shows), the linear layers' gated norm ones; ``A`` log-uniform in
    ``a_range`` and ``dt_bias`` ones, so that a step's decay spans about
    0.5-0.999; the convolution uniform in +-K^-0.5."""
    z = sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 4 + 20 * z["layers"]))
    normal = lambda *shape, std=z["std"]: (std * jax.random.normal(
        next(keys), shape, F32)).astype(dt)
    norm = lambda n: {"weight": normal(n, std=z["norm_std"])}
    d = z["hidden"]
    params = [{"weight": normal(z["vocab"], d)}]
    for layer in range(z["layers"]):
        if is_full(z, layer):
            q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
            mixer = {"k_norm": normal(z["head_dim"], std=z["norm_std"]),
                     "q_norm": normal(z["head_dim"], std=z["norm_std"]),
                     "wk": normal(d, kv), "wo": normal(q, d),
                     "wq": normal(d, 2 * q), "wv": normal(d, kv)}
        else:
            hv, c, taps = z["v_heads"], conv_dim(z), z["taps"]
            lo, hi = z["a_range"]
            bound = taps ** -0.5
            mixer = {
                "A_log": (jax.random.uniform(next(keys), (hv,), F32)
                          * (math.log(hi) - math.log(lo))
                          + math.log(lo)).astype(dt),
                "conv_weight": jax.random.uniform(
                    next(keys), (taps, c), F32, -bound, bound).astype(dt),
                "dt_bias": jnp.ones((hv,), dt),
                "in_ba": normal(d, 2 * hv),
                "in_qkvz": normal(d, c + hv * z["dv"]),
                "norm": jnp.ones((z["dv"],), dt),
                "out_proj": normal(hv * z["dv"], d)}
        params.append([norm(d), mixer])
        e, w, s = z["held"][1], z["expert"], z["shared"]
        params.append([norm(d), {
            "gate": normal(d, z["routed"]),
            "shared_down": normal(s, d), "shared_gate": normal(d, s),
            "shared_score": normal(d, 1), "shared_up": normal(d, s),
            "w_down": normal(e, w, d), "w_gate": normal(e, d, w),
            "w_up": normal(e, d, w)}])
    params.append(norm(d))
    params.append({"weight": normal(z["vocab"], d)})
    return params


# --------------------------------------------------------------- the layers


def _wide(w):
    return w.astype(F32)


def rms_norm(x, w, eps):
    """The zero-centred form: ``x / sqrt(mean x^2 + eps) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + _wide(w))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def linear_attention(z, p, u, prec, state=None):
    """u [T, hidden] -> this share's term of the gated delta-rule layer's
    output, position by position.  The heads held are read off the
    parameters.  ``state`` (tests): ``(S [Hv, dk, dv], last K - 1 inputs [K
    - 1, channels])`` to start from; returns the output alone without it,
    else (output, state after the last position)."""
    dk, dv, taps = z["dk"], z["dv"], z["taps"]
    hv = p["A_log"].shape[0]
    c = p["conv_weight"].shape[1]
    hk = (c - hv * dv) // (2 * dk)
    proj = matmul(u, _wide(p["in_qkvz"]), prec)
    qkv, gate = proj[:, :c], proj[:, c:]
    ba = matmul(u, _wide(p["in_ba"]), prec)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_wide(p["A_log"])) * jax.nn.softplus(
        ba[:, hv:] + _wide(p["dt_bias"]))
    s0, w0 = state if state is not None else (
        jnp.zeros((hv, dk, dv), F32), jnp.zeros((taps - 1, c), F32))

    def step(carry, at):
        s, window = carry
        qkv_t, beta_t, g_t = at
        window = jnp.concatenate([window, qkv_t[None]], axis=0)   # K inputs
        x = jax.nn.silu(jnp.sum(window * _wide(p["conv_weight"]), axis=0))
        q = l2norm(x[:hk * dk].reshape(hk, dk)) * dk ** -0.5
        k = l2norm(x[hk * dk:2 * hk * dk].reshape(hk, dk))
        v = x[2 * hk * dk:].reshape(hv, dv)
        q, k = (jnp.repeat(a, hv // hk, axis=0) for a in (q, k))
        s = jnp.exp(g_t)[:, None, None] * s
        delta = beta_t[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k,
                                                  precision=HIGHEST))
        s = s + k[:, :, None] * delta[:, None, :]
        return (s, window[1:]), jnp.einsum("hkv,hk->hv", s, q,
                                           precision=HIGHEST)

    last, o = jax.lax.scan(step, (s0, w0), (qkv, beta, g))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + z["eps"]) * _wide(p["norm"])
    y = o.reshape(-1, hv * dv) * jax.nn.silu(gate)
    out = matmul(y, _wide(p["out_proj"]), prec)
    return out if state is None else (out, last)


def rotate(x, pos, rotary: int, theta: float):
    """x [T, heads, D] at positions pos [T]: the first ``rotary`` values of
    every head turned (pairs ``(x[m], x[m + rotary / 2])``), the rest
    left."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary)
    ang = pos.astype(F32)[:, None] * inv                     # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def attention(z, p, x, prec):
    """x [T, hidden] -> this share's term of the attention output; the
    heads held are read off the parameters."""
    t, d = x.shape[0], z["head_dim"]
    h, kv = p["wo"].shape[0] // d, p["wk"].shape[1] // d
    qg = matmul(x, _wide(p["wq"]), prec).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = matmul(x, _wide(p["wk"]), prec).reshape(t, kv, d)
    v = matmul(x, _wide(p["wv"]), prec).reshape(t, kv, d)
    pos = jnp.arange(t)
    q = rotate(rms_norm(q, p["q_norm"], z["eps"]), pos, z["rotary"],
               z["theta"])
    k = rotate(rms_norm(k, p["k_norm"], z["eps"]), pos, z["rotary"],
               z["theta"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    heads = []
    for i in range(h):
        j = i // (h // kv)
        s = matmul(q[:, i], k[:, j].T, prec) * d ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        heads.append(matmul(w, v[:, j], prec) * jax.nn.sigmoid(gate[:, i]))
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
    """x [T, hidden] -> this share's term of the expert layer's output:
    the held experts' (``held = (first, count)``, default the
    configuration's) and the shared expert's under its gate."""
    first, count = held or z["held"]
    w = jax.lax.dynamic_slice_in_dim(routing(z, p, x, forced), first, count,
                                     axis=1)

    def step(acc, a):
        wg, wu, wd, we = a
        return acc + gated_mlp(x, _wide(wg), _wide(wu), _wide(wd), prec) \
            * we[:, None], None

    y, _ = jax.lax.scan(step, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], w.T))
    shared = gated_mlp(x, _wide(p["shared_gate"]), _wide(p["shared_up"]),
                       _wide(p["shared_down"]), prec)
    return y + shared * jax.nn.sigmoid(
        matmul(x, _wide(p["shared_score"]), prec))


def mixer(z, p, x, prec):
    if "in_qkvz" in p:
        return linear_attention(z, p, x, prec)
    return attention(z, p, x, prec)


def logits(cfg, params, tokens, prec: str = "f32", routers: bool = False,
           forced=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits (before the
    program's log-softmax).  With ``routers`` also ``chosen``: the experts
    every expert layer's router chose of its own (bool [B, layers, T,
    routed]).

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
        chosen = []
        blocks = params[1:-2]
        for n in range(len(blocks) // 2):
            (norm, p), (norm_e, pe) = blocks[2 * n], blocks[2 * n + 1]
            x = x + mixer(z, p, rms_norm(x, norm["weight"], z["eps"]), prec)
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
