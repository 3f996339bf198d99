"""Plain reference of AI21-Jamba2-3B (``model_type`` ``jamba``; the equations
are those of the published ``config.json``'s keys, of the family's
``modeling_jamba.py`` and of Gu and Dao, Mamba, arXiv:2312.00752), the whole
model: in straightforward ``jax.numpy``, float32, ``precision=highest``.  No
kernel, no cache, no chunking, no batching, no program code: the recurrence
runs position by position, a row at a time.

``h`` is the residual stream, ``RMSNorm(x) = x rsqrt(mean(x^2) + eps) g``
with ``eps = rms_norm_eps`` everywhere.

* Block ``i``: ``h = h + Mixer_i(RMSNorm(h))``, then ``h = h + W_down(silu(
  W_gate x) * W_up x)`` with ``x = RMSNorm(h)`` (``num_experts`` 1: the plain
  gated MLP in every layer, no routing).  Layer ``i`` is attention where ``i %
  attn_layer_period == attn_layer_offset``, else Mamba.  After the last block
  ``RMSNorm``, then ``logits = x E^T`` with ``E`` the embedding's own table
  (``tie_word_embeddings``).  No positions anywhere.
* Mamba mixer (``d_inner = mamba_expand x hidden_size`` channels, state ``N
  = mamba_d_state``, rank ``R = mamba_dt_rank``, ``K = mamba_d_conv`` taps):
  ``[x, z] = u W_in``; ``x = silu(conv_K(x) + b)``, causal, a channel at a
  time; ``[dt, B, C] = x W_x`` (widths ``R``, ``N``, ``N``); ``dt =
  RMSNorm_dt(dt)``, ``B = RMSNorm_B(B)``, ``C = RMSNorm_C(C)``, each with its
  learned scale (the Jamba family's addition to Mamba); ``Delta =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t[n, d] =
  exp(Delta_t[d] A[n, d]) h_(t-1)[n, d] + Delta_t[d] B_t[n] x_t[d]``;
  ``y_t[d] = sum_n h_t[n, d] C_t[n] + D[d] x_t[d]``; ``y = y * silu(z)``;
  ``out = y W_out``.  No bias but the convolution's and ``b_dt``.
* Attention mixer: ``q``, ``k``, ``v``, ``o`` without bias; ``heads`` query
  heads of ``hidden_size / heads`` on ``kv_heads`` key-value heads (query head
  ``i`` reads key head ``i // (heads / kv_heads)``); causal softmax of ``q . k
  * head_dim^-0.5``; no rotary, no learned positions, no window.

Departures from the published code, each one of layout or of precision, none
of the mathematics: ``A_log`` is kept ``[N, d_inner]`` (published ``[d_inner,
N]``: the same numbers transposed, as the program keeps its state with the
channels last); every matrix is applied ``x @ w`` and kept ``(in, out)``
except the MLP's three and the table, kept ``(out, in)`` like the program's
``Linear`` and ``LookupTable``; the published model computes in bfloat16 with
a float32 recurrence, this reference in float32 throughout.

Parameters are a list that flattens in the program's order: ``[{weight}
table, layer..., {weight} final norm]`` with ``layer = [{weight} norm, mixer,
{weight} norm, [{weight} gate, {weight} up, {weight} down]]`` and ``mixer``
one of ``{A_log B_norm C_norm D conv_bias conv_weight dt_bias dt_norm dt_proj
in_proj out_proj x_proj}``, ``{wk wo wq wv}``.  They are the published
dtype's values: made from the seed in float32 and kept in ``param_dtype``;
the reference widens each to float32 where it is used, which is exact.  Rows
go one at a time (``lax.map``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.common import matmul

F32 = jnp.float32


def sizes(cfg) -> dict:
    """The sizes as run: what the configuration's keys give, by short name."""
    d = cfg["hidden_size"]
    return dict(
        vocab=cfg["vocab_size"], hidden=d, layers=cfg["num_hidden_layers"],
        period=cfg["attn_layer_period"], offset=cfg["attn_layer_offset"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        mlp=cfg["intermediate_size"], inner=cfg["mamba_expand"] * d,
        state=cfg["mamba_d_state"], rank=cfg["mamba_dt_rank"],
        taps=cfg["mamba_d_conv"], eps=cfg["rms_norm_eps"],
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
        std=cfg["initializer_range"])


def is_attention(z, layer: int) -> bool:
    return layer % z["period"] == z["offset"]


def init_params(cfg, key) -> list:
    """Seeded weights: every matrix and the table N(0, std); norms 1;
    ``A[n, d] = n + 1`` (``A_log`` its logarithm), ``Delta`` log-uniform in
    [dt_min, dt_max] put through the inverse softplus into ``dt_bias``, ``D``
    ones (Mamba-1's initialisation); the convolution uniform in +-K^-0.5
    (weight and bias)."""
    z = sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 1 + 10 * z["layers"]))
    normal = lambda *shape: (z["std"] * jax.random.normal(
        next(keys), shape, F32)).astype(dt)
    ones = lambda n: {"weight": jnp.ones((n,), dt)}
    d, c, n, r, taps = z["hidden"], z["inner"], z["state"], z["rank"], \
        z["taps"]
    params = [{"weight": normal(z["vocab"], d)}]
    for layer in range(z["layers"]):
        if is_attention(z, layer):
            q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
            mixer = {"wk": normal(d, kv), "wo": normal(q, d),
                     "wq": normal(d, q), "wv": normal(d, kv)}
        else:
            step = jnp.exp(jax.random.uniform(next(keys), (c,), F32)
                           * (math.log(z["dt_max"]) - math.log(z["dt_min"]))
                           + math.log(z["dt_min"]))
            bound = taps ** -0.5
            mixer = {
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=F32)[:, None], (n, c))
                    ).astype(dt),
                "B_norm": jnp.ones((n,), dt), "C_norm": jnp.ones((n,), dt),
                "D": jnp.ones((c,), dt),
                "conv_bias": jax.random.uniform(
                    next(keys), (c,), F32, -bound, bound).astype(dt),
                "conv_weight": jax.random.uniform(
                    next(keys), (taps, c), F32, -bound, bound).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "dt_norm": jnp.ones((r,), dt),
                "dt_proj": normal(r, c), "in_proj": normal(d, 2 * c),
                "out_proj": normal(c, d), "x_proj": normal(c, r + 2 * n)}
        mlp = [{"weight": normal(z["mlp"], d)},
               {"weight": normal(z["mlp"], d)},
               {"weight": normal(d, z["mlp"])}]
        params.append([ones(d), mixer, ones(d), mlp])
    params.append(ones(d))
    return params


# --------------------------------------------------------------- the layers


def _wide(w):
    return w.astype(F32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _wide(g)


def mamba(z, p, u, prec, state=None):
    """u [T, hidden] -> the mixer's output, position by position.  ``state``
    (tests): ``(h [N, d_inner], last K - 1 inputs [K - 1, d_inner])`` to
    start from; returns the output alone without it, else (output, state
    after the last position)."""
    c, n, r, taps = z["inner"], z["state"], z["rank"], z["taps"]
    proj = matmul(u, _wide(p["in_proj"]), prec)
    x_in, gate = proj[:, :c], proj[:, c:]
    a = -jnp.exp(_wide(p["A_log"]))                           # [N, d_inner]
    h0, w0 = state if state is not None else (
        jnp.zeros((n, c), F32), jnp.zeros((taps - 1, c), F32))

    def step(carry, x_t):
        h, window = carry
        window = jnp.concatenate([window, x_t[None]], axis=0)     # K inputs
        x = jax.nn.silu(jnp.sum(window * _wide(p["conv_weight"]), axis=0)
                        + _wide(p["conv_bias"]))
        dbc = matmul(x[None], _wide(p["x_proj"]), prec)[0]
        dt = rms_norm(dbc[:r], p["dt_norm"], z["eps"])
        b = rms_norm(dbc[r:r + n], p["B_norm"], z["eps"])
        cm = rms_norm(dbc[r + n:], p["C_norm"], z["eps"])
        delta = jax.nn.softplus(
            matmul(dt[None], _wide(p["dt_proj"]), prec)[0]
            + _wide(p["dt_bias"]))                                # [d_inner]
        h = jnp.exp(delta[None, :] * a) * h \
            + (delta * x)[None, :] * b[:, None]
        y = jnp.sum(h * cm[:, None], axis=0) + _wide(p["D"]) * x
        return (h, window[1:]), y

    last, y = jax.lax.scan(step, (h0, w0), x_in)
    out = matmul(y * jax.nn.silu(gate), _wide(p["out_proj"]), prec)
    return out if state is None else (out, last)


def attention(z, p, x, prec):
    """x [T, hidden] -> the attention mixer's output."""
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


def gated_mlp(p, x, prec):
    """W_down(silu(W_gate x) * W_up x); the three are kept ``(out, in)``."""
    gate, up, down = (_wide(m["weight"]).T for m in p)
    return matmul(jax.nn.silu(matmul(x, gate, prec)) * matmul(x, up, prec),
                  down, prec)


def mixer(z, p, x, prec):
    if "in_proj" in p:
        return mamba(z, p, x, prec)
    return attention(z, p, x, prec)


def logits(cfg, params, tokens, prec: str = "f32"):
    """[B, T] token ids -> [B, T, vocab] float32 logits (before the
    program's log-softmax)."""
    z = sizes(cfg)
    table = params[0]["weight"]

    def row(toks):
        x = _wide(table[toks.astype(jnp.int32)])
        for norm_a, p, norm_b, mlp in params[1:-1]:
            x = x + mixer(z, p, rms_norm(x, norm_a["weight"], z["eps"]), prec)
            x = x + gated_mlp(mlp, rms_norm(x, norm_b["weight"], z["eps"]),
                              prec)
        x = rms_norm(x, params[-1]["weight"], z["eps"])
        return matmul(x, _wide(table).T, prec)

    return jax.lax.map(row, tokens)
