"""Plain reference of GPT-2 (Radford et al. 2019; sizes from the published
``config.json``): token and learned position embeddings, pre-norm blocks of
causal softmax attention and a GELU MLP, a final LayerNorm and an output
head, in straightforward ``jax.numpy``, float32, ``precision=highest``.  No
kernel, no cache, no batching tricks, no program code.

Departures from GPT-2, because the system under test has them
(``bigdl_tpu/models/transformer_lm.py``): the output head is a separate
``Linear`` with a bias, where GPT-2 ties it to the token embedding; the four
attention projections are separate matrices applied ``x @ w``; ``Linear``
weights are ``(out, in)``.  GELU is the tanh approximation, as in GPT-2.

Parameters are a list that flattens in the program's order:
``[{weight} tokens, {weight} positions, block..., {bias, weight} final norm,
{bias, weight} head]`` with ``block = [{bias, weight} ln1, {bk bo bq bv wk wo
wq wv} attention, {bias, weight} ln2, {bias, weight} fc1, {bias, weight}
fc2]``.  ``remat`` recomputes each block in the backward pass (memory
only).  One scale a tensor for fp8 means one for each block's tensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.common import matmul

EPS = 1e-5


def init_params(cfg, key) -> list:
    """GPT-2's scheme: every matrix and embedding N(0, 0.02), the two
    projections that write into the residual stream scaled by
    1/sqrt(2 layers), biases 0, LayerNorm 1 and 0."""
    d, v, n = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    keys = iter(jax.random.split(key, 3 + 6 * n))
    normal = lambda shape, std=0.02: std * jax.random.normal(
        next(keys), shape, jnp.float32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    ln = lambda: {"bias": zeros(d), "weight": jnp.ones((d,), jnp.float32)}
    resid = 0.02 / (2.0 * n) ** 0.5
    params = [{"weight": normal((v, d))},
              {"weight": normal((cfg["n_positions"], d))}]
    for _ in range(n):
        attn = {"bk": zeros(d), "bo": zeros(d), "bq": zeros(d),
                "bv": zeros(d), "wk": normal((d, d)),
                "wo": normal((d, d), resid), "wq": normal((d, d)),
                "wv": normal((d, d))}
        params.append([ln(), attn, ln(),
                       {"bias": zeros(4 * d), "weight": normal((4 * d, d))},
                       {"bias": zeros(d),
                        "weight": normal((d, 4 * d), resid)}])
    params.append(ln())
    params.append({"bias": zeros(v), "weight": normal((v, d))})
    return params


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["weight"] + p["bias"]


def _block(x, p, heads, prec):
    ln1, at, ln2, fc1, fc2 = p
    b, t, d = x.shape
    a = _ln(x, ln1)
    split = lambda y: y.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)
    q = split(matmul(a, at["wq"], prec) + at["bq"])
    k = split(matmul(a, at["wk"], prec) + at["bk"])
    v = split(matmul(a, at["wv"], prec) + at["bv"])
    s = matmul(q, k.transpose(0, 1, 3, 2), prec) / (d // heads) ** 0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = matmul(w, v, prec).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + matmul(o, at["wo"], prec) + at["bo"]
    m = _ln(x, ln2)
    m = jax.nn.gelu(matmul(m, fc1["weight"].T, prec) + fc1["bias"],
                    approximate=True)
    return x + matmul(m, fc2["weight"].T, prec) + fc2["bias"]


def logits(cfg, params, tokens, prec: str = "f32", remat: bool = True):
    """[B, T] token ids -> [B, T, vocab] logits (before the log-softmax).
    The blocks are alike, so they are stacked and run by one ``lax.scan``:
    the same arithmetic as a Python loop, in a program 24 times smaller to
    compile and to keep in the compile cache."""
    t = tokens.shape[1]
    x = params[0]["weight"][tokens.astype(jnp.int32)] + params[1]["weight"][:t]
    block = lambda x_, p_: _block(x_, p_, cfg["n_head"], prec)
    if remat:
        block = jax.checkpoint(block)
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *params[2:-2])
    x, _ = jax.lax.scan(lambda x_, p_: (block(x_, p_), None), x, stacked)
    x = _ln(x, params[-2])
    return matmul(x, params[-1]["weight"].T, prec) + params[-1]["bias"]


def loss(cfg, params, tokens, targets, prec: str = "f32"):
    """Mean next-token negative log-likelihood over every position."""
    logp = jax.nn.log_softmax(logits(cfg, params, tokens, prec), axis=-1)
    picked = jnp.take_along_axis(
        logp, targets.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)
