"""Decoder-only transformer language model — the long-context flagship.

Net-new vs the 2017 reference (its only sequence model is the SimpleRNN
char-LM, models/rnn/SimpleRNN.scala:29-31); this is the workload that
exercises the rebuild's §7 capabilities end to end: flash attention
(ops/attention, Pallas on TPU), ring/Ulysses sequence parallelism
(parallel/ring_attention via MultiHeadAttention(seq_parallel=True)), and
the usual DP/TP mesh strategies — all under the same Optimizer facade.

Built from the library's own Torch-style containers: residual branches are
ConcatTable + CAddTable (the reference's residual idiom, e.g.
models/resnet/ResNet.scala shortcuts), so the model doubles as a stress
test of the container algebra.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import get_policy
from ..nn import (CAddTable, ConcatTable, Dropout, GELU, Identity, LayerNorm,
                  Linear, LogSoftMax, LookupTable, MultiHeadAttention,
                  Sequential)
from ..nn.module import Module

__all__ = ["TransformerLM", "TransformerBlock", "PositionalEmbedding",
           "greedy_generate", "sample_next"]

import weakref

_GENERATE_FWD_CACHE = weakref.WeakKeyDictionary()


class PositionalEmbedding(Module):
    """Learned absolute positions added to [B, T, E] token embeddings."""

    # (max_len, emb) table: position rows shard like vocab rows
    PARAM_ROLES = {"weight": "embedding_row"}

    def __init__(self, max_len: int, embed_dim: int):
        super().__init__()
        self.max_len = max_len
        self.embed_dim = embed_dim

    def _init(self, rng):
        dt = get_policy().param_dtype
        return {"weight": 0.02 * jax.random.normal(
            rng, (self.max_len, self.embed_dim), dt)}

    def _apply(self, params, x):
        t = x.shape[1]
        if t > self.max_len:
            raise ValueError(f"sequence length {t} > max_len {self.max_len}")
        return x + params["weight"][:t].astype(x.dtype)

    # incremental decoding: nothing is kept, but the position matters

    def decode_state(self, rows: int, length: int):
        return {}

    def decode_prefill(self, params, x, cache, slot, length):
        return self._apply(params, x), cache      # rows 0..P-1

    def decode_step(self, params, x, cache, pos):
        w = jnp.take(params["weight"], jnp.maximum(pos, 0), axis=0)  # [S, E]
        return x + w[:, None].astype(x.dtype), cache


def _residual(branch: Module) -> Sequential:
    """y = x + branch(x), via the library's table algebra."""
    return (Sequential()
            .add(ConcatTable(branch, Identity()))
            .add(CAddTable()))


def TransformerBlock(d_model: int, num_heads: int, mlp_ratio: int = 4,
                     dropout: float = 0.0, causal: bool = True,
                     seq_parallel: bool = False, num_experts: int = 0,
                     expert_k: int = 1, expert_axis=None) -> Sequential:
    """Pre-norm block: x + MHA(LN(x)); x + MLP(LN(x)).

    num_experts > 0 swaps the dense MLP for a capacity-routed MoE FFN
    (parallel/expert.MoEFFN, Switch-Transformer style); expert_axis names
    the mesh axis for expert parallelism under jit/GSPMD."""
    attn = (Sequential()
            .add(LayerNorm(d_model))
            .add(MultiHeadAttention(d_model, num_heads, causal=causal,
                                    seq_parallel=seq_parallel)))
    if num_experts:
        from ..parallel.expert import MoEFFN
        mlp = (Sequential()
               .add(LayerNorm(d_model))
               .add(MoEFFN(d_model, mlp_ratio * d_model, num_experts,
                           k=expert_k, expert_axis=expert_axis)))
    else:
        mlp = (Sequential()
               .add(LayerNorm(d_model))
               .add(Linear(d_model, mlp_ratio * d_model))
               .add(GELU())
               .add(Linear(mlp_ratio * d_model, d_model)))
    if dropout > 0:
        attn.add(Dropout(dropout))
        mlp.add(Dropout(dropout))
    return Sequential().add(_residual(attn)).add(_residual(mlp))


def TransformerLM(vocab_size: int, max_len: int = 1024, d_model: int = 256,
                  num_heads: int = 8, num_layers: int = 4,
                  mlp_ratio: int = 4, dropout: float = 0.0,
                  causal: bool = True,
                  seq_parallel: bool = False, num_experts: int = 0,
                  expert_k: int = 1, expert_axis=None) -> Sequential:
    """tokens [B, T] int -> log-probs [B, T, vocab]; pairs with
    TimeDistributedCriterion(ClassNLLCriterion) like the PTB LSTM.
    num_experts > 0 builds the Switch-style MoE variant (EP workload)."""
    model = (Sequential()
             .add(LookupTable(vocab_size, d_model))
             .add(PositionalEmbedding(max_len, d_model)))
    for _ in range(num_layers):
        model.add(TransformerBlock(d_model, num_heads, mlp_ratio=mlp_ratio,
                                   dropout=dropout, causal=causal,
                                   seq_parallel=seq_parallel,
                                   num_experts=num_experts,
                                   expert_k=expert_k,
                                   expert_axis=expert_axis))
    model.add(LayerNorm(d_model))
    model.add(Linear(d_model, vocab_size))  # contracts the last axis of BTE
    model.add(LogSoftMax())
    return model


def sample_next(row, temperature: float, top_k: int, rng):
    """Pick next tokens from a [B, vocab] logit row; returns (tokens, rng).

    temperature <= 0 -> argmax; else softmax(row / temperature) sampling,
    optionally truncated to EXACTLY the top_k most likely tokens
    (rank-based argpartition, O(V) — a >=threshold mask would keep every
    kth-value tie, so top_k=1 would not reduce to greedy under ties).
    Shared by greedy_generate and decode.cached_generate so the two
    decoders cannot drift."""
    import numpy as np

    if temperature <= 0:
        return np.argmax(row, axis=-1), rng
    scaled = row / temperature
    if 0 < top_k < scaled.shape[-1]:
        keep = np.argpartition(scaled, -top_k, axis=-1)[:, -top_k:]
        masked = np.full_like(scaled, -np.inf)
        np.put_along_axis(masked, keep,
                          np.take_along_axis(scaled, keep, -1), -1)
        scaled = masked
    rng, sub = jax.random.split(rng)
    return np.asarray(jax.random.categorical(
        sub, jnp.asarray(scaled), axis=-1)), rng


def greedy_generate(model, prompt, num_tokens: int, max_len: int,
                    pad_token: int = 0, temperature: float = 0.0,
                    top_k: int = 0, rng=None):
    """Decode: extend `prompt` (list/array of ints, or [B, T0] batch) by
    `num_tokens`.  temperature == 0 -> greedy argmax; temperature > 0 ->
    sample from softmax(logits / temperature), optionally truncated to the
    `top_k` most likely tokens (requires `rng`, a jax PRNG key).

    Serving-style utility (the udfpredictor analog for the LM): the jitted
    forward runs once per generated token at the STATIC [B, max_len] shape
    (right-padded), so there is exactly one compile; causal masking makes
    the padding inert for positions < current length."""
    import numpy as np

    toks = np.asarray(prompt, np.int32)
    if toks.ndim == 1:
        toks = toks[None, :]
    batch, t0 = toks.shape
    if t0 == 0:
        raise ValueError("empty prompt: need at least one token to condition"
                         " the first prediction on")
    if t0 + num_tokens > max_len:
        raise ValueError(f"prompt ({t0}) + num_tokens ({num_tokens}) "
                         f"exceeds max_len ({max_len})")
    buf = np.full((batch, max_len), pad_token, np.int32)
    buf[:, :t0] = toks

    # jit cached PER MODEL so a serving loop compiles once, not per call;
    # kept OUTSIDE the module (weak map) so Module.save stays picklable.
    # The closure holds a weakref — a strong capture would make the cached
    # value reference its own key and the WeakKeyDictionary never collect.
    fwd = _GENERATE_FWD_CACHE.get(model)
    if fwd is None:
        import weakref

        model_ref = weakref.ref(model)

        @jax.jit
        def fwd(params, state, tokens):
            out, _ = model_ref().apply(params, state, tokens,
                                       training=False, rng=None)
            return out

        _GENERATE_FWD_CACHE[model] = fwd

    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs a jax PRNG key "
                         "via rng=")

    for i in range(t0, t0 + num_tokens):
        logits = fwd(model.params, model.state, jnp.asarray(buf))
        # slice on DEVICE: only the [B, vocab] row crosses to host
        row = np.asarray(logits[:, i - 1])
        buf[:, i], rng = sample_next(row, temperature, top_k, rng)
    out = buf[:, : t0 + num_tokens]
    return out[0] if np.asarray(prompt).ndim == 1 else out
