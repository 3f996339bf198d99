"""DeepSeek-V2-shaped decoder language model (arXiv:2405.04434), built from
the library's own containers like ``TransformerLM``.

A block is ``h = h + Attn(RMSNorm(h))``; ``h = h + FFN(RMSNorm(h))``:
latent attention with rotary positions (``nn.LatentAttention``; no learned
positions), and a gated MLP.  The first ``first_k_dense`` blocks' MLP is
dense (``ConcatTable`` -> ``CMulTable`` out of the table algebra); the
others are ``parallel/expert.GatedMoE``: routed experts chosen by
group-limited top-k beside shared experts.  After the last block an
``RMSNorm``, a head without bias, and ``LogSoftMax``.

The residual stream is float32 whatever the dtype policy (``Float32``
after the embedding; a branch's bfloat16 output widens as it is added):
it is a sum of many terms, every norm and the router read it, and the
router's choice is discrete.  The matrix products run in the compute dtype.

The share arguments make the model one chip's part of a wider deployment:
``heads_held`` heads of attention (a tensor-parallel share), ``experts_held
= (first, count)`` of the routed experts (an expert-parallel share; the
router keeps every output), and ``vocab_size`` is the rows of the embedding
and the head that are held.  What every chip of a layer computes alike (the
latent projections, the norms, the router, the shared experts, the dense
MLP) is whole.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..nn import (CAddTable, CMulTable, ConcatTable, Identity,
                  LatentAttention, Linear, LogSoftMax, LookupTable, RMSNorm,
                  Sequential, SiLU)
from ..nn.module import Module

__all__ = ["DeepSeekV2LM", "DeepSeekV2Block", "GatedMLP", "Float32"]


class Float32(Module):
    """Widen to float32: what follows the embedding is the residual
    stream."""

    def _apply(self, params, x):
        return x.astype(jnp.float32)


def _residual(branch) -> Sequential:
    """y = x + branch(x), via the library's table algebra."""
    return (Sequential()
            .add(ConcatTable(branch, Identity()))
            .add(CAddTable()))


def GatedMLP(d_model: int, d_hidden: int) -> Sequential:
    """``W_down(SiLU(W_gate x) * W_up x)``, no biases."""
    return (Sequential()
            .add(ConcatTable(
                Sequential().add(Linear(d_model, d_hidden, with_bias=False))
                .add(SiLU()),
                Linear(d_model, d_hidden, with_bias=False)))
            .add(CMulTable())
            .add(Linear(d_hidden, d_model, with_bias=False)))


def DeepSeekV2Block(hidden: int, attention: dict, ffn) -> Sequential:
    """One pre-norm block; ``attention`` are ``LatentAttention``'s
    arguments and ``ffn`` the block's MLP module."""
    eps = attention.get("eps", 1e-6)
    attn = (Sequential().add(RMSNorm(hidden, eps))
            .add(LatentAttention(hidden, **attention)))
    mlp = Sequential().add(RMSNorm(hidden, eps)).add(ffn)
    return Sequential().add(_residual(attn)).add(_residual(mlp))


def DeepSeekV2LM(vocab_size: int, hidden: int, num_layers: int,
                 heads_held: int, q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, dense_width: int, expert_width: int,
                 num_experts: int, experts_per_token: int, n_group: int,
                 topk_group: int, n_shared: int,
                 routed_scaling_factor: float = 1.0,
                 first_k_dense: int = 1, experts_held=None,
                 rope_theta: float = 10000.0, rope_scaling=None,
                 eps: float = 1e-6) -> Sequential:
    """tokens [B, T] int -> log-probs [B, T, vocab_size]."""
    from ..parallel.expert import GatedMoE
    attention = dict(num_heads=heads_held, q_lora_rank=q_lora_rank,
                     kv_lora_rank=kv_lora_rank,
                     qk_nope_head_dim=qk_nope_head_dim,
                     qk_rope_head_dim=qk_rope_head_dim,
                     v_head_dim=v_head_dim, rope_theta=rope_theta,
                     rope_scaling=rope_scaling, eps=eps)
    model = Sequential().add(LookupTable(vocab_size, hidden)).add(Float32())
    for layer in range(num_layers):
        if layer < first_k_dense:
            ffn = GatedMLP(hidden, dense_width)
        else:
            ffn = GatedMoE(hidden, expert_width, num_experts,
                           experts_per_token, n_group=n_group,
                           topk_group=topk_group, n_shared=n_shared,
                           scale=routed_scaling_factor, held=experts_held)
        model.add(DeepSeekV2Block(hidden, attention, ffn))
    model.add(RMSNorm(hidden, eps))
    model.add(Linear(hidden, vocab_size, with_bias=False))
    model.add(LogSoftMax())
    return model
