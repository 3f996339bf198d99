"""Qwen3-Next-shaped hybrid decoder language model (``model_type``
``qwen3_next``; the equations are ``modeling_qwen3_next.py``'s), built from
the library's own containers like ``NemotronHLM``.

A block is ``h = h + Mixer(N(h))``; ``h = h + MoE(N(h))`` with ``N`` the
zero-centred ``RMSNorm`` (``x / sqrt(mean x^2 + eps) * (1 + w)``).  Block
``l`` (from 0) is *full* when ``(l + 1) % full_attention_interval == 0``:
grouped-query attention whose query and key heads are normed, whose first
``partial_rotary_factor`` of each head is rotated by the position, and whose
output is gated (``nn.MultiHeadAttention(qk_norm=, gated=, rope=)``).  Every
other block is *linear*: a gated delta-rule layer with a matrix state a head
(``nn.GatedDeltaNet``).  Every block, unlike Nemotron's, has a mixer *and*
routed experts (``parallel/expert.GatedMoE``: softmax over all experts, the
``experts_per_token`` largest renormalised, gated SiLU experts beside one
shared expert with a sigmoid gate of its own).  After the last block the
norm, a head without bias, and ``LogSoftMax``.

The residual stream is float32 whatever the dtype policy (``Float32`` after
the embedding), for ``DeepSeekV2LM``'s reasons: every norm and the router
read it, and the router's choice is discrete.

The share arguments make the model one chip's part of a wider deployment:
``v_heads_held`` / ``k_heads_held`` value and key heads of each linear layer,
``heads_held`` / ``kv_heads_held`` heads of attention, ``experts_held =
(first, count)`` of the routed experts (the router keeps every output), and
``vocab_size`` is the rows of the embedding and the head that are held.
What every chip of a layer computes alike (the norms, the router, the shared
expert and its gate) is whole.
"""

from __future__ import annotations

from typing import Optional

from ..nn import (GatedDeltaNet, LogSoftMax, LookupTable, Linear,
                  MultiHeadAttention, RMSNorm, Sequential)
from .deepseek import Float32, _residual

__all__ = ["Qwen3NextLM"]


def Qwen3NextLM(vocab_size: int, hidden: int, num_layers: int,
                num_heads: int, num_kv_heads: int, head_dim: int,
                linear_k_heads: int, linear_v_heads: int,
                linear_k_head_dim: int, linear_v_head_dim: int,
                expert_width: int, shared_width: int, num_experts: int,
                experts_per_token: int, full_attention_interval: int = 4,
                partial_rotary_factor: float = 0.25,
                rope_theta: float = 1e7, conv_kernel: int = 4,
                chunk: int = 64, v_heads_held: Optional[int] = None,
                k_heads_held: Optional[int] = None,
                heads_held: Optional[int] = None,
                kv_heads_held: Optional[int] = None, experts_held=None,
                eps: float = 1e-6) -> Sequential:
    """tokens [B, T] int -> log-probs [B, T, vocab_size]."""
    from ..parallel.expert import GatedMoE

    def branch(layer):
        return _residual(Sequential().add(RMSNorm(hidden, eps, plus_one=True))
                         .add(layer))

    model = Sequential().add(LookupTable(vocab_size, hidden)).add(Float32())
    for l in range(num_layers):
        if (l + 1) % full_attention_interval == 0:
            mixer = MultiHeadAttention(
                hidden, heads_held or num_heads, causal=True,
                with_bias=False, num_kv_heads=kv_heads_held or num_kv_heads,
                head_dim=head_dim, qk_norm=True, gated=True,
                rope=(rope_theta, int(head_dim * partial_rotary_factor)),
                eps=eps)
        else:
            mixer = GatedDeltaNet(
                hidden, linear_k_heads, linear_v_heads, linear_k_head_dim,
                linear_v_head_dim, conv_kernel, chunk,
                v_heads_held=v_heads_held, k_heads_held=k_heads_held, eps=eps)
        model.add(branch(mixer))
        model.add(branch(GatedMoE(
            hidden, expert_width, num_experts, experts_per_token, n_shared=1,
            held=experts_held, score="softmax", renormalise=True,
            d_shared=shared_width, shared_gate=True)))
    model.add(RMSNorm(hidden, eps, plus_one=True))
    model.add(Linear(hidden, vocab_size, with_bias=False))
    model.add(LogSoftMax())
    return model
