"""Nemotron-H-shaped hybrid decoder language model (NVIDIA Nemotron-3-Nano,
``model_type`` ``nemotron_h``), built from the library's own containers like
``DeepSeekV2LM``.

A block is ``h = h + Mixer(RMSNorm(h))`` with one mixer, chosen by a
character of ``pattern``: ``M`` a Mamba-2 layer (``nn.Mamba2Mixer``), ``*``
grouped-query attention without any positions (``nn.MultiHeadAttention``:
the recurrent layers carry the order), ``E`` routed experts
(``parallel/expert.GatedMoE``: sigmoid scores, a selection bias, renormalised
weights, plain ``W_down relu(W_up x)^2`` experts beside one shared expert).
A block is one mixer *or* one feed-forward part, never both.  After the last
block an ``RMSNorm``, a head without bias, and ``LogSoftMax``.

The residual stream is float32 whatever the dtype policy (``Float32`` after
the embedding), for ``DeepSeekV2LM``'s reasons: every norm and the router
read it, and the router's choice is discrete.

The share arguments make the model one chip's part of a wider deployment:
``mamba_heads_held`` heads of each Mamba layer with their groups,
``heads_held`` / ``kv_heads_held`` heads of attention, ``experts_held =
(first, count)`` of the routed experts (the router keeps every output), and
``vocab_size`` is the rows of the embedding and the head that are held.
What every chip of a layer computes alike (the norms, the router, the shared
expert) is whole.
"""

from __future__ import annotations

from typing import Optional

from ..nn import (LogSoftMax, LookupTable, Linear, Mamba2Mixer,
                  MultiHeadAttention, RMSNorm, Sequential)
from .deepseek import Float32, _residual

__all__ = ["NemotronHLM"]


def NemotronHLM(vocab_size: int, hidden: int, pattern: str,
                mamba_heads: int, mamba_head_dim: int, mamba_groups: int,
                ssm_state: int, conv_kernel: int, chunk: int,
                num_heads: int, num_kv_heads: int, head_dim: int,
                expert_width: int, shared_width: int, num_experts: int,
                experts_per_token: int, n_group: int = 1,
                topk_group: int = 1, routed_scaling_factor: float = 1.0,
                mamba_heads_held: Optional[int] = None,
                heads_held: Optional[int] = None,
                kv_heads_held: Optional[int] = None, experts_held=None,
                eps: float = 1e-5, dt_range=(0.001, 0.1),
                dt_floor: float = 1e-4) -> Sequential:
    """tokens [B, T] int -> log-probs [B, T, vocab_size]."""
    from ..parallel.expert import GatedMoE

    def mixer(kind):
        if kind == "M":
            return Mamba2Mixer(hidden, mamba_heads, mamba_head_dim,
                               mamba_groups, ssm_state, conv_kernel, chunk,
                               heads_held=mamba_heads_held, eps=eps,
                               dt_range=dt_range, dt_floor=dt_floor)
        if kind == "*":
            return MultiHeadAttention(
                hidden, heads_held or num_heads, causal=True,
                with_bias=False, num_kv_heads=kv_heads_held or num_kv_heads,
                head_dim=head_dim)
        if kind == "E":
            return GatedMoE(hidden, expert_width, num_experts,
                            experts_per_token, n_group=n_group,
                            topk_group=topk_group, n_shared=1,
                            scale=routed_scaling_factor, held=experts_held,
                            score="sigmoid", select_bias=True,
                            renormalise=True, gated=False, act="relu2",
                            d_shared=shared_width)
        raise ValueError(f"layer pattern {pattern!r}: {kind!r} is none of "
                         "M, E, *")

    model = Sequential().add(LookupTable(vocab_size, hidden)).add(Float32())
    for kind in pattern:
        model.add(_residual(Sequential().add(RMSNorm(hidden, eps))
                            .add(mixer(kind))))
    model.add(RMSNorm(hidden, eps))
    model.add(Linear(hidden, vocab_size, with_bias=False))
    model.add(LogSoftMax())
    return model
