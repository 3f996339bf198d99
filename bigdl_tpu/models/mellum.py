"""Mellum-2-shaped decoder language model (``model_type`` ``mellum``: window
and full attention mixed, every block with routed experts), built from the
library's own containers like ``Qwen3NextLM``.

A block is ``h = h + Attn(N(h))``; ``h = h + MoE(N(h))`` with ``N`` the
plain ``RMSNorm``.  ``layer_types[l]`` chooses block ``l``'s attention:
``"sliding_attention"`` is grouped-query attention under a window of
``sliding_window`` keys, the query's own among them, with plain rotary
positions over the whole head (``nn.WindowAttention``: a decoder keeps a ring
of ``sliding_window`` rows, of fixed size a slot); ``"full_attention"`` reads
every earlier key with the frequencies ``rope_parameters["full_attention"]``
gives (YaRN) and its ``attention_factor`` on cos and sin
(``nn.RotaryAttention``: a key and a value a position).  Every block's
experts are ``parallel/expert.GatedMoE``: softmax over all experts, the
``experts_per_token`` largest renormalised, gated SiLU experts, no shared
expert.  After the last block the norm, a head without bias, ``LogSoftMax``.

The residual stream is float32 whatever the dtype policy (``Float32`` after
the embedding), for ``DeepSeekV2LM``'s reasons.

The share arguments make the model one chip's part of a wider deployment:
``heads_held`` / ``kv_heads_held`` heads of attention, ``experts_held =
(first, count)`` of the routed experts (the router keeps every output), and
``vocab_size`` is the rows of the embedding and the head that are held.
The norms and the router are whole.
"""

from __future__ import annotations

from typing import Optional, Sequence

from ..nn import (LogSoftMax, LookupTable, Linear, RMSNorm, RotaryAttention,
                  Sequential, WindowAttention)
from .deepseek import Float32, _residual

__all__ = ["MellumLM"]


def _rope(group: dict) -> dict:
    """A published ``rope_parameters`` group as the attention layers'
    arguments: plain (``rope_type`` default) or YaRN."""
    out = {"rope_theta": group["rope_theta"]}
    if group.get("rope_type", "default") != "default":
        out["rope_scaling"] = dict(group, type=group["rope_type"])
        out["attention_factor"] = group["attention_factor"]
    return out


def MellumLM(vocab_size: int, hidden: int, layer_types: Sequence[str],
             num_heads: int, num_kv_heads: int, head_dim: int,
             expert_width: int, num_experts: int, experts_per_token: int,
             sliding_window: int, rope_parameters: dict,
             heads_held: Optional[int] = None,
             kv_heads_held: Optional[int] = None, experts_held=None,
             eps: float = 1e-6) -> Sequential:
    """tokens [B, T] int -> log-probs [B, T, vocab_size]."""
    from ..parallel.expert import GatedMoE

    def branch(layer):
        return _residual(Sequential().add(RMSNorm(hidden, eps)).add(layer))

    heads = dict(num_kv_heads=kv_heads_held or num_kv_heads,
                 head_dim=head_dim)
    model = Sequential().add(LookupTable(vocab_size, hidden)).add(Float32())
    for kind in layer_types:
        if kind == "sliding_attention":
            mixer = WindowAttention(
                hidden, heads_held or num_heads, sliding_window, **heads,
                **_rope(rope_parameters["sliding_attention"]))
        elif kind == "full_attention":
            mixer = RotaryAttention(
                hidden, heads_held or num_heads, **heads,
                **_rope(rope_parameters["full_attention"]))
        else:
            raise ValueError(f"layer type {kind!r}")
        model.add(branch(mixer))
        model.add(branch(GatedMoE(
            hidden, expert_width, num_experts, experts_per_token, n_shared=0,
            held=experts_held, score="softmax", renormalise=True)))
    model.add(RMSNorm(hidden, eps))
    model.add(Linear(hidden, vocab_size, with_bias=False))
    model.add(LogSoftMax())
    return model
