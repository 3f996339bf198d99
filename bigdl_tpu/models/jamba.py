"""Jamba-shaped hybrid decoder language model (AI21 Jamba, ``model_type``
``jamba``; the equations are those of the family's ``modeling_jamba.py`` and
of Gu and Dao, Mamba, arXiv:2312.00752), built from the library's own
containers like ``NemotronHLM``.

Block ``i`` is ``h = h + Mixer_i(RMSNorm(h))``; ``h = h + MLP(RMSNorm(h))``.
The mixer is attention where ``i % attn_layer_period == attn_layer_offset``
(the published config gives the order of the layers through those two keys):
grouped-query attention without any positions, bias or window
(``nn.MultiHeadAttention``: the recurrent layers carry the order); everywhere
else Mamba-1's selective state-space layer with the family's norms on ``dt``,
``B`` and ``C`` (``nn.MambaMixer``).  The MLP is the gated one,
``W_down(silu(W_gate x) * W_up x)`` (``num_experts`` 1: no routing).  After the
last block an ``RMSNorm``, then the head, which reads the embedding's own
table (``tie_word_embeddings``: ``nn.TiedSequential``, one leaf), and
``LogSoftMax``.

The residual stream is float32 whatever the dtype policy (``Float32`` after
the embedding), as ``DeepSeekV2LM`` keeps it: it is a sum of many terms and
every norm reads it.
"""

from __future__ import annotations

from ..nn import (LogSoftMax, LookupTable, Linear, MambaMixer,
                  MultiHeadAttention, RMSNorm, Sequential, TiedSequential)
from .deepseek import Float32, GatedMLP, _residual

__all__ = ["JambaLM", "jamba_layer_kinds"]


def jamba_layer_kinds(num_layers: int, attn_layer_period: int,
                      attn_layer_offset: int) -> str:
    """The order of the layers, a character each: ``*`` attention, ``M``
    Mamba."""
    return "".join("*" if i % attn_layer_period == attn_layer_offset else "M"
                   for i in range(num_layers))


def JambaLM(vocab_size: int, hidden: int, num_layers: int,
            attn_layer_period: int, attn_layer_offset: int,
            num_heads: int, num_kv_heads: int, mlp_width: int,
            mamba_expand: int = 2, mamba_state: int = 16,
            mamba_dt_rank: int = 256, mamba_conv: int = 4,
            tie_embeddings: bool = True,
            eps: float = 1e-6) -> TiedSequential:
    """tokens [B, T] int -> log-probs [B, T, vocab_size]."""

    def branch(layer):
        return _residual(Sequential().add(RMSNorm(hidden, eps)).add(layer))

    table = LookupTable(vocab_size, hidden)
    model = TiedSequential().add(table).add(Float32())
    for kind in jamba_layer_kinds(num_layers, attn_layer_period,
                                  attn_layer_offset):
        if kind == "*":
            mixer = MultiHeadAttention(
                hidden, num_heads, causal=True, with_bias=False,
                num_kv_heads=num_kv_heads, head_dim=hidden // num_heads)
        else:
            mixer = MambaMixer(hidden, mamba_expand * hidden, mamba_state,
                               mamba_dt_rank, mamba_conv, eps=eps)
        model.add(branch(mixer))
        model.add(branch(GatedMLP(hidden, mlp_width)))
    model.add(RMSNorm(hidden, eps))
    head = Linear(hidden, vocab_size, with_bias=False)
    model.add(head)
    if tie_embeddings:
        model.tie(head, table)
    model.add(LogSoftMax())
    return model
