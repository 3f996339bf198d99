"""Attention layers (net-new vs the 2017 reference; required for the rebuild's
long-context capability, SURVEY.md §5.7/§7).

MultiHeadAttention: q, k, v projections -> flash attention (Pallas kernel on
TPU, ops/attention.py) -> output projection.  ``num_kv_heads`` fewer key and
value heads than query heads (grouped-query attention: query head ``i``
reads key head ``i // (num_heads / num_kv_heads)``) and a ``head_dim`` that
is not ``embed_dim / num_heads`` are arguments; the defaults are the plain
form.  With `seq_parallel=True` the
attention core runs as a ring over the mesh 'seq' axis (parallel/ring_attention)
so sequences sharded across devices never gather.  `BIGDL_TPU_RING_ATTN=1`
instead reuses a MeshLayout's 'tp' axis as the sequence axis: on a tp>1
mesh whose sequence length divides |tp|, the attention core rings over
'tp' — long contexts shard across the tensor-parallel group with no extra
mesh axis (parity-pinned on the CPU mesh, tests/test_pipeline_expert.py).

LatentAttention (MLA, DeepSeek-V2): queries and keys/values go through
low-rank projections, and what a decoder keeps of a position is the shared
latent ``c_kv`` and one rotary key, not a key and a value for every head.

Incremental decoding: each layer declares what it keeps (``decode_state``),
how a prompt fills a row (``decode_prefill``) and how one position a row
advances it (``decode_step``); models/decode.py and serve/decode.py walk any
model through that interface.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..common import get_policy
from .initialization import compute_fans, default_weight_init
from .module import Module, StateLeaf, prefill_rows, write_prompt_rows
from .normalization import rms_norm
from .rotary import (apply_rope, apply_rope_half, rope_angles, rope_inv_freq,
                     yarn_mscale)

__all__ = ["MultiHeadAttention", "LatentAttention"]


def _write_rows(cache, pos, new):
    """How a decode step puts what it has computed into its donated state:
    ``cache [S, L, width]`` with ``new [S, width]`` at position ``pos[s]``
    of row ``s``, one scatter of S whole minor rows (XLA writes them in
    place; a window with an axis *before* the position, as ``[H, 1, D]``
    into ``[S, H, L, D]``, it expands into a loop of S passes instead)."""
    return cache.at[jnp.arange(cache.shape[0]), pos].set(
        new.astype(cache.dtype))


class MultiHeadAttention(Module):
    """Self-attention over [B, T, E] inputs: ``num_heads`` query heads of
    width ``head_dim`` (default ``embed_dim / num_heads``) over
    ``num_kv_heads`` key and value heads (default: as many), each shared
    by a run of ``num_heads / num_kv_heads`` consecutive query heads; scores
    are scaled by ``head_dim^-0.5``; no positions are applied here by
    default.

    Three options, each off by default (the default's parameters and
    program are then what they were), as the Qwen3-Next family's full
    attention has them: ``qk_norm`` norms every query and key head over its
    ``head_dim`` (``x / sqrt(mean x^2 + eps) * (1 + w)``, one ``w`` for the
    queries' heads and one for the keys', zero at the start); ``gated``
    makes ``W_q`` twice as wide, a head's columns ``[q | gate]``, and the
    head's output is multiplied by ``sigmoid(gate)`` before ``W_o``;
    ``rope = (theta, rotary_dim)`` turns the first ``rotary_dim`` values of
    every query and key head by its position (half-split pairs,
    ``nn/rotary.apply_rope_half``) and leaves the rest.  The order is the
    source's: norm, then rotate; the cache keeps keys normed and
    rotated."""

    #: projections are applied x @ w (in-major): kernel_in
    PARAM_ROLES = {"wq": "kernel_in", "wk": "kernel_in", "wv": "kernel_in",
                   "wo": "kernel_in", "q_norm": "norm_scale",
                   "k_norm": "norm_scale", "*": "bias"}

    def __init__(self, embed_dim: int, num_heads: int, causal: bool = False,
                 seq_parallel: bool = False, seq_axis: str = "seq",
                 with_bias: bool = True, num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, qk_norm: bool = False,
                 gated: bool = False, rope: Optional[tuple] = None,
                 eps: float = 1e-6):
        super().__init__()
        if head_dim is None:
            if embed_dim % num_heads:
                raise ValueError(
                    f"embed_dim {embed_dim} % num_heads {num_heads}")
            head_dim = embed_dim // num_heads
        num_kv_heads = num_kv_heads or num_heads
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} % num_kv_heads "
                             f"{num_kv_heads}")
        self.embed_dim = embed_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.causal = causal
        self.seq_parallel = seq_parallel
        self.seq_axis = seq_axis
        self.with_bias = with_bias
        self.qk_norm, self.gated, self.eps = qk_norm, gated, eps
        self.rotary_dim, self.inv_freq = 0, None
        if rope is not None:
            theta, self.rotary_dim = rope
            if self.rotary_dim % 2 or self.rotary_dim > head_dim:
                raise ValueError(f"rotary_dim {self.rotary_dim} of a head of "
                                 f"{head_dim}")
            self.inv_freq = rope_inv_freq(self.rotary_dim, theta)
        #: whether anything stands between the projections and the scores
        self._shaped = qk_norm or gated or rope is not None

    def _init(self, rng):
        ks = jax.random.split(rng, 4)
        e = self.embed_dim
        qw = self.num_heads * self.head_dim
        kvw = self.num_kv_heads * self.head_dim
        winit = self.weight_initializer or default_weight_init
        dt = get_policy().param_dtype

        def w(k, shape):
            fi, fo = compute_fans(shape)
            return winit(k, shape, fi, fo, dt)

        # gated: a head's columns of W_q are [q | gate]
        qcols = 2 * qw if self.gated else qw
        p = {"wq": w(ks[0], (e, qcols)), "wk": w(ks[1], (e, kvw)),
             "wv": w(ks[2], (e, kvw)), "wo": w(ks[3], (qw, e))}
        if self.with_bias:
            # distinct arrays per bias: aliased leaves crash buffer donation
            # in the compiled train step ("donate the same buffer twice")
            p.update({"bq": jnp.zeros((qcols,), dt),
                      "bk": jnp.zeros((kvw,), dt),
                      "bv": jnp.zeros((kvw,), dt), "bo": jnp.zeros((e,), dt)})
        if self.qk_norm:
            p.update({"q_norm": jnp.zeros((self.head_dim,), dt),
                      "k_norm": jnp.zeros((self.head_dim,), dt)})
        return p

    def _proj(self, params, x, name):
        c = get_policy().compute_dtype
        y = jax.lax.dot_general(
            x.astype(c), params["w" + name].astype(c),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(c)
        if self.with_bias:
            y = y + params["b" + name].astype(c)
        return y

    def _shape(self, params, q, k, pos):
        """The options, between the projections and the scores: ``q [...,
        H * D]`` (twice as wide when gated) and ``k [..., H_kv * D]`` at
        positions ``pos [...]`` -> (q, k, the heads' gates ``[..., H * D]``
        or None)."""
        H, G, D, r = (self.num_heads, self.num_kv_heads, self.head_dim,
                      self.rotary_dim)
        lead = q.shape[:-1]
        gate = None
        if self.gated:
            q = q.reshape(lead + (H, 2 * D))
            q, gate = q[..., :D], q[..., D:].reshape(lead + (H * D,))
        q, k = q.reshape(lead + (H, D)), k.reshape(lead + (G, D))
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.eps, plus_one=True)
            k = rms_norm(k, params["k_norm"], self.eps, plus_one=True)
        if r:
            cos, sin = (a[..., None, :] for a in
                        rope_angles(pos, self.inv_freq))
            q, k = (jnp.concatenate(
                [apply_rope_half(a[..., :r], cos, sin), a[..., r:]], axis=-1)
                for a in (q, k))
        return q.reshape(lead + (H * D,)), k.reshape(lead + (G * D,)), gate

    @staticmethod
    def _gate(o, gate):
        """A head's output times ``sigmoid`` of its gate (float32 inside)."""
        return (o.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)

    def _ring_over_tp(self, T):
        """The env-gated ring-attention seam: a MeshLayout 'tp' axis
        doubles as the sequence axis when BIGDL_TPU_RING_ATTN is set and
        the sequence divides it (parallel/ring_attention)."""
        from ..utils import config
        if not config.get_bool("RING_ATTN", False):
            return None
        from ..parallel.pipeline import _active_mesh
        mesh = _active_mesh()
        if mesh is None or "tp" not in mesh.axis_names:
            return None
        n = int(mesh.shape["tp"])
        if n <= 1 or T % n:
            return None
        return mesh

    def _apply(self, params, x):
        B, T, _ = x.shape
        H, G, D = self.num_heads, self.num_kv_heads, self.head_dim
        split = lambda y, n: y.reshape(B, T, n, D).transpose(0, 2, 1, 3)
        gate = None
        # two branches here and in ``decode_step``: the plain form keeps the
        # order of its operations, so its program is what it was
        if self._shaped:
            q, k, v = (self._proj(params, x, n) for n in "qkv")
            q, k, gate = self._shape(params, q, k, jnp.arange(T)[None])
            q, k, v = split(q, H), split(k, G), split(v, G)
        else:
            q = split(self._proj(params, x, "q"), H)
            k, v = (split(self._proj(params, x, n), G) for n in "kv")
        if G != H:
            # the full-sequence cores take a key head for every query head
            k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
        ring_mesh = None if self.seq_parallel else self._ring_over_tp(T)
        if self.seq_parallel:
            from ..parallel.ring_attention import ring_attention
            o = ring_attention(q, k, v, seq_axis=self.seq_axis,
                               causal=self.causal)
        elif ring_mesh is not None:
            from ..parallel.ring_attention import ring_attention
            o = ring_attention(q, k, v, mesh=ring_mesh, seq_axis="tp",
                               causal=self.causal,
                               batch_axis=("data", "fsdp"))
        else:
            from ..ops.attention import flash_attention
            o = flash_attention(q, k, v, causal=self.causal)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        if gate is not None:
            o = self._gate(o, gate)
        return self._proj(params, o, "o")

    # -- incremental decoding ------------------------------------------

    def _require_causal(self):
        if not self.causal:
            # a KV cache presumes causal attention; fail loudly instead of
            # silently masking a bidirectional model into different outputs
            raise NotImplementedError(
                "cached decoding requires causal attention "
                "(MultiHeadAttention(causal=False) found)")

    def decode_state(self, rows: int, length: int):
        """A key and a value for every position, the key-value heads side
        by side as the projections give them: ``[rows, length, H_kv * D]``
        each.  A position of a row is then one whole minor row of the leaf,
        which a step can write in place."""
        shape = (rows, length, self.num_kv_heads * self.head_dim)
        return {"k": StateLeaf(shape, 1, "kv_cache"),
                "v": StateLeaf(shape, 1, "kv_cache")}

    def _attend(self, q, k, v, mask, dtype):
        """q ``[B, Q, H * D]`` over keys and values ``[B, L, H_kv * D]``:
        float32 scores of each group's query heads over its one key head,
        exact-zero weight where ``mask`` (broadcast to ``[B, G, R, Q, L]``)
        is false; returns ``[B, Q, H * D]`` in ``dtype``."""
        B, Q, _ = q.shape
        L = k.shape[1]
        G, D = self.num_kv_heads, self.head_dim
        R = self.num_heads // G
        q = q.reshape(B, Q, G, R, D).transpose(0, 2, 3, 1, 4)
        scores = jnp.einsum("bgrqd,blgd->bgrql", q.astype(jnp.float32),
                            k.reshape(B, L, G, D).astype(jnp.float32)) \
            / (D ** 0.5)
        w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrql,blgd->bgrqd", w,
                       v.reshape(B, L, G, D).astype(jnp.float32))
        return o.astype(dtype).transpose(0, 3, 1, 2, 4) \
            .reshape(B, Q, G * R * D)

    def decode_prefill(self, params, x, cache, slot, length):
        """x: [n, P, E], a group of whole prompts from position 0, row i
        entering the fresh cache row `slot[i]`; returns ([n, P, E],
        new_cache).  (`length`, each prompt's real positions, is not needed
        here: the pads are computed and masked later.)

        Each prompt attends causally over itself with `decode_step`'s
        float32 score path and exact-zero masked weights; k and v of all P
        positions go into the cache, ``[n, P, H_kv * D]`` at rows `slot`
        from position 0, by one scatter of n windows a leaf
        (`write_prompt_rows`; a fill-up row's is dropped)."""
        self._require_causal()
        P = x.shape[1]
        slot, _ = prefill_rows(x, slot, length)
        q, k, v = (self._proj(params, x, n) for n in "qkv")
        gate = None
        if self._shaped:
            q, k, gate = self._shape(params, q, k, jnp.arange(P)[None])
        # attend over what the cache will hold: k and v in the cache's dtype
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        ck = write_prompt_rows(cache["k"], slot, k)
        cv = write_prompt_rows(cache["v"], slot, v)
        mask = jnp.arange(P)[None, :] <= jnp.arange(P)[:, None]
        o = self._attend(q, k, v, mask, x.dtype)
        if gate is not None:
            o = self._gate(o, gate)
        return self._proj(params, o, "o"), {"k": ck, "v": cv}

    def decode_step(self, params, x, cache, pos):
        """x: [S, 1, E], pos: [S] int32, every row at its own position;
        returns ([S, 1, E], new_cache).  Each row's key and value land at
        its position by one scatter of S whole minor rows a leaf
        (`_write_rows`: in place under the step's donation), not by a pass
        over the cache or a loop over the rows."""
        self._require_causal()
        pos = jnp.maximum(pos, 0)                 # an idle row: position 0
        q = self._proj(params, x, "q")
        gate = None
        if self._shaped:
            k, v = (self._proj(params, x, n) for n in "kv")
            q, k, gate = self._shape(params, q, k, pos[:, None])
            ck, cv = (_write_rows(cache[n], pos, a[:, 0])
                      for n, a in (("k", k), ("v", v)))
        else:
            ck, cv = (_write_rows(cache[n], pos,
                                  self._proj(params, x, n)[:, 0])
                      for n in "kv")
        # per-row causal horizon; positions past a row's pos get EXACT
        # zero softmax weight (exp(-inf)), so stale cache rows from a
        # previous occupant of the slot contribute exactly nothing
        mask = jnp.arange(ck.shape[1])[None, None, None, None, :] \
            <= pos[:, None, None, None, None]
        o = self._attend(q, ck, cv, mask, x.dtype)
        if gate is not None:
            o = self._gate(o, gate)
        return self._proj(params, o, "o"), {"k": ck, "v": cv}


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) over
    [B, T, hidden], causal.

    ``c_Q = RMSNorm(x W_DQ)``; a head's query is ``[q_nope, q_rope] = c_Q
    W_UQ``.  ``[c_KV, k_r] = x W_DKV``, ``c_KV = RMSNorm(c_KV)``; a head's
    ``[k_nope, v] = c_KV W_UKV``; ``k_rope = RoPE(k_r)`` is one vector for
    all heads.  Scores are ``(q_nope . k_nope + RoPE(q_rope) . k_rope) *
    scale`` with ``scale = (nope + rope)^-0.5 * mscale^2``, softmax in
    float32, output ``concat_heads(P v) W_O``.

    ``num_heads`` is the number of heads held here: a tensor-parallel share
    of a wider layer holds some heads' columns of ``W_UQ`` and ``W_UKV`` and
    rows of ``W_O``, and its output is that share's term of the sum.

    The full-sequence ``_apply`` expands keys and values (the training
    shape; prefill uses it).  ``decode_step`` is the same mathematics with
    ``W_UK`` absorbed into the query and ``W_UV`` into the output, so a
    position costs a read of ``c_KV`` and ``k_rope`` and nothing a head:
    those two are all the state kept.
    """

    #: what every tensor-parallel share computes alike stays whole;
    #: the per-head matrices split their head axis over tp
    PARAM_ROLES = {"wdq": "kernel_whole", "wdkv": "kernel_whole",
                   "wuq": "kernel_in", "wukv": "kernel_in",
                   "wo": "kernel_in", "*": "norm_scale"}

    #: queries of a long sequence attend in blocks of this many, so the
    #: float32 scores are [H, block, <= T] and never [H, T, T]
    QUERY_BLOCK = 512

    def __init__(self, hidden: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, rope_scaling=None,
                 eps: float = 1e-6):
        super().__init__()
        self.hidden, self.num_heads = hidden, num_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.eps = eps
        self.inv_freq = rope_inv_freq(qk_rope_head_dim, rope_theta,
                                      rope_scaling)
        m = 1.0
        if rope_scaling and rope_scaling.get("mscale_all_dim"):
            m = yarn_mscale(rope_scaling["factor"],
                            rope_scaling["mscale_all_dim"])
        # the cos/sin scale mscale(mscale) / mscale(mscale_all_dim) is 1
        # wherever the two are published equal, and is not applied
        self.score_scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 \
            * m * m

    def _init(self, rng):
        ks = jax.random.split(rng, 5)
        dt = get_policy().param_dtype
        winit = self.weight_initializer or default_weight_init

        def w(k, shape):
            fi, fo = compute_fans(shape)
            return winit(k, shape, fi, fo, dt)

        H = self.num_heads
        return {"wdq": w(ks[0], (self.hidden, self.q_lora_rank)),
                "q_norm": jnp.ones((self.q_lora_rank,), dt),
                "wuq": w(ks[1], (self.q_lora_rank,
                                 H * (self.nope + self.rope))),
                "wdkv": w(ks[2], (self.hidden,
                                  self.kv_lora_rank + self.rope)),
                "kv_norm": jnp.ones((self.kv_lora_rank,), dt),
                "wukv": w(ks[3], (self.kv_lora_rank,
                                  H * (self.nope + self.v_dim))),
                "wo": w(ks[4], (H * self.v_dim, self.hidden))}

    @staticmethod
    def _mm(x, w):
        """x @ w over the last axis: compute-dtype operands, float32
        accumulation, compute-dtype result."""
        c = get_policy().compute_dtype
        return jax.lax.dot_general(
            x.astype(c), w.astype(c), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(c)

    def _project(self, params, x, pos):
        """x [..., hidden] at positions ``pos [...]`` -> the heads' queries
        ``q_nope [..., H, nope]``, ``q_rope [..., H, rope]`` (rotated), and
        what a cache keeps: ``c_kv [..., kv_lora]`` (normed) and ``k_rope
        [..., rope]`` (rotated)."""
        H = self.num_heads
        cq = rms_norm(self._mm(x, params["wdq"]), params["q_norm"], self.eps)
        q = self._mm(cq, params["wuq"]).reshape(
            x.shape[:-1] + (H, self.nope + self.rope))
        q_nope, q_rope = q[..., :self.nope], q[..., self.nope:]
        kv = self._mm(x, params["wdkv"])
        c_kv = rms_norm(kv[..., :self.kv_lora_rank], params["kv_norm"],
                        self.eps)
        cos, sin = rope_angles(pos, self.inv_freq)
        k_rope = apply_rope(kv[..., self.kv_lora_rank:], cos, sin)
        q_rope = apply_rope(q_rope, cos[..., None, :], sin[..., None, :])
        return q_nope, q_rope, c_kv, k_rope

    def _up(self, params):
        """``W_UKV`` as ``[kv_lora, H, nope + v]``: a head's ``W_UK`` is
        ``[..., :nope]`` and its ``W_UV`` the rest."""
        return params["wukv"].reshape(self.kv_lora_rank, self.num_heads,
                                      self.nope + self.v_dim)

    def _expanded(self, params, q_nope, q_rope, c_kv, k_rope, length=None):
        """Causal attention of [B, T] positions with keys and values
        expanded from the latent; returns [B, T, hidden].  ``length``
        (traced): only the first ``length`` positions are real, as in a
        padded prompt; query blocks that hold none are not computed."""
        c = get_policy().compute_dtype
        B, T = c_kv.shape[:2]
        H = self.num_heads
        kv = jnp.einsum("btc,chd->bhtd", c_kv.astype(c),
                        self._up(params).astype(c),
                        preferred_element_type=jnp.float32).astype(c)
        k = jnp.concatenate(
            [kv[..., :self.nope],
             jnp.broadcast_to(k_rope.astype(c)[:, None],
                              (B, H, T, self.rope))], axis=-1)
        v = kv[..., self.nope:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(c) \
            .transpose(0, 2, 1, 3)                      # [B, H, T, d]

        def attend(start, stop):
            # queries start..stop-1 over the keys they may see, 0..stop-1
            s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, start:stop],
                           k[:, :, :stop],
                           preferred_element_type=jnp.float32) \
                * self.score_scale
            qi = jnp.arange(start, stop)[:, None]
            s = jnp.where(jnp.arange(stop)[None, :] <= qi, s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", w.astype(c), v[:, :, :stop],
                              preferred_element_type=jnp.float32).astype(c)

        n = self.QUERY_BLOCK
        if T > n and T % n == 0:
            # blocks of queries, one after another, each over the keys up
            # to its own end: the float32 scores are [H, n, <= T] at a
            # time, and the half above the diagonal is never computed
            blocks = []
            for start in range(0, T, n):
                if length is None:
                    blocks.append(attend(start, start + n))
                else:
                    blocks.append(jax.lax.cond(
                        start < length,
                        lambda s=start: attend(s, s + n),
                        lambda: jnp.zeros((B, H, n, self.v_dim), c)))
            o = jnp.concatenate(blocks, axis=2)
        else:
            o = attend(0, T)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * self.v_dim)
        return self._mm(o, params["wo"])

    def _apply(self, params, x):
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        return self._expanded(params, *self._project(params, x, pos))

    # -- incremental decoding ------------------------------------------

    def decode_state(self, rows: int, length: int):
        """The latent and the rotary key of every position, shared by all
        heads: ``[rows, length, kv_lora]`` and ``[rows, length, rope]``."""
        return {"c_kv": StateLeaf((rows, length, self.kv_lora_rank), 1,
                                  "latent_cache"),
                "k_rope": StateLeaf((rows, length, self.rope), 1,
                                    "latent_cache")}

    def decode_prefill(self, params, x, cache, slot, length):
        """x: [n, P, hidden], a group of whole prompts from position 0, of
        row i ``length[i]`` positions real: the expanded form over what the
        cache will hold, and one scatter of n windows a leaf."""
        P = x.shape[1]
        slot, length = prefill_rows(x, slot, length)
        q_nope, q_rope, c_kv, k_rope = self._project(
            params, x, jnp.arange(P)[None])
        c_kv = c_kv.astype(cache["c_kv"].dtype)
        k_rope = k_rope.astype(cache["k_rope"].dtype)
        new = {"c_kv": write_prompt_rows(cache["c_kv"], slot, c_kv),
               "k_rope": write_prompt_rows(cache["k_rope"], slot, k_rope)}
        # a block of queries is skipped where no row has a real one in it
        return self._expanded(params, q_nope, q_rope, c_kv, k_rope,
                              jnp.max(length)), new

    def decode_step(self, params, x, cache, pos):
        """x: [S, 1, hidden], pos: [S]: the absorbed form.  Each row's
        ``c_kv`` and ``k_rope`` land at its own position by one scatter of S
        rows (`_write_rows`, in place under the step's donation), not by a
        pass over the cache; scores and the weighted sum take compute-dtype
        operands from the cache as it is, with float32 accumulation."""
        c = get_policy().compute_dtype
        S = x.shape[0]
        pos = jnp.maximum(pos, 0)                 # an idle row: position 0
        q_nope, q_rope, c_kv, k_rope = self._project(params, x[:, 0], pos)
        cc = _write_rows(cache["c_kv"], pos, c_kv)
        ck = _write_rows(cache["k_rope"], pos, k_rope)
        up = self._up(params).astype(c)
        q_lat = jnp.einsum("shn,chn->shc", q_nope.astype(c),
                           up[..., :self.nope],
                           preferred_element_type=jnp.float32).astype(c)
        scores = (jnp.einsum("shc,slc->shl", q_lat, cc.astype(c),
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("shr,slr->shl", q_rope.astype(c),
                               ck.astype(c),
                               preferred_element_type=jnp.float32)) \
            * self.score_scale
        L = cc.shape[1]
        # positions past a row's pos get exact-zero weight: a previous
        # occupant's stale rows, and a prompt's pads, add exactly nothing
        mask = jnp.arange(L)[None, None, :] <= pos[:, None, None]
        w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("shl,slc->shc", w.astype(c), cc.astype(c),
                           preferred_element_type=jnp.float32).astype(c)
        o = jnp.einsum("shc,chv->shv", o_lat, up[..., self.nope:],
                       preferred_element_type=jnp.float32).astype(c)
        y = self._mm(o.reshape(S, self.num_heads * self.v_dim), params["wo"])
        return y[:, None], {"c_kv": cc, "k_rope": ck}
