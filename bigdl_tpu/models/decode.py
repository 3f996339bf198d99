"""KV-cache incremental decoding for the TransformerLM family.

`greedy_generate` (transformer_lm.py) re-runs the full [B, max_len] forward
for every emitted token — O(T·L²) attention work per sequence.  This module
adds the serving-grade path: a per-layer key/value cache updated in place
(buffer-donated under jit), so each new token costs one [B, 1, E] forward
and an O(L) masked attention read — the standard TPU decode shape (static
cache length, position mask instead of dynamic slicing, exactly one
compile).

No reference counterpart (the 2017 reference serves batch predictors only,
`example/udfpredictor/`); this is part of the net-new long-context /
serving capability (SURVEY.md §7).

Works structurally: the decoder walks the same Module tree the training
forward uses (Sequential / residual ConcatTable+CAddTable / LayerNorm /
MoEFFN / MultiHeadAttention...), so a model trained through the Optimizer
decodes with its own modules — no weight surgery.  Unrecognized module
types raise rather than silently mis-decode.

What is kept between steps is each layer's own declaration
(``Module.decode_state``: leaves, each leaf's length axis, its layout
role): ``MultiHeadAttention`` keeps ``{k, v}`` of ``[rows, L, H_kv * D]``,
``LatentAttention`` ``{c_kv, k_rope}`` of ``[rows, L, width]`` (in both a
position of a row is one whole minor row, and a step writes its S new rows
in place under the donation, one scatter a leaf),
``PositionalEmbedding`` nothing but needs the position.

There are two kinds of leaf, and one cache holds both (``nn.StateLeaf``).
A leaf *with a length axis* holds something of every position: it grows by
a page along that axis, a step reads it under the mask ``<= pos``, and so a
prefill may leave its pads' rows, and a slot's last occupant its stale
ones, where they fell.  A leaf *without one* (``length_axis`` None:
``Mamba2Mixer``'s recurrent state ``ssm`` of ``[rows, H, P, N]`` float32
and its convolution's last inputs ``conv`` of ``[rows, K - 1, channels]``)
has a fixed size a row, and nothing masks it.  What a prefill owes such a
leaf: it starts from zero whatever the slot held, no pad moves it, and the
row is written whole, as it stands after the prompt's last real position.
What a step owes it: one update a row in place (an idle row may write
anything, since the next prefill of that slot overwrites all of it).
``grow_cache`` carries it over bit for bit.  No function here or in
serve/decode.py tests a layer's type for any of this.

``init_kv_cache`` asks the layers, and ``decode_walk`` is the one walk the
serving engine's two programs (prefill, step) share: it hands every
declaring layer its leaves through ``decode_prefill`` or ``decode_step``
and applies every other leaf to the positions in hand.  ``cached_generate`` keeps a walk of
its own (``_step``, one position shared by all rows, written out for
the plain ``MultiHeadAttention``; every other layer with state, that one
with its norms, gate or rotary positions among them, goes through its own
``decode_step``): it is the oracle the engine's tokens are held to.
"""

from __future__ import annotations

import weakref
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ..nn.attention import MultiHeadAttention
from ..nn.containers import ConcatTable, Sequential
from ..nn.module import Container
from .transformer_lm import PositionalEmbedding, sample_next

__all__ = ["init_kv_cache", "cached_generate", "beam_generate"]

# jitted decode step per model (weak: dropping the model drops the cache —
# the step closure holds only a weakref to the model, else the value would
# strongly reference its own key and defeat the WeakKeyDictionary);
# inner dict keyed by (batch, max_len, cache dtype) — the shapes that
# change the compiled program
_DECODE_STEP_CACHE = weakref.WeakKeyDictionary()


def _modules_of_type(module, cls):
    """Leaves of type `cls` in traversal order (== cache slot order)."""
    if isinstance(module, cls):
        return [module]
    if isinstance(module, Container):
        out = []
        for m in module.modules:
            out.extend(_modules_of_type(m, cls))
        return out
    return []


def _mha_modules(module):
    return _modules_of_type(module, MultiHeadAttention)


def _stateful_modules(model, rows: int = 1, length: int = 1):
    """The layers that keep something between decode steps, in traversal
    order (== the order of the cache list), each with its declaration."""
    def leaves(module):
        if isinstance(module, Container):
            for m in module.modules:
                yield from leaves(m)
        else:
            yield module

    return [(m, spec) for m in leaves(model)
            for spec in [m.decode_state(rows, length)] if spec]


def _cache_sharding(mesh, shape, role: str = "kv_cache"):
    """Where one leaf of the decode state lives on a canonical layout
    mesh: its declared role (``kv_cache``: rows over data x fsdp, heads
    over tp; ``latent_cache``: rows alone).  None without a mesh."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding
    from ..parallel import layout as _layout
    lay = _layout.MeshLayout.of_mesh(mesh)
    if lay is None:
        raise ValueError(
            "init_kv_cache: mesh lacks the canonical layout axes "
            "(build it with parallel/layout.MeshLayout.build_mesh)")
    return NamedSharding(mesh, lay.spec_for(role, shape, min_size=0))


def cache_avals(model, rows: int, length: int, dtype, mesh=None):
    """The decode state's shapes, dtypes and shardings, as the layers
    declare them: one dict a stateful layer."""
    return tuple(
        {n: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype or dtype,
            sharding=_cache_sharding(mesh, leaf.shape, leaf.role))
         for n, leaf in spec.items()}
        for _m, spec in _stateful_modules(model, rows, length))


def state_bytes_per_row(model, length: int, dtype) -> tuple:
    """Bytes of decode state one row holds at ``length`` positions, from
    the layers' declarations: (all leaves, the leaves of fixed size)."""
    total = fixed = 0
    for _m, spec in _stateful_modules(model, 1, length):
        for leaf in spec.values():
            n = int(np.prod(leaf.shape)) \
                * jnp.dtype(leaf.dtype or dtype).itemsize
            total += n
            if leaf.length_axis is None:
                fixed += n
    return total, fixed


def init_kv_cache(model, batch: int, max_len: int, dtype=jnp.float32,
                  mesh=None):
    """Zeroed decode state for ``batch`` rows of ``max_len`` positions: one
    dict of buffers for each layer that declares some
    (``Module.decode_state``), e.g. ``{k, v}`` of [B, max_len, H * D] for
    every ``MultiHeadAttention``.

    ``mesh``: optional canonical layout mesh (parallel/layout
    ``build_mesh``) — each leaf is then placed through its declared role
    (``kv_cache``: rows over data x fsdp, the heads' axis over tp), so a
    tp-sharded model decodes against caches that already match its
    column-parallel q/k/v kernels: each device holds exactly the 1/tp
    of the cache its heads produce, no per-step resharding."""
    caches = []
    for aval in cache_avals(model, batch, max_len, dtype, mesh):
        c = {}
        for n, a in aval.items():
            z = jnp.zeros(a.shape, a.dtype)
            c[n] = z if a.sharding is None else jax.device_put(z, a.sharding)
        caches.append(c)
    return caches


def grow_cache(model, caches, length: int, mesh=None):
    """``caches`` padded with zeros to ``length`` positions along each
    leaf's own length axis (masked positions carry exact-zero weight, so
    rows in flight decode on unchanged); a leaf of fixed size is carried
    over as it is (rows in flight keep their recurrence)."""
    grown = []
    rows = jax.tree.leaves(caches)[0].shape[0]
    for c, (_m, spec) in zip(caches, _stateful_modules(model, rows, length)):
        out = {}
        for n, arr in c.items():
            ax, shape = spec[n].length_axis, spec[n].shape
            out[n] = arr
            if ax is not None:
                pad = jnp.zeros(arr.shape[:ax] + (shape[ax] - arr.shape[ax],)
                                + arr.shape[ax + 1:], arr.dtype)
                out[n] = jnp.concatenate([arr, pad], axis=ax)
            sh = _cache_sharding(mesh, shape, spec[n].role)
            if sh is not None:
                out[n] = jax.device_put(out[n], sh)
        grown.append(out)
    return tuple(grown)


def _positions(a, at):
    """Row i of ``a [n, P, ...]`` at its own position ``at[i]``: ``[n, 1,
    ...]`` (as it is where one position is all it holds: an outer
    Sequential asks again for what an inner one has cut)."""
    if a.shape[1] == 1:
        return a
    return jnp.take_along_axis(
        a, at.reshape((-1,) + (1,) * (a.ndim - 1)), axis=1)


class _Walk:
    """One pass of some positions through a module tree with decode state
    (module docstring).  ``visit(module, params, x, cache) -> (y, cache)``
    serves the layers that declare state; ``caches`` (a list) is updated in
    place, and ``tally`` gathers what the layers without leaves report of
    the call (an expert layer: the tokens each held expert took, and the
    experts each position's router chose).  With
    ``last`` set (a prefill: all positions of a group's prompts at once;
    ``[n]``, a position a row), everything past the last layer that keeps
    leaves is position-wise, so a Sequential outside any ConcatTable keeps
    only each row's position ``last`` from there on: the rest of the model
    (the last block's MLP, a final norm, the head, LogSoftMax) runs on [n,
    1, E]."""

    def __init__(self, caches, visit, last=None):
        self.caches, self.visit = caches, visit
        self.last = last
        self.tally = []

    def report(self):
        """What the layers without leaves reported of the call, None where
        none did: (the token counts summed over those layers, a tuple of
        each layer's chosen experts, ``[..., k]`` int32, in traversal
        order)."""
        if not self.tally:
            return None
        counts = [c for c, _idx in self.tally]
        return sum(counts[1:], counts[0]), tuple(i for _c, i in self.tally)

    def walk(self, module, params, state, x, layer=0, in_table=False):
        """Returns (y, next_layer)."""
        spec = module.decode_state(1, 1)
        if spec is not None:
            if not spec:                      # no leaves: maybe a report
                y, report = self.visit(module, params, x, None)
                if report is not None:
                    self.tally.append(report)
                return y, layer
            y, self.caches[layer] = self.visit(module, params, x,
                                               self.caches[layer])
            return y, layer + 1
        if isinstance(module, Sequential):
            for m, p, s in zip(module.modules, module.child_params(params),
                               state):
                before = layer
                x, layer = self.walk(m, p, s, x, layer, in_table)
                if self.last is not None and not in_table \
                        and before < layer == len(self.caches):
                    x = jax.tree.map(lambda a: _positions(a, self.last), x)
            return x, layer
        if isinstance(module, ConcatTable):
            outs = []
            for m, p, s in zip(module.modules, params, state):
                o, layer = self.walk(m, p, s, x, layer, True)
                outs.append(o)
            return outs, layer
        if not isinstance(module, Container):
            # every other leaf (norms, Linear, activations, CAddTable, ...)
            # is position-independent: its own eval apply, on whatever
            # positions are in hand
            y, _ = module.apply(params, state, x, training=False, rng=None)
            return y, layer
        raise NotImplementedError(
            f"cached decoding: unsupported container "
            f"{type(module).__name__}")


def _cached_attention(mha, params, x, cache, pos):
    """x: [B, 1, E] at position `pos`; returns ([B, 1, E], new_cache)."""
    if not mha.causal:
        # a KV cache presumes causal attention; fail loudly instead of
        # silently masking a bidirectional model into different outputs
        raise NotImplementedError(
            "cached decoding requires causal attention "
            "(MultiHeadAttention(causal=False) found)")
    q = mha._proj(params, x, "q")
    # the leaves are [B, L, H_kv * D] (MultiHeadAttention.decode_state):
    # every row writes the one position
    ck = jax.lax.dynamic_update_slice(
        cache["k"], mha._proj(params, x, "k").astype(cache["k"].dtype),
        (0, pos, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], mha._proj(params, x, "v").astype(cache["v"].dtype),
        (0, pos, 0))
    mask = jnp.arange(ck.shape[1]) <= pos
    o = mha._attend(q, ck, cv, mask, x.dtype)
    return mha._proj(params, o, "o"), {"k": ck, "v": cv}


def _step(module, params, state, x, caches, slot, pos):
    """Incremental apply of one module; returns (y, next_slot).

    `caches` is mutated in place (list of per-MHA dicts) — the caller
    rebuilds the functional output tuple.
    """
    if isinstance(module, MultiHeadAttention) and not module._shaped:
        y, caches[slot] = _cached_attention(module, params, x, caches[slot],
                                            pos)
        return y, slot + 1
    if isinstance(module, PositionalEmbedding):
        return x + jax.lax.dynamic_slice_in_dim(
            params["weight"], pos, 1, axis=0).astype(x.dtype)[None], slot
    if module.decode_state(1, 1):
        # any other layer with state has no second form written out here:
        # its own step, every row at the one position
        y, caches[slot] = module.decode_step(
            params, x, caches[slot],
            jnp.full((x.shape[0],), pos, jnp.int32))
        return y, slot + 1
    if isinstance(module, Sequential):
        for m, p, s in zip(module.modules, module.child_params(params),
                           state):
            x, slot = _step(m, p, s, x, caches, slot, pos)
        return x, slot
    if isinstance(module, ConcatTable):
        outs = []
        for m, p, s in zip(module.modules, params, state):
            o, slot = _step(m, p, s, x, caches, slot, pos)
            outs.append(o)
        return outs, slot
    if not isinstance(module, Container):
        # leaf modules (LayerNorm, Linear, GELU, CAddTable, MoEFFN, ...)
        # are position-independent: reuse their own eval apply
        y, _ = module.apply(params, state, x, training=False, rng=None)
        return y, slot
    raise NotImplementedError(
        f"cached decoding: unsupported container {type(module).__name__}")


def _prefill(model, params, state, toks, caches, slot, t0):
    """One-pass prefill of a group of n sequences, row i into cache row
    `slot[i]`: `toks` is `[n, P]`, each row a prompt with pads past its
    `t0[i]` real tokens (P no longer than the cache); `slot` and `t0` are
    `[n]`.  One pass over the weights serves the whole group.  A row whose
    `slot` lies past the cache's rows (with `t0` 0) fills the program up: it
    writes nothing and is counted nowhere.  Returns the `[n, V]` logits of
    each row's position `t0 - 1`, the caches, and what the expert layers
    report (None for a model that has none): the token counts of the
    group's real tokens, and a tuple of the experts each layer's router
    chose, `[n, positions, k]` int32 a layer: the P positions of the
    bucket, pads included, or, past the last layer that keeps leaves, the
    one position `t0 - 1`.

    In a leaf with a length axis, rows t0..P-1 of a slot take the pads'
    state: finite, and masked by `<= pos` in every later step until the
    sequence overwrites them, like a previous occupant's stale rows.  A
    leaf of fixed size is written whole a row, as it is after position t0 -
    1 (each layer's `decode_prefill` sees every row's `t0` and keeps its
    pads out)."""
    last = jnp.maximum(t0 - 1, 0)
    w = _Walk(list(caches),
              lambda m, p, x, c: m.decode_prefill(p, x, c, slot, t0),
              last=last)
    y, _ = w.walk(model, params, state, toks)
    if y.shape[1] != 1:  # nothing follows the last stateful layer
        y = _positions(y, last)
    return y[:, 0], tuple(w.caches), w.report()


def _slot_step(model, params, state, tok, caches, pos):
    """Every row one position forward, each at its own: `tok` and `pos`
    are [S]; a row with `pos` < 0 is idle (it computes position 0 of
    token `tok`, is counted nowhere, and what it writes a prefill
    overwrites).  Returns ([S, V] logits, caches, and what the expert
    layers report, None for a model that has none: the live rows' token
    counts and the experts every row's routers chose, `[layers, S, k]`
    int32, idle rows included as routed)."""
    w = _Walk(list(caches),
              lambda m, p, x, c: m.decode_step(p, x, c, pos))
    y, _ = w.walk(model, params, state, tok[:, None])
    report = w.report()
    if report is not None:
        report = report[0], jnp.stack([i[:, 0] for i in report[1]])
    return y[:, -1], tuple(w.caches), report


def _get_step(model, rows: int, max_len: int, dtype):
    """The jitted one-position decode step, cached per
    (model, rows, max_len, dtype)."""
    shape_key = (rows, max_len, jnp.dtype(dtype).name)
    per_model = _DECODE_STEP_CACHE.setdefault(model, {})
    step = per_model.get(shape_key)
    if step is None:
        model_ref = weakref.ref(model)  # break the value->key cycle

        @partial(jax.jit, donate_argnums=(2,))  # cache updated in place
        def step(params, state, caches, tok, pos):
            x = tok[:, None]  # [rows, 1] token ids; LookupTable embeds them
            caches = list(caches)
            y, _ = _step(model_ref(), params, state, x, caches, 0, pos)
            return y[:, -1], tuple(caches)

        per_model[shape_key] = step
    return step


def _validate_generate(model, toks, num_tokens, max_len):
    if toks.shape[1] == 0:
        raise ValueError("empty prompt")
    if toks.shape[1] + num_tokens > max_len:
        raise ValueError(f"prompt ({toks.shape[1]}) + num_tokens "
                         f"({num_tokens}) exceeds max_len ({max_len})")
    for pe in _modules_of_type(model, PositionalEmbedding):
        if max_len > pe.max_len:
            # fail loudly like the full forward would — dynamic_slice on a
            # traced position would otherwise CLAMP and silently mis-decode
            raise ValueError(f"max_len {max_len} > model positional "
                             f"embedding max_len {pe.max_len}")
    if model.params is None:
        model.build()


def beam_generate(model, prompt, num_tokens: int, max_len: int,
                  beam_size: int = 4, pad_token: int = 0,
                  eos_token: int = None, cache_dtype=None):
    """Beam-search decoding over the KV cache: keeps the `beam_size`
    highest-total-log-prob hypotheses per batch row; returns the best
    sequence(s), [t0+num_tokens] for a 1-D prompt else [B, t0+num_tokens].

    Assumes the model emits log-probabilities (the zoo TransformerLM ends
    in LogSoftMax) so per-step scores sum to a sequence log-prob.
    beam_size=1 reduces exactly to greedy.  Per step, the KV caches are
    reordered along the row axis to follow the surviving hypotheses
    (device-side jnp.take).

    eos_token: a finished hypothesis (one that emitted eos_token) stops
    accumulating log-prob — its only continuation is `pad_token` at score
    0 — so shorter finished sequences compete fairly against longer live
    ones and are padded to length in the output."""
    prompt_arr = np.asarray(prompt, np.int32)
    toks = prompt_arr[None, :] if prompt_arr.ndim == 1 else prompt_arr
    B, t0 = toks.shape
    _validate_generate(model, toks, num_tokens, max_len)
    if beam_size < 1:
        raise ValueError(f"beam_size {beam_size}")
    if eos_token is not None and eos_token == pad_token:
        raise ValueError("eos_token must differ from pad_token (padding "
                         "marks the post-EOS tail)")

    from ..common import get_policy
    dtype = cache_dtype or get_policy().compute_dtype
    rows = B * beam_size
    step = _get_step(model, rows, max_len, dtype)
    buf = np.full((rows, max_len), pad_token, np.int32)
    buf[:, :t0] = np.repeat(toks, beam_size, axis=0)
    # prefill with B rows only (all beams are byte-identical until the
    # first scored step), then expand the caches beam_size-fold — saves
    # beam_size x the prompt FLOPs/cache traffic for long prompts
    if t0 > 1 and beam_size > 1:
        pre = _get_step(model, B, max_len, dtype)
        caches = tuple(init_kv_cache(model, B, max_len, dtype))
        for pos in range(t0 - 1):
            _, caches = pre(model.params, model.state, caches,
                            jnp.asarray(toks[:, pos]), pos)
        caches = tuple({k2: jnp.repeat(c[k2], beam_size, axis=0)
                        for k2 in c} for c in caches)
    else:
        caches = tuple(init_kv_cache(model, rows, max_len, dtype))
        for pos in range(t0 - 1):
            _, caches = step(model.params, model.state, caches,
                             jnp.asarray(buf[:, pos]), pos)
    # all beams start as copies of the prompt; only beam 0 may expand on
    # the first scored step, else the top-k would pick duplicates
    scores = np.full((B, beam_size), -np.inf, np.float64)
    scores[:, 0] = 0.0
    finished = np.zeros((B, beam_size), bool)
    for pos in range(t0 - 1, t0 + num_tokens - 1):
        logits, caches = step(model.params, model.state, caches,
                              jnp.asarray(buf[:, pos]), pos)
        lp = np.asarray(logits, np.float64).reshape(B, beam_size, -1)
        V = lp.shape[-1]
        if eos_token is not None and finished.any():
            # a finished beam's only continuation is pad at logprob 0:
            # its score freezes and it keeps competing in the top-k
            lp = np.where(finished[:, :, None], -np.inf, lp)
            lp[:, :, pad_token] = np.where(finished, 0.0,
                                           lp[:, :, pad_token])
        flat = (scores[:, :, None] + lp).reshape(B, beam_size * V)
        k = min(beam_size, flat.shape[1])
        top = np.argpartition(flat, -k, axis=-1)[:, -k:]
        order = np.argsort(-np.take_along_axis(flat, top, -1), axis=-1)
        top = np.take_along_axis(top, order, -1)
        scores = np.take_along_axis(flat, top, -1)        # [B, k] desc
        src = top // V                                    # surviving beam
        tok = (top % V).astype(np.int32)
        gather = (np.arange(B)[:, None] * beam_size + src).reshape(-1)
        if not np.array_equal(gather, np.arange(rows)):
            buf = buf[gather].copy()
            # cache reorder is a full [rows, max_len, H * D] copy per layer —
            # skip when the permutation is the identity (always true for
            # beam_size=1) and on the final step, whose caches are unused
            if pos + 2 < t0 + num_tokens:
                gidx = jnp.asarray(gather)
                caches = tuple({k2: jnp.take(c[k2], gidx, axis=0)
                                for k2 in c} for c in caches)
        buf[:, pos + 1] = tok.reshape(-1)
        if eos_token is not None:
            finished = np.take_along_axis(finished, src, axis=1) | \
                (tok == eos_token)
            if finished.all():
                break  # buf is pad-prefilled; remaining steps are no-ops
    out = buf.reshape(B, beam_size, max_len)[:, 0, : t0 + num_tokens]
    return out[0] if prompt_arr.ndim == 1 else out


def cached_generate(model, prompt, num_tokens: int, max_len: int,
                    pad_token: int = 0, temperature: float = 0.0,
                    top_k: int = 0, rng=None, cache_dtype=None,
                    mesh=None):
    """KV-cache decode: same contract as transformer_lm.greedy_generate
    (greedy when temperature == 0, else temperature/top-k sampling) but
    each generated token runs a [B, 1, E] incremental forward against the
    cache instead of a full [B, max_len] re-forward.

    Greedy outputs are bit-identical to greedy_generate (parity-tested).
    MoE caveat, for the capacity-routed ``parallel/expert.MoEFFN`` only:
    its capacity is computed from the live token count, so with a large
    batch an expert can overflow in one mode but not the other (both drop
    per the capacity contract); raise capacity_factor on the model if
    exact parity at scale matters.  ``GatedMoE`` has no capacity and drops
    nothing in either mode.

    ``mesh``: optional canonical layout mesh — params are placed through
    the role table (parallel/layout.assign_shardings) and caches through
    the ``kv_cache`` role, so a tp-sharded model serves decode through
    the existing mesh machinery unchanged (jit propagates the input
    shardings; no resharding in the step).
    """
    prompt_arr = np.asarray(prompt, np.int32)
    toks = prompt_arr[None, :] if prompt_arr.ndim == 1 else prompt_arr
    B, t0 = toks.shape
    _validate_generate(model, toks, num_tokens, max_len)
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng=")

    from ..common import get_policy
    dtype = cache_dtype or get_policy().compute_dtype
    params, state = model.params, model.state
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel import layout as _layout
        params = jax.device_put(
            params, _layout.assign_shardings(model, params, mesh))
        rep = NamedSharding(mesh, PartitionSpec())
        state = jax.device_put(state, jax.tree.map(lambda _: rep, state))
    step = _get_step(model, B, max_len, dtype)
    caches = tuple(init_kv_cache(model, B, max_len, dtype, mesh=mesh))
    buf = np.full((B, max_len), pad_token, np.int32)
    buf[:, :t0] = toks
    for pos in range(t0 + num_tokens - 1):
        logits, caches = step(params, state, caches,
                              jnp.asarray(buf[:, pos]), pos)
        if pos + 1 < t0:
            continue  # prompt prefill: only the cache matters
        buf[:, pos + 1], rng = sample_next(np.asarray(logits), temperature,
                                           top_k, rng)
    out = buf[:, : t0 + num_tokens]
    return out[0] if prompt_arr.ndim == 1 else out
