"""qwen3_next_share4: how the benchmark builds one chip's share of
Qwen3-Next-80B-A3B out of the program's public API, makes its weights from
the seed, and counts the bytes and operations that a decode step, its
matrix-state update and a prefill's chunked delta rule cannot avoid.  Sizes
come from qwen3_next_share4.json."""

from __future__ import annotations

from benchmark.reference import qwen3_next_share4 as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.qwen3_next import Qwen3NextLM
    z = ref.sizes(cfg)
    w = z["whole"]
    return Qwen3NextLM(
        vocab_size=z["vocab"], hidden=z["hidden"], num_layers=z["layers"],
        num_heads=w["num_attention_heads"],
        num_kv_heads=w["num_key_value_heads"], head_dim=z["head_dim"],
        linear_k_heads=w["linear_num_key_heads"],
        linear_v_heads=w["linear_num_value_heads"],
        linear_k_head_dim=z["dk"], linear_v_head_dim=z["dv"],
        expert_width=z["expert"], shared_width=z["shared"],
        num_experts=z["routed"], experts_per_token=z["k"],
        full_attention_interval=z["interval"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=z["theta"], conv_kernel=z["taps"], chunk=z["chunk"],
        v_heads_held=z["v_heads"], k_heads_held=z["k_heads"],
        heads_held=z["heads"], kv_heads_held=z["kv_heads"],
        experts_held=z["held"], eps=z["eps"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def routed_logits_fn(cfg, prec: str = "f32"):
    """What the ``decode_closed_routed`` driver compares served tokens
    with: ``f(params, tokens, forced) -> (logits, made, disagree)``, as
    ``nemotron3_nano_share2``'s.

    ``forced`` (int32 ``[rows, expert layers, width, k]``, -1 where there is
    none) are the experts another computation of the model chose: the
    served run's (``PendingRequest.routing``, which the driver keeps of
    each sampled request), or the control's.  ``logits`` are the
    reference's in ``prec`` *with those choices* (``ref.logits(forced=)``:
    every score, weight and sum the reference's own).  Routing is discrete
    and every layer of this model mixes positions, so one choice decided
    the other way by a bfloat16 program moves every later position's
    router, and a served token then lies far under the reference's best
    where neither is wrong (PERF.md section 2).

    ``made`` (int32, like ``forced``) are the choices this computation made:
    the forced ones where given, its own elsewhere (the control's own, for
    the driver to force into the float32 reference in its turn).
    ``disagree`` (``[rows, expert layers]``): of the positions with a forced
    choice, the share whose held experts are not what this reference's own
    router chooses there, given the forced choices everywhere before; a
    router that chooses wrongly is not to be followed into its fault, and
    the driver holds this to ``limits.decode.routing_disagree``."""
    z = ref.sizes(cfg)
    first, count = z["held"]

    def compared(params, tokens, forced):
        import jax.numpy as jnp
        out, own = ref.logits(cfg, params, tokens, prec, routers=True,
                              forced=forced)   # own [rows, layers, T, routed]
        rows, layers, width, _ = own.shape
        given = forced[..., 0] >= 0              # [rows, layers, T]
        hot = jnp.zeros(own.shape, bool).at[
            jnp.arange(rows)[:, None, None, None],
            jnp.arange(layers)[None, :, None, None],
            jnp.arange(width)[None, None, :, None],
            jnp.maximum(forced, 0)].set(True)
        differs = (hot != own)[..., first:first + count].any(-1)
        disagree = (differs & given).sum(-1) / jnp.maximum(given.sum(-1), 1)
        mine = jnp.argsort(~own, axis=-1, stable=True)[..., :z["k"]]
        made = jnp.where(given[..., None], forced, mine.astype(jnp.int32))
        return out, made, disagree

    return compared


def _layer_counts(z) -> tuple:
    full = sum(ref.is_full(z, l) for l in range(z["layers"]))
    return z["layers"] - full, full


def param_counts(cfg) -> dict:
    """Parameters held here, by what a decode step does with them: ``once``
    are read whole by every step (the linear and full mixers, norms,
    routers, shared experts and their gates, the head), ``routed`` are the
    held routed experts (a step reads those that some token chose),
    ``embedding`` is read a row a token; and by kind of block, one block
    each (mixer and experts with their two norms)."""
    z = ref.sizes(cfg)
    d = z["hidden"]
    linear = (d * (ref.conv_dim(z) + z["v_heads"] * z["dv"])
              + d * 2 * z["v_heads"] + z["taps"] * ref.conv_dim(z)
              + 2 * z["v_heads"] + z["dv"] + z["v_heads"] * z["dv"] * d)
    q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    full = d * 2 * q + 2 * d * kv + q * d + 2 * z["head_dim"]
    experts = z["held"][1] * 3 * d * z["expert"]
    moe_once = d * z["routed"] + 3 * d * z["shared"] + d
    n_linear, n_full = _layer_counts(z)
    once = (n_linear * linear + n_full * full
            + z["layers"] * (moe_once + 2 * d) + d + z["vocab"] * d)
    return {"once": once, "routed": z["layers"] * experts,
            "embedding": z["vocab"] * d,
            "linear_block": linear + experts + moe_once + 2 * d,
            "full_block": full + experts + moe_once + 2 * d}


def state_bytes_per_row(cfg) -> dict:
    """Bytes of decode state of fixed size one row holds: the matrix states
    (``ssm``, float32 ``[value heads, dk, dv]`` a linear layer) and the
    convolutions' last inputs (``conv``, ``[taps - 1, channels]`` in the
    compute dtype)."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    layers = _layer_counts(z)[0]
    return {"ssm": layers * z["v_heads"] * z["dk"] * z["dv"] * 4,
            "conv": layers * (z["taps"] - 1) * ref.conv_dim(z)
            * jnp.dtype(cfg["compute_dtype"]).itemsize}


def decode_step_min_bytes(cfg, active: float) -> float:
    """The bytes a decode step of ``active`` tokens cannot avoid, counted as
    the siblings count them: every held weight outside the routed experts
    and the embedding once; of the routed experts' weights the share that at
    least one of the tokens selects, ``1 - (1 - k / routed)^active`` (each
    token's choice taken as uniform); and the state of fixed size of
    ``active`` rows twice, since a step must read it and write it.  Keys and
    values, the activations and the embedding's rows are left out, so the
    count cannot come out too high."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    n = param_counts(cfg)
    active = max(float(active), 0.0)
    touched = 1.0 - (1.0 - z["k"] / z["routed"]) ** active
    return jnp.dtype(cfg["param_dtype"]).itemsize \
        * (n["once"] + n["routed"] * touched) \
        + 2.0 * active * sum(state_bytes_per_row(cfg).values())


def ssm_update_min_bytes(cfg, rows: int) -> int:
    """The bytes the matrix-state update of one decode step cannot avoid for
    ``rows`` slots: every ``ssm`` leaf read once and written once (the step
    updates every slot's row, idle or not).  The delta rule needs what the
    old state holds of the key before it can write the new one; a second
    pass over the state for that is not counted."""
    return 2 * rows * state_bytes_per_row(cfg)["ssm"]


def ssm_leaf_shape(cfg, rows: int) -> str:
    """One ``ssm`` leaf as the device trace prints it."""
    z = ref.sizes(cfg)
    return f"f32[{rows},{z['v_heads']},{z['dk']},{z['dv']}]"


def gdn_chunk_flops(cfg, positions: int) -> float:
    """Multiply-adds times two of the chunked gated delta rule over a prompt
    bucket of ``positions`` (whole chunks), all linear layers: a chunk and
    value head, with ``Q`` positions of ``dk`` and ``dv``: ``k k^T`` and ``q
    k^T`` (``2 Q^2 dk``), the triangular system against ``dk + dv`` columns
    by substitution (``Q^2 (dk + dv) / 2``), ``W S`` and ``q S`` (``2 Q dk
    dv``), ``(q k^T) v_new`` (``Q^2 dv``) and ``k^T v_new`` (``Q dk dv``).
    The projections, the convolution and the gated norm are not the chunked
    form's and are left out."""
    z = ref.sizes(cfg)
    Q, dk, dv = z["chunk"], z["dk"], z["dv"]
    chunks = -(-positions // Q)
    macs = (2 * Q * Q * dk + Q * Q * (dk + dv) / 2 + 3 * Q * dk * dv
            + Q * Q * dv)
    return 2.0 * macs * chunks * z["v_heads"] * _layer_counts(z)[0]


def gdn_chunk_min_bytes(cfg, positions: int) -> float:
    """The bytes the chunked form cannot avoid over a bucket of
    ``positions``, all linear layers: q, k, v of every position and value
    head read once in the compute dtype (q and k as the heads' repeats hold
    them), the decay and beta read once (float32), the output written once
    (float32), and the final state written once (float32)."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    c = jnp.dtype(cfg["compute_dtype"]).itemsize
    hv, dk, dv = z["v_heads"], z["dk"], z["dv"]
    per_position = hv * ((2 * dk + dv) * c + 2 * 4 + dv * 4)
    return float(_layer_counts(z)[0]
                 * (positions * per_position + hv * dk * dv * 4))
