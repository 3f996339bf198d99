"""nemotron3_nano_share2: how the benchmark builds one chip's share of
Nemotron-3-Nano-30B-A3B out of the program's public API, makes its weights
from the seed, and counts the bytes a decode step, and its recurrent-state
update, cannot avoid.  Sizes come from nemotron3_nano_share2.json."""

from __future__ import annotations

from benchmark.reference import nemotron3_nano_share2 as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.nemotron import NemotronHLM
    z = ref.sizes(cfg)
    w = z["whole"]
    return NemotronHLM(
        vocab_size=z["vocab"], hidden=z["hidden"], pattern=z["pattern"],
        mamba_heads=w["mamba_num_heads"], mamba_head_dim=z["m_dim"],
        mamba_groups=w["n_groups"], ssm_state=z["state"],
        conv_kernel=z["taps"], chunk=z["chunk"],
        num_heads=w["num_attention_heads"],
        num_kv_heads=w["num_key_value_heads"], head_dim=z["head_dim"],
        expert_width=z["expert"], shared_width=z["shared"],
        num_experts=z["routed"], experts_per_token=z["k"],
        n_group=z["n_group"], topk_group=z["topk_group"],
        routed_scaling_factor=z["scale"], mamba_heads_held=z["m_heads"],
        heads_held=z["heads"], kv_heads_held=z["kv_heads"],
        experts_held=z["held"], eps=z["eps"],
        dt_range=(z["dt_min"], z["dt_max"]), dt_floor=z["dt_floor"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def routed_logits_fn(cfg, prec: str = "f32"):
    """What the ``decode_closed_routed`` driver compares served tokens
    with: ``f(params, tokens, forced) -> (logits, made, disagree)``.

    ``forced`` (int32 ``[rows, expert layers, width, k]``, -1 where there is
    none) are the experts another computation of the model chose: the
    served run's (``PendingRequest.routing``, which the driver keeps of
    each sampled request), or the control's.  ``logits`` are the
    reference's in ``prec`` *with those choices* (``ref.logits(forced=)``:
    every score, weight and sum the reference's own).  Routing is discrete,
    and this model mixes positions in 8 of its 14 layers: on the chip the
    bfloat16 program's choice of held experts differs from the float32
    reference's at 2-24 % of a layer's positions, one difference moves
    every later position's router, and a served token then lies up to 2
    under the reference's best where neither is wrong (PERF.md section 2,
    PR 32); the positions whose choice the reference decides by a safe
    margin in all six expert layers are 0.04 %, so ``deepseek_v2_share4``'s
    way of comparing only those compares nothing here.

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
        out, seen = ref.logits(cfg, params, tokens, prec, widths=(),
                               forced=forced)
        own = seen["chosen"]                     # [rows, layers, T, routed]
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


def param_counts(cfg) -> dict:
    """Parameters held here, by what a decode step does with them: ``once``
    are read whole by every step (the Mamba and attention layers, norms,
    routers, shared experts, the head), ``routed`` are the held routed
    experts (a step reads those that some token chose), ``embedding`` is
    read a row a token; and by kind of layer, one layer each."""
    z = ref.sizes(cfg)
    d = z["hidden"]
    inner, c = z["m_heads"] * z["m_dim"], ref.conv_dim(z)
    mamba = (d * (inner + c + z["m_heads"]) + z["taps"] * c + c
             + 3 * z["m_heads"] + inner + inner * d)
    q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    attn = 2 * d * q + 2 * d * kv
    expert = 2 * d * z["expert"]
    moe_once = d * z["routed"] + z["routed"] + 2 * d * z["shared"]
    n = {k: z["pattern"].count(k) for k in "ME*"}
    once = (n["M"] * mamba + n["*"] * attn + n["E"] * moe_once
            + len(z["pattern"]) * d + d + z["vocab"] * d)
    return {"once": once, "routed": n["E"] * z["held"][1] * expert,
            "embedding": z["vocab"] * d,
            "mamba_layer": mamba + d, "attention_layer": attn + d,
            "expert_layer": z["held"][1] * expert + moe_once + d}


def state_bytes_per_row(cfg) -> dict:
    """Bytes of decode state of fixed size one row holds: the recurrent
    states (``ssm``, float32 ``[heads, head_dim, state]`` a Mamba layer) and
    the convolutions' last inputs (``conv``, ``[taps - 1, channels]`` in the
    compute dtype)."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    layers = z["pattern"].count("M")
    return {"ssm": layers * z["m_heads"] * z["m_dim"] * z["state"] * 4,
            "conv": layers * (z["taps"] - 1) * ref.conv_dim(z)
            * jnp.dtype(cfg["compute_dtype"]).itemsize}


def decode_step_min_bytes(cfg, active: float) -> float:
    """The bytes a decode step of ``active`` tokens cannot avoid: every held
    weight outside the routed experts and the embedding once; of the routed
    experts' weights the share that at least one of the tokens selects, ``1
    - (1 - k / routed)^active`` (each token's choice taken as uniform, as
    deepseek_v2_share4 counts them); and the state of fixed size of
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
    """The bytes the recurrent-state update of one decode step cannot avoid
    for ``rows`` slots: every ``ssm`` leaf read once and written once (the
    step updates every slot's row, idle or not)."""
    return 2 * rows * state_bytes_per_row(cfg)["ssm"]


def ssm_leaf_shape(cfg, rows: int) -> str:
    """One ``ssm`` leaf as the device trace prints it."""
    z = ref.sizes(cfg)
    return f"f32[{rows},{z['m_heads']},{z['m_dim']},{z['state']}]"
