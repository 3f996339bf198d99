"""jamba2_3b: how the benchmark builds AI21-Jamba2-3B, whole, out of the
program's public API, makes its weights from the seed, and counts the bytes a
decode step and its selective-state update cannot avoid.  Sizes come from
jamba2_3b.json."""

from __future__ import annotations

from benchmark.reference import jamba2_3b as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.jamba import JambaLM
    z = ref.sizes(cfg)
    return JambaLM(
        vocab_size=z["vocab"], hidden=z["hidden"], num_layers=z["layers"],
        attn_layer_period=z["period"], attn_layer_offset=z["offset"],
        num_heads=z["heads"], num_kv_heads=z["kv_heads"], mlp_width=z["mlp"],
        mamba_expand=cfg["mamba_expand"], mamba_state=z["state"],
        mamba_dt_rank=z["rank"], mamba_conv=z["taps"],
        tie_embeddings=cfg["tie_word_embeddings"], eps=z["eps"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def logits_fn(cfg, prec: str = "f32"):
    """What the decode driver compares served tokens with: the reference's
    logits at every position (nothing in this model is discrete before the
    greedy token, so every served position is held)."""
    return lambda params, tokens: ref.logits(cfg, params, tokens, prec)


def _layer_counts(z) -> tuple:
    attn = sum(ref.is_attention(z, l) for l in range(z["layers"]))
    return z["layers"] - attn, attn


def param_counts(cfg) -> dict:
    """Parameters, by what a decode step does with them: ``once`` are read
    whole by every step (every layer, the norms, and the table, which the
    head reads whole and the embedding a row a token: one leaf, counted
    once: ``once`` is the whole model); and by kind of layer, one layer each
    with its MLP and two norms."""
    z = ref.sizes(cfg)
    d, c, n, r = z["hidden"], z["inner"], z["state"], z["rank"]
    mamba = (d * 2 * c + z["taps"] * c + c + c * (r + 2 * n) + r * c + c
             + n * c + c + c * d + r + 2 * n)
    q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    attn = 2 * d * q + 2 * d * kv
    mlp = 3 * d * z["mlp"]
    n_mamba, n_attn = _layer_counts(z)
    table = z["vocab"] * d
    whole = (n_mamba * (mamba + mlp + 2 * d) + n_attn * (attn + mlp + 2 * d)
             + table + d)
    return {"once": whole, "table": table,
            "mamba_mixer": mamba, "attention_mixer": attn, "mlp": mlp,
            "mamba_layer": mamba + mlp + 2 * d,
            "attention_layer": attn + mlp + 2 * d}


def state_bytes_per_row(cfg) -> dict:
    """Bytes of decode state of fixed size one row holds: the recurrent
    states (``ssm``, float32 ``[state, d_inner]`` a Mamba layer) and the
    convolutions' last inputs (``conv``, ``[taps - 1, d_inner]`` in the
    compute dtype)."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    layers = _layer_counts(z)[0]
    return {"ssm": layers * z["state"] * z["inner"] * 4,
            "conv": layers * (z["taps"] - 1) * z["inner"]
            * jnp.dtype(cfg["compute_dtype"]).itemsize}


def decode_step_min_bytes(cfg, active: float) -> float:
    """The bytes a decode step of ``active`` tokens cannot avoid: every
    weight once (the table once: the head reads it whole) and the state of
    fixed size of ``active`` rows twice, since a step must read it and write
    it.  Keys and values and the activations are left out, so the count
    cannot come out too high."""
    import jax.numpy as jnp
    active = max(float(active), 0.0)
    return jnp.dtype(cfg["param_dtype"]).itemsize \
        * param_counts(cfg)["once"] \
        + 2.0 * active * sum(state_bytes_per_row(cfg).values())


def ssm_update_min_bytes(cfg, rows: int) -> int:
    """The bytes the selective-state update of one decode step cannot avoid
    for ``rows`` slots: every ``ssm`` leaf read once and written once (the
    step updates every slot's row, idle or not)."""
    return 2 * rows * state_bytes_per_row(cfg)["ssm"]


def ssm_leaf_shape(cfg, rows: int) -> str:
    """One ``ssm`` leaf as the device trace prints it."""
    z = ref.sizes(cfg)
    return f"f32[{rows},{z['state']},{z['inner']}]"

