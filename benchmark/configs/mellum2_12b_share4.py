"""mellum2_12b_share4: how the benchmark builds one chip's share of
Mellum2-12B-A2.5B out of the program's public API, makes its weights from
the seed, and counts the bytes that a decode step, and its read of keys and
values, cannot avoid.  Sizes come from mellum2_12b_share4.json."""

from __future__ import annotations

from benchmark.reference import mellum2_12b_share4 as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.mellum import MellumLM
    z = ref.sizes(cfg)
    w = z["whole"]
    return MellumLM(
        vocab_size=z["vocab"], hidden=z["hidden"], layer_types=z["kinds"],
        num_heads=w["num_attention_heads"],
        num_kv_heads=w["num_key_value_heads"], head_dim=z["head_dim"],
        expert_width=z["expert"], num_experts=z["routed"],
        experts_per_token=z["k"], sliding_window=z["window"],
        rope_parameters=z["rope"], heads_held=z["heads"],
        kv_heads_held=z["kv_heads"], experts_held=z["held"], eps=z["eps"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def routed_logits_fn(cfg, prec: str = "f32"):
    """What the ``decode_closed_routed`` driver compares served tokens
    with: ``f(params, tokens, forced) -> (logits, made, disagree)``, as
    ``qwen3_next_share4``'s (its text says what each is): the reference's
    logits in ``prec`` with the choices ``forced`` (int32 ``[rows, expert
    layers, width, k]``, -1 where there is none), the choices this
    computation made, and the share of each layer's forced positions whose
    held experts are not the reference's own router's."""
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
    window = sum(kind == "sliding_attention" for kind in z["kinds"])
    return window, len(z["kinds"]) - window


def param_counts(cfg) -> dict:
    """Parameters held here, by what a decode step does with them: ``once``
    are read whole by every step (attention, norms, routers, the head),
    ``routed`` are the held routed experts (a step reads those that some
    token chose), ``embedding`` is read a row a token; and one ``block``
    (attention and experts with their two norms and the router)."""
    z = ref.sizes(cfg)
    d = z["hidden"]
    q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    attention = 2 * d * q + 2 * d * kv
    experts = z["held"][1] * 3 * d * z["expert"]
    router = d * z["routed"]
    layers = len(z["kinds"])
    once = layers * (attention + router + 2 * d) + d + z["vocab"] * d
    return {"once": once, "routed": layers * experts,
            "embedding": z["vocab"] * d,
            "block": attention + experts + router + 2 * d}


def state_bytes_per_row(cfg) -> dict:
    """Bytes of decode state one row holds: ``ring``, the window layers'
    keys and values, ``sliding_window`` rows each whatever the length; and
    ``position``, what the full layers keep of every position."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    window, full = _layer_counts(z)
    row = 2 * z["kv_heads"] * z["head_dim"] \
        * jnp.dtype(cfg["compute_dtype"]).itemsize
    return {"ring": window * z["window"] * row, "position": full * row}


def decode_step_min_bytes(cfg, active: float) -> float:
    """The bytes a decode step of ``active`` tokens cannot avoid, counted as
    ``deepseek_v2_share4`` counts them: every held weight outside the routed
    experts and the embedding once, and of the routed experts' weights the
    share that at least one of the tokens selects, ``1 - (1 - k /
    routed)^active`` (each token's choice taken as uniform).  Keys and
    values (``kv_read_min_bytes``), the activations and the embedding's
    rows are left out, so the count cannot come out too high."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    n = param_counts(cfg)
    touched = 1.0 - (1.0 - z["k"] / z["routed"]) ** max(float(active), 0.0)
    return jnp.dtype(cfg["param_dtype"]).itemsize \
        * (n["once"] + n["routed"] * touched)


def kv_read_min_bytes(cfg, active: float, mean_position: float) -> float:
    """The keys and values a decode step of ``active`` rows cannot avoid
    reading: every window layer's ring whole (each row past the window
    reads all of it; a row still inside it reads less, so a cell whose
    prompts are shorter than the window counts too much here and has to
    say so), and of every full layer the ``mean_position`` rows a row has
    behind it and at it.  The one row a step writes is left out."""
    state = state_bytes_per_row(cfg)
    return max(float(active), 0.0) \
        * (state["ring"] + state["position"] * float(mean_position))
