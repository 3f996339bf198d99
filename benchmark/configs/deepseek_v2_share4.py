"""deepseek_v2_share4: how the benchmark builds one chip's share of
DeepSeek-V2 out of the program's public API, makes its weights from the seed,
and counts the bytes a decode step cannot avoid.  Sizes come from
deepseek_v2_share4.json."""

from __future__ import annotations

from benchmark.reference import deepseek_v2_share4 as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.deepseek import DeepSeekV2LM
    z = ref.sizes(cfg)
    return DeepSeekV2LM(
        vocab_size=z["vocab"], hidden=z["hidden"], num_layers=z["layers"],
        heads_held=z["heads"], q_lora_rank=z["q_lora"],
        kv_lora_rank=z["kv_lora"], qk_nope_head_dim=z["nope"],
        qk_rope_head_dim=z["rope"], v_head_dim=z["v"],
        dense_width=z["dense"], expert_width=z["expert"],
        num_experts=z["routed"], experts_per_token=z["k"],
        n_group=z["n_group"], topk_group=z["topk_group"],
        n_shared=z["shared"], routed_scaling_factor=z["scale"],
        first_k_dense=z["first_dense"], experts_held=z["held"],
        rope_theta=z["theta"], rope_scaling=z["scaling"], eps=z["eps"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def logits_fn(cfg, prec: str = "f32"):
    """What the decode driver compares served tokens with.  In float32: the
    reference's logits at the positions where every expert layer's choice
    among the held experts is decided by ``limits.decode.routing_margin``
    router logits (the reference's ``held_choice_decided``), and a flat row
    (every token alike, so no gap can be read there) at the others.  Routing
    is discrete: where a chosen expert leads one left out by less than the
    rounding of the activations that reach the router, a sound bfloat16
    program and the float32 reference choose different experts, that
    position's logits then differ by far more than any precision's
    rounding, and neither is wrong (PERF.md section 2 has the chip's
    readings; tools/routing_check.py makes them).  The mask is the float32
    reference's own, so the sound runs and the control are held at the same
    positions.  Should fewer than ``limits.decode.decided_share_min`` of the
    rows' real positions be decided, every position is held instead: a
    comparison of a handful of tokens is not let pass.  The share goes to
    stderr, one line a call.  Below float32 (the control): the plain logits,
    of which the driver takes the greedy token."""
    if prec != "f32":
        return lambda params, tokens: ref.logits(cfg, params, tokens, prec)
    lim = cfg["limits"]["decode"]
    margin, least = lim["routing_margin"], lim["decided_share_min"]

    def compared(params, tokens):
        import jax
        import jax.numpy as jnp
        out, seen = ref.logits(cfg, params, tokens, "f32", widths=(margin,))
        decided = seen["decided"][:, :, 0].all(axis=1)            # [B, T]
        # a row's real positions end at its last token that is not the pad
        real = jnp.flip(jnp.cumsum(jnp.flip(tokens != 0, 1), 1), 1) > 0
        n, of = (decided & real).sum(), real.sum()
        jax.debug.callback(_say_share, n, of)
        return jnp.where(decided[..., None] | (n < least * of), out, 0.0)

    return compared


def _say_share(n, of) -> None:
    import sys
    print(f"deepseek_v2_share4 logits_fn: routing decided at {int(n)} of "
          f"{int(of)} positions of the sampled rows", file=sys.stderr,
          flush=True)


def param_counts(cfg) -> dict:
    """Parameters held here, by what a decode step does with them: ``once``
    are read whole by every step (attention, norms, router, shared experts,
    the dense layers' MLP, the head), ``routed`` are the held routed experts
    (a step reads those that some token chose), ``embedding`` is read a row
    a token."""
    z = ref.sizes(cfg)
    d, h = z["hidden"], z["heads"]
    attn = (d * z["q_lora"] + z["q_lora"]
            + z["q_lora"] * h * (z["nope"] + z["rope"])
            + d * (z["kv_lora"] + z["rope"]) + z["kv_lora"]
            + z["kv_lora"] * h * (z["nope"] + z["v"]) + h * z["v"] * d)
    n_dense = z["first_dense"]
    n_moe = z["layers"] - n_dense
    per_moe = d * z["routed"] + 3 * d * z["shared"] * z["expert"]
    once = (z["layers"] * (attn + 2 * d) + n_dense * 3 * d * z["dense"]
            + n_moe * per_moe + d + z["vocab"] * d)
    return {"once": once,
            "routed": n_moe * z["held"][1] * 3 * d * z["expert"],
            "embedding": z["vocab"] * d}


def decode_step_min_bytes(cfg, active: float) -> float:
    """The bytes a decode step of ``active`` tokens cannot avoid reading:
    every held weight outside the routed experts and the embedding once, and
    of the routed experts' weights the share that at least one of the tokens
    selects, ``1 - (1 - k / routed)^active`` (each token's choice taken as
    uniform over the routed experts: group-limited routing on seeded weights
    spreads evenly, and an uneven spread touches fewer experts only if it is
    known beforehand).  The cache, the activations and the embedding's rows
    are left out, so the count cannot come out too high."""
    import jax.numpy as jnp
    z = ref.sizes(cfg)
    n = param_counts(cfg)
    touched = 1.0 - (1.0 - z["k"] / z["routed"]) ** max(float(active), 0.0)
    return jnp.dtype(cfg["param_dtype"]).itemsize \
        * (n["once"] + n["routed"] * touched)
