"""gpt2_medium: how the benchmark builds this configuration out of the
program's public API, makes its weights, data and prompts from the seed, and
counts its operations.  Sizes come from gpt2_medium.json."""

from __future__ import annotations

import numpy as np

from benchmark.reference import common as refc
from benchmark.reference import gpt2_medium as ref


def set_policy(cfg) -> None:
    import jax.numpy as jnp
    from bigdl_tpu.common import DTypePolicy, set_policy as _set
    _set(DTypePolicy(param_dtype=jnp.dtype(cfg["param_dtype"]),
                     compute_dtype=jnp.dtype(cfg["compute_dtype"])))


def build_model(cfg):
    from bigdl_tpu.models.transformer_lm import TransformerLM
    return TransformerLM(vocab_size=cfg["vocab_size"],
                         max_len=cfg["n_positions"], d_model=cfg["n_embd"],
                         num_heads=cfg["n_head"], num_layers=cfg["n_layer"],
                         mlp_ratio=cfg["n_inner"] // cfg["n_embd"])


def criterion(cfg):
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    return TimeDistributedCriterion(ClassNLLCriterion(), size_average=True)


def optim_method(cfg):
    from bigdl_tpu.optim import Adam
    o = cfg["optimizer"]
    return Adam(o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                epsilon=o["epsilon"])


def init_params(cfg, key):
    return ref.init_params(cfg, key)


def loss_fn(cfg, prec: str = "f32"):
    return lambda params, x, y: ref.loss(cfg, params, x, y, prec)


def optimizer_rule(cfg):
    """The optimizer's rule written out, for the reference to follow."""
    hyper = cfg["optimizer"]
    return (refc.adam_init,
            lambda p, g, s, t: refc.adam_step(p, g, s, hyper, t))


def update_numbers(cfg, got: dict, ref_: dict) -> dict:
    """What is compared of the program's updates (``got``: parameters after
    the first and the last followed step) against the reference's
    (``ref_``: also the seeded weights ``p0`` and the first gradient
    ``g1``).  Adam's first update keeps only the gradient's sign."""
    p0 = ref_["p0"]
    d1 = [a - b for a, b in zip(got["p1"], p0)]
    out = {"grad_sign_gap": refc.sign_gap(d1, ref_["g1"])}
    out.update(refc.change_numbers(got["pk"], ref_["pk"], p0))
    return out


def logits_fn(cfg, prec: str = "f32"):
    return lambda params, tokens: ref.logits(cfg, params, tokens, prec,
                                             remat=False)


def records(cfg, traffic, seed: int):
    """``traffic['records']`` sequences that walk a cycle over
    ``traffic['alphabet']`` tokens spread through the vocabulary (next =
    current + stride, wrapping): learnable in a few steps, so the loss must
    fall.  Every row starts somewhere else."""
    r = np.random.default_rng(seed)
    n, t, alpha = traffic["records"], traffic["seq_len"], traffic["alphabet"]
    stride = cfg["vocab_size"] // alpha
    starts = np.concatenate([r.permutation(alpha)
                             for _ in range(-(-n // alpha))])[:n]
    idx = (starts[:, None] + np.arange(t + 1)[None, :]) % alpha
    toks = (idx * stride).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def model_flops_per_record(cfg, seq_len: int = None) -> float:
    """Operations forward and backward need for one sequence of ``seq_len``
    tokens: six per parameter of every matrix multiplication and token
    (twelve n_embd^2 a block, plus the head; embeddings are look-ups), plus
    causal attention's scores and weighted values: of the T^2 products only
    the half under the diagonal is required, 6 T n_embd a token and block."""
    t = seq_len or cfg["n_positions"]
    d, n = cfg["n_embd"], cfg["n_layer"]
    matmul_params = n * (4 * d * d + 2 * d * cfg["n_inner"]) \
        + d * cfg["vocab_size"]
    return t * (6.0 * matmul_params + 6.0 * n * t * d)
