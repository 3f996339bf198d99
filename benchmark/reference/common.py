"""What the plain references share: the precision a reference computes in,
optimizers' update rules written out for the configurations to pick from,
and the gaps that are compared.

A reference runs in float32 with ``precision=highest`` (on a TPU a float32
matrix multiplication is otherwise done in bfloat16 passes).  The same code
computes the *control*: with ``prec`` set to ``bf16`` every operand and every
result of a matrix multiplication or convolution is rounded to bfloat16,
which is the program's own recipe (bfloat16 compute, float32 parameters and
accumulation); with ``fp8`` the operands are rounded further, to e4m3 with
one scale per tensor (the usual recipe), the step that would tempt a later
PR.  Rounding is straight-through: the backward pass sees the rounded
operands and keeps float32 cotangents, the mildest form of the fault.

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _straight_through(x, r):
    return x + jax.lax.stop_gradient(r - x)


def rounded(x, prec: str):
    """``x`` as a matmul operand at precision ``prec`` (f32 | bf16 | fp8)."""
    if prec == "f32":
        return x
    if prec == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif prec == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        r = (x / scale).astype(_FP8).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return _straight_through(x, r)


def result(y, prec: str):
    """A matmul's result as the program keeps it: bfloat16 below f32."""
    if prec == "f32":
        return y
    return _straight_through(y, y.astype(jnp.bfloat16).astype(jnp.float32))


def matmul(a, b, prec: str):
    return result(jnp.matmul(rounded(a, prec), rounded(b, prec),
                             precision=HIGHEST), prec)


# ------------------------------------------------------------ optimizers


def sgd_init(params):
    return {"velocity": jax.tree.map(jnp.zeros_like, params)}


def sgd_step(params, grads, state, hyper, t):
    """Torch-style SGD: v = mu v + (1 - dampening) g;  w -= lr v."""
    mu, damp, lr = hyper["momentum"], hyper["dampening"], hyper["lr"]
    vel = jax.tree.map(lambda v, g: mu * v + (1.0 - damp) * g,
                       state["velocity"], grads)
    return (jax.tree.map(lambda w, v: w - lr * v, params, vel),
            {"velocity": vel})


def adam_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


def adam_step(params, grads, state, hyper, t):
    """Adam with bias correction; ``t`` is the 1-based step."""
    b1, b2, eps, lr = (hyper["beta1"], hyper["beta2"], hyper["epsilon"],
                       hyper["lr"])
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                     grads)
    step = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = jax.tree.map(lambda w, m_, v_: w - step * m_ / (jnp.sqrt(v_) + eps),
                       params, m, v)
    return new, {"m": m, "v": v}


def train_steps(loss_fn, params, batches, rule):
    """Follow ``len(batches)`` steps of training from ``params`` under the
    optimizer ``rule`` = ``(init(params), step(params, grads, state, t))``,
    as a configuration's module gives it: returns the loss of each step, the
    first gradient and the parameters after the first and after the last
    step (lists of host leaves, in the order of ``params``)."""
    init, step = rule
    vg = jax.jit(jax.value_and_grad(loss_fn))
    upd = jax.jit(step, donate_argnums=(0, 2))
    host = lambda tree: [np.asarray(x) for x in jax.tree.leaves(tree)]
    state = init(params)
    losses, g1, p1 = [], None, None
    for i, (x, y) in enumerate(batches):
        loss, grads = vg(params, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            g1 = host(grads)
        params, state = upd(params, grads, state, jnp.float32(i + 1))
        del grads
        if i == 0:
            p1 = host(params)
    return losses, g1, p1, host(params)


# ------------------------------------------------- numbers that are compared


def leaf_norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
                     for x in leaves])


def leaf_gaps(got: np.ndarray, want: np.ndarray, zero_below: float = 1e-3):
    """The gap between two lists of leaf norms, leaf by leaf, each measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' gradients are all but zero); and which
    leaves are real.  A leaf whose reference norm is under ``zero_below`` of
    the median is zero by construction (a bias in front of a batch norm,
    which removes it again): what a bfloat16 program has there is
    cancellation noise that changes nothing the model computes, and it is
    not real."""
    floor = float(np.median(want))
    return (np.abs(got - want) / np.maximum(want, floor),
            want >= zero_below * floor)


def worst_leaf_gap(got, want, only=None) -> float:
    """Largest gap over the real leaves (of those ``only`` marks, if given)."""
    gaps, real = leaf_gaps(got, want)
    return float(np.max(gaps[real if only is None else real & only]))


def median_leaf_gap(got, want) -> float:
    gaps, real = leaf_gaps(got, want)
    return float(np.median(gaps[real]))


def change_numbers(got_pk, ref_pk, p0, only=None) -> dict:
    """The parameters' change after the followed steps, program against
    reference: the gap of norms by the worst and the median leaf (and by the
    worst of the leaves ``only`` marks), and how many real leaves the
    program left exactly as they were."""
    dk_got = leaf_norms([a - b for a, b in zip(got_pk, p0)])
    dk_ref = leaf_norms([a - b for a, b in zip(ref_pk, p0)])
    _gaps, real = leaf_gaps(dk_got, dk_ref)
    out = {"dparam_norm_gap": worst_leaf_gap(dk_got, dk_ref),
           "dparam_norm_gap_median": median_leaf_gap(dk_got, dk_ref),
           "leaves_unchanged": int(np.sum(real & (dk_got == 0.0)))}
    if only is not None:
        out["dparam_norm_gap_weights"] = worst_leaf_gap(dk_got, dk_ref, only)
    return out


def sign_gap(update_leaves, grad_leaves) -> float:
    """For an optimizer whose first update keeps only the gradient's sign
    (Adam: the update is lr g / (|g| + eps)): the share of the reference
    gradient's absolute mass whose sign the update contradicts, worst leaf,
    each leaf's mass floored at the median leaf's."""
    wrong, mass = [], []
    for u, g in zip(update_leaves, grad_leaves):
        u = np.asarray(u, np.float32).ravel()
        g = np.asarray(g, np.float32).ravel()
        a = np.abs(g)
        # a sound update moves against the gradient: u g < 0
        wrong.append(float(a[(u * g) >= 0].sum(dtype=np.float64)))
        mass.append(float(a.sum(dtype=np.float64)))
    mass = np.array(mass)
    return float(np.max(np.array(wrong) / np.maximum(mass, np.median(mass))))
