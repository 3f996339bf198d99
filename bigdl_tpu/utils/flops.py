"""Analytic FLOP counting by walking a jaxpr.

Role in the reference: the perf harness `DistriOptimizerPerf.scala:91-95`
reports only records/s; MFU accounting is net-new for the TPU rebuild
(BASELINE.md: ResNet-50 >= 45% MFU on v5e).  XLA's `compiled.cost_analysis()`
is the primary FLOPs source, but it can fail on experimental backends — this
module is the deterministic fallback: trace the function with
`jax.make_jaxpr` (no compile, no device) and count matmul/conv FLOPs
directly from the equations, recursing into scan/cond/while/pjit/custom-vjp
sub-jaxprs.

Conventions: a dot_general counts 2*M*N*K (multiply+add); a conv counts
2 * prod(out_shape) * (in_features / feature_group_count) * prod(kernel_spatial).
Elementwise ops are ignored (matmul/conv dominate on the MXU).  `scan` bodies
are multiplied by trip count; `while_loop` bodies are counted once (trip count
is data-dependent) — callers that need exact totals should avoid while_loop in
the hot path anyway (it also blocks XLA pipelining).
"""

from __future__ import annotations

import math

import jax

__all__ = ["jaxpr_flops", "fn_flops", "device_peak_flops",
           "CPU_NOMINAL_PEAK"]

# bf16 peak FLOP/s per *jax device* (v2/v3 devices are single cores) —
# the MFU denominator bench.py and the Optimizer's per-step mfu counter
# share.  Ordering matters: "v5p" must match before "v5" (lite/e).
_TPU_PEAK_BF16 = (
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),  # v5 lite / v5e
    ("v4", 275e12), ("v3", 61.5e12), ("v2", 22.5e12),
)

# Nominal CPU denominator: there is no honest single peak for a shared
# host CPU, but a FIXED nominal one still makes the per-step mfu counter
# a usable *regression* signal in CPU traces (the absolute value is
# meaningless; the trend is not).  Override with BIGDL_TPU_PEAK_FLOPS.
CPU_NOMINAL_PEAK = 1e12


def device_peak_flops(device=None):
    """(peak_flops, source) for the MFU denominator.

    source is ``"env"`` (BIGDL_TPU_PEAK_FLOPS override), ``"table"`` (TPU
    device-kind match), or ``"nominal"`` (a non-TPU device,
    :data:`CPU_NOMINAL_PEAK`).  Callers that refuse to report MFU against
    a made-up denominator (bench.py) gate on ``source != "nominal"``.  A
    device on platform ``tpu`` whose kind matches no row of the table is
    an error: a utilisation against the CPU's nominal peak would look like
    a measurement."""
    from . import config
    env = config.get_float("PEAK_FLOPS", 0.0)
    if env > 0:
        return env, "env"
    if device is None:
        device = jax.devices()[0]
    if device.platform == "tpu":
        kind = device.device_kind.lower()
        for key, val in _TPU_PEAK_BF16:
            if key in kind:
                return val, "table"
        raise ValueError(
            f"no bf16 peak known for TPU device_kind "
            f"{device.device_kind!r}: add it to flops._TPU_PEAK_BF16")
    return CPU_NOMINAL_PEAK, "nominal"


def _prod(xs):
    return math.prod(int(x) for x in xs)


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, _rc), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        k = _prod(lhs[d] for d in lc)
        return 2.0 * _prod(out) * k
    if name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval.shape
        out = eqn.outvars[0].aval.shape
        # rhs_spec = (out_f, in_f, *spatial); the in_f dim of the kernel is
        # already per-group (in_features / feature_group_count), so no extra
        # group division is needed
        in_f = rhs[dn.rhs_spec[1]]
        k_spatial = _prod(rhs[d] for d in dn.rhs_spec[2:])
        return 2.0 * _prod(out) * in_f * k_spatial
    return 0.0


def _sub_jaxprs(eqn):
    """Yield (jaxpr, multiplier) for every sub-jaxpr in an equation."""
    name = eqn.primitive.name
    for pname, val in eqn.params.items():
        mult = 1.0
        if name == "scan" and pname == "jaxpr":
            mult = float(eqn.params.get("length", 1))
        for j in _iter_jaxprs(val):
            yield j, mult


def _iter_jaxprs(val):
    if hasattr(val, "eqns") or hasattr(val, "jaxpr"):  # Jaxpr / ClosedJaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _iter_jaxprs(v)


def jaxpr_flops(jaxpr) -> float:
    """Total matmul+conv FLOPs in a (Closed)Jaxpr, recursing into sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    total = 0.0
    for eqn in inner.eqns:
        total += _eqn_flops(eqn)
        if eqn.primitive.name == "cond":
            # conservative: cost of the most expensive branch, counted once
            branches = eqn.params.get("branches", ())
            if branches:
                total += max(jaxpr_flops(b) for b in branches)
            continue
        for sub, mult in _sub_jaxprs(eqn):
            total += mult * jaxpr_flops(sub)
    return total


def fn_flops(fn, *args, **kwargs) -> float:
    """FLOPs of one call of `fn(*args)` — traced, never compiled or executed."""
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    return jaxpr_flops(closed)
