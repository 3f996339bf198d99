"""Analytic FLOP counting by walking a jaxpr.

Role in the reference: the perf harness `DistriOptimizerPerf.scala:91-95`
reports only records/s; counting a model's operations is net-new for the TPU
rebuild.  Trace the function with `jax.make_jaxpr` (no compile, no device)
and count matmul/conv FLOPs directly from the equations, recursing into
scan/cond/while/pjit/custom-vjp sub-jaxprs.  The count states no speed: the
benchmark divides its configurations' own counts (checked against this one
in tests/benchmark) by a device time and by `benchmark/peaks.json`, the one
table of peaks.

Conventions: a dot_general counts 2*M*N*K (multiply+add), a ragged dot the
same over its rows (each meets one group's matrix), a Pallas call what its
`cost_estimate` states; a conv counts
2 * prod(out_shape) * (in_features / feature_group_count) * prod(kernel_spatial).
Elementwise ops are ignored (matmul/conv dominate on the MXU).  `scan` bodies
are multiplied by trip count; `while_loop` bodies are counted once (trip count
is data-dependent) — callers that need exact totals should avoid while_loop in
the hot path anyway (it also blocks XLA pipelining).
"""

from __future__ import annotations

import math

import jax

__all__ = ["jaxpr_flops", "fn_flops"]


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
    if name == "ragged_dot_general":
        # every row meets one group's matrix: [m, k] x [g, k, n] -> [m, n]
        lhs = eqn.invars[0].aval.shape
        return 2.0 * _prod(eqn.outvars[0].aval.shape) * lhs[-1]
    if name == "pallas_call":
        # a kernel states its own count (its body is one grid step's)
        cost = eqn.params.get("cost_estimate")
        return float(cost.flops) if cost is not None else 0.0
    return 0.0


def _sub_jaxprs(eqn):
    """Yield (jaxpr, multiplier) for every sub-jaxpr in an equation."""
    name = eqn.primitive.name
    if name == "pallas_call":
        return
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
