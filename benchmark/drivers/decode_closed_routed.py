"""Driver ``decode_closed_routed``: the ``decode_closed`` loop, window and
metrics, for a model with routed experts whose served tokens can only be
held against the plain reference under the routing that was served.

Routing is discrete.  Where two experts score nearly alike, a bfloat16
program and the float32 reference choose differently and neither is wrong;
in a model whose layers mix positions one such difference moves every later
position's router, so a served token can lie far under the reference's best
at a position where nothing is at fault (PERF.md section 2).  The engine
returns the experts each request's tokens chose with the request
(``PendingRequest.routing``, for every caller), so here each client keeps
them beside the row, and ``correct`` is decided on two numbers:

* ``logit_gap``: as in ``decode_closed``, the widest gap by which a served
  token's float32 reference logit lies below the reference's best, over
  prompt + served tokens of a seeded sample of finished requests, the
  reference computed *with the served choices* (the configuration's
  ``routed_logits_fn``: every score, weight and sum the reference's own);
* ``routing_disagree``: the largest share, over the sampled requests and the
  expert layers, of positions whose served choice of held experts is not
  what the reference's own router chooses there, given the served choices
  everywhere before.  A router that chooses wrongly is not followed into
  its fault unseen.

The control is put in the program's place for real: the reference in the
lower precision makes its own choices and tokens, its choices are forced
into the float32 reference as the served ones are, and its tokens' gap is
read there.

Everything else is ``decode_closed``'s own code (its ``drive`` with this
file's ``Client``, ``window_metrics``, ``sample_rows``); a ``benchmark`` PR
that lets ``decode_closed`` hand a request's routing to ``logits_fn`` makes
this file one with it (PERF.md Open question 19).
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import harness

# a copy of decode_closed of this driver's own, whose clients are this
# file's: ``drive`` looks ``Client`` up when it starts them
base = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "decode_closed.py"), "bench_driver_decode_closed_routed_base")


class Client(base.Client):
    """``decode_closed``'s caller, keeping each request's routing beside its
    row: ``done`` holds (t_submit, t_done, prompt, max_tokens, row,
    routing)."""

    def run(self):
        for prompt, max_tokens in self.requests:
            if self.stop_flag.is_set():
                return
            t0 = self.waiting_since = time.perf_counter()
            try:
                req = self.engine.submit(prompt, max_tokens)
                row = np.asarray(req.result(600))
                self.done.append((t0, time.perf_counter(), prompt,
                                  max_tokens, row, req.routing))
            except Exception as e:  # noqa: BLE001 -- counted, not hidden
                self.errors.append((t0, time.perf_counter(), repr(e)))
            self.waiting_since = None
            self.first_done.set()


base.Client = Client


def compared_numbers(run, rows, precs=()) -> dict:
    """Over every generated position of the sampled requests: the widest gap
    by which the served token's float32 logit lies below the float32 best,
    the reference given the served routing, and the largest share of a
    layer's positions whose served choice is not the reference's own
    (module text).  For each of ``precs`` (below f32) also the control's
    gap: the token that precision puts first, read in the float32 reference
    under that precision's own choices (the control decodes nothing)."""
    import jax
    import jax.numpy as jnp
    cm, cfg = run.cell.cfg_mod, run.cfg
    width = run.traffic["prompt_len"][1] + run.traffic["output_len"][1]
    if any(d[5] is None for d in rows):
        raise RuntimeError("decode_closed_routed: a finished request carries "
                           "no routing (a model without routed experts "
                           "belongs with decode_closed)")
    layers, _n, k = rows[0][5].shape
    toks = np.zeros((len(rows), width), np.int32)
    served = np.full((len(rows), layers, width, k), -1, np.int32)
    for i, d in enumerate(rows):
        toks[i, :len(d[4])] = d[4]
        served[i, :, :d[5].shape[1]] = d[5]
    p0 = jax.jit(lambda key: cm.init_params(cfg, key))(
        jax.random.key(run.seed))
    f32 = jax.jit(cm.routed_logits_fn(cfg, "f32"))
    toks = jnp.asarray(toks)

    def widest(ref, token_at):
        gap = 0.0
        for i, d in enumerate(rows):
            t0, n = len(d[2]), len(d[4])
            at = np.arange(t0 - 1, n - 1)   # logits that chose t0..n-1
            gap = max(gap, float((ref[i, at].max(axis=-1)
                                  - ref[i, at, token_at(i, at)]).max()))
        return gap

    ref, _made, disagree = f32(p0, toks, jnp.asarray(served))
    ref = np.asarray(ref)
    out = {"logit_gap": widest(ref, lambda i, at: rows[i][4][at + 1]),
           "routing_disagree": float(np.asarray(disagree).max()),
           "positions": sum(len(d[4]) - len(d[2]) for d in rows),
           "control_gap": {}}
    for prec in precs:
        low, made, _ = jax.jit(cm.routed_logits_fn(cfg, prec))(
            p0, toks, jnp.full(served.shape, -1, jnp.int32))
        low = np.asarray(low)
        ref = np.asarray(f32(p0, toks, made)[0])
        out["control_gap"][prec] = widest(
            ref, lambda i, at: low[i, at].argmax(axis=-1))
    return out


def decide(run, seen) -> None:
    lim = run.cfg["limits"]["decode"]
    rows = base.sample_rows(run, seen)
    t = time.perf_counter()
    got = compared_numbers(run, rows)
    run.say("reference", seconds=round(time.perf_counter() - t, 2),
            requests=len(rows), served_tokens=got["positions"])
    run.check("logit_gap", got["logit_gap"], lim["logit_gap"])
    run.check("routing_disagree", got["routing_disagree"],
              lim["routing_disagree"])
    run.check("wrong_row_lengths",
              sum(1 for d in seen["done"] if len(d[4]) != len(d[2]) + d[3]),
              0)
    run.check("compiles_in_window",
              run.compiles.inside(seen["t_open"], seen["t_close"]), 0)
    run.check("client_threads_left", seen["threads_left"], 0)


def run(run) -> dict:
    seen = base.drive(run)
    e2e = base.window_metrics(run, seen)
    e2e["setup_s"] = seen["t_open"] - run.t0
    decide(run, seen)
    trace = seen["trace"].reduce() if seen["trace"] is not None else None
    facts = {"trace": trace,
             "counts": seen["counts"], "trace_counts": seen["trace_counts"],
             "slots": run.traffic["slots"],
             "memory_peak_bytes": seen["memory_peak_bytes"],
             "cfg": run.cfg, "traffic": run.traffic, "device": run.device}
    return {"e2e": e2e, "facts": facts,
            "attempted": e2e["completed"] + e2e["failed"],
            "failed": e2e["failed"]}


def control(run, precs=("fp8",)) -> dict:
    """For benchmark/control.py: a short window at the cell's own load, then
    this seed's sound numbers and the control's gap on the same prompts and
    tokens."""
    seen = base.drive(run)
    rows = base.sample_rows(run, seen)
    got = compared_numbers(run, rows, precs)
    out = {"program": {"logit_gap": got["logit_gap"],
                       "routing_disagree": got["routing_disagree"]},
           "served_tokens": got["positions"]}
    for prec in precs:
        out[prec] = {"logit_gap": got["control_gap"][prec]}
    return out
