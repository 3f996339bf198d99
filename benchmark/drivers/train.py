"""Driver ``train``: one ``Optimizer.optimize()`` call, timed from outside.

The window opens at the train-summary hook's call for iteration
``warmup_steps`` (the first iterations compile or read the compile cache) and
closes at the first hook call at or past ``--seconds``; the end trigger then
stops the loop.  The program fetches the loss at every iteration
(``log_interval`` 1, its default), so a hook call is a step's completion.

``correct`` follows the program's first ``check_steps`` iterations with the
plain reference (benchmark/reference): the same seeded weights, the batches
the program was really fed (tapped from the dataset chain), the optimizer's
rule written out.  The program's parameters after iterations 1 and
``check_steps`` come from the summary's ``Parameters`` histograms, a public
hook of the Optimizer.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import harness
from benchmark.reference import common as refc


def make_tap(n: int):
    """Last link of the dataset chain: keeps the first ``n`` minibatches as
    the program was fed them (shuffled by the program's own DataSet)."""
    from bigdl_tpu.dataset.transformer import Transformer

    class Tap(Transformer):
        def __init__(self):
            self.batches = []

        def __call__(self, it):
            for batch in it:
                if len(self.batches) < n:
                    self.batches.append((np.array(batch.get_input()),
                                         np.array(batch.get_target())))
                yield batch

    return Tap()


class StepClock:
    """The train-summary hook: stamps every iteration's ``Loss`` call with
    the host's clock, opens and closes the window, starts and stops the
    profiler in a traced run, and keeps the parameters after the iterations
    the reference is compared at."""

    def __init__(self, seconds, warmup_steps, capture_at, trace=None,
                 trace_seconds=0.0):
        self.seconds, self.warmup_steps = seconds, warmup_steps
        self.capture_at = set(capture_at)
        self.trace, self.trace_seconds = trace, trace_seconds
        self.stamps = []          # (iteration, perf_counter, loss)
        self.captured = {}        # iteration -> leaves in flatten order
        self.t_open = self.t_close = None
        self.on_open = None

    def add_scalar(self, tag, value, step):
        if tag != "Loss":
            return self
        t = time.perf_counter()
        self.stamps.append((int(step), t, float(value)))
        if step == self.warmup_steps:
            self.t_open = t
            if self.on_open:
                self.on_open()
        if self.t_open is not None and self.t_close is None \
                and t - self.t_open >= self.seconds:
            self.t_close = t
        if self.trace is not None and self.t_open is not None:
            if self.trace.t_start is None and step >= self.warmup_steps + 2 \
                    and self.t_close is None:
                self.trace.start()
            elif self.trace.active and (
                    t - self.trace.t_start >= self.trace_seconds
                    or self.t_close is not None):
                self.trace.stop()
        return self

    def add_histogram(self, name, values, step):
        self.captured.setdefault(int(step), []).append(np.array(values))
        return self

    def get_summary_trigger(self, name):
        if name == "Parameters":
            return lambda state: state["neval"] in self.capture_at
        return None

    def should_end(self, state) -> bool:
        return self.t_close is not None


def drive(run, window: bool = True):
    """Build the cell and run ``optimize()``; returns what was observed."""
    import jax
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.utils import telemetry

    cm, cfg, tr = run.cell.cfg_mod, run.cfg, run.traffic
    n_dev = len(jax.devices())
    batch = int(tr["batch_per_chip"]) * n_dev
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(run.seed))
    model.attach(params, state)
    del params, state
    x, y = cm.records(cfg, tr, run.seed)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    tap = make_tap(int(tr["check_steps"]))
    ds = (DataSet.array(samples, seed=run.seed)
          .transform(SampleToMiniBatch(batch, drop_last=True))
          .transform(tap))
    run.say("built", batch=batch, records=len(samples),
            setup_s=round(time.perf_counter() - run.t0, 2))

    trace = harness.TraceWindow(run.cell.name) if run.trace else None
    clock = StepClock(run.seconds if window else 0.0,
                      int(tr["warmup_steps"]), (1, int(tr["check_steps"])),
                      trace, float(tr["trace_seconds"]))
    spans = None
    if run.trace:
        # the program's own `data` spans, kept in memory (never flushed)
        spans = telemetry.Tracer(harness.TRACE_DIR, flush_every=0,
                                 ring=1 << 20)
        telemetry.set_active(spans)
    opt = (Optimizer(model, ds, cm.criterion(cfg))
           .set_optim_method(cm.optim_method(cfg))
           .set_end_when(Trigger(clock.should_end, "benchmarkWindow")))
    opt.set_train_summary(clock)
    clock.on_open = lambda: run.say(
        "open", setup_s=round(time.perf_counter() - run.t0, 2),
        compile_s=round(run.compiles.seconds(), 2),
        first_steps_s=[round(b[1] - a[1], 3) for a, b in
                       zip(clock.stamps, clock.stamps[1:])])
    try:
        opt.optimize()
    finally:
        if trace is not None and trace.active:
            trace.stop()
        if spans is not None:
            telemetry.set_active(None)
    peak = harness.memory_peak_bytes(run)
    span_events = spans.events_tail(1 << 20) if spans is not None else []
    del opt, ds, samples, x, y
    model.params = model.state = model.grads = None
    gc.collect()
    return {"clock": clock, "tap": tap, "batch": batch, "trace": trace,
            "spans": span_events, "memory_peak_bytes": peak, "n_dev": n_dev}


def window_metrics(run, seen) -> dict:
    """End-to-end numbers of the window, and its failures."""
    clock, batch = seen["clock"], seen["batch"]
    inside = [s for s in clock.stamps if clock.t_open < s[1] <= clock.t_close]
    times = [clock.t_open] + [s[1] for s in inside]
    gaps_ms = [(b - a) * 1e3 for a, b in zip(times, times[1:])]
    length = clock.t_close - clock.t_open
    run.say("window", steps=len(inside), seconds=length,
            step_ms_median=harness.quantile(gaps_ms, 0.5),
            p95_samples=len(gaps_ms),
            first_loss=clock.stamps[0][2], last_loss=clock.stamps[-1][2])
    return {"train_records_per_s": len(inside) * batch / length,
            "train_step_p95_ms": harness.quantile(gaps_ms, 0.95),
            "steps": len(inside), "gaps_ms": gaps_ms}


def program_numbers(run, seen) -> dict:
    """What the program produced in its first ``check_steps`` iterations."""
    k = int(run.traffic["check_steps"])
    clock = seen["clock"]
    return {"losses": [s[2] for s in clock.stamps[:k]],
            "p1": clock.captured[1], "pk": clock.captured[k],
            "batches": seen["tap"].batches[:k]}


def reference_numbers(run, batches, prec: str = "f32") -> dict:
    """The same iterations by the plain reference (``prec`` below f32 makes
    it the control).  Leaves are host arrays in the tree's flatten order,
    which every configuration keeps equal to the program's."""
    import jax
    cm, cfg = run.cell.cfg_mod, run.cfg
    p0 = jax.jit(lambda k: cm.init_params(cfg, k))(jax.random.key(run.seed))
    p0_host = [np.asarray(v) for v in jax.tree.leaves(p0)]
    t = time.perf_counter()
    losses, g1, p1, pk = refc.train_steps(
        cm.loss_fn(cfg, prec), p0, batches, cm.optimizer_rule(cfg))
    run.say("reference_steps", prec=prec,
            seconds=round(time.perf_counter() - t, 2))
    return {"losses": losses, "g1": g1, "p1": p1, "pk": pk, "p0": p0_host}


def compare(cm, cfg, got: dict, ref: dict) -> dict:
    """The numbers ``correct`` can be decided on: ``got`` (the program, or
    the control in its place) against the float32 reference ``ref``: each
    followed step's loss, and what the configuration's module compares of
    the updates (its optimizer decides how the first gradient shows in the
    first update).  A configuration's ``limits`` say which numbers it holds
    (PERF.md says why); the others are printed without a limit."""
    out = {f"loss_gap_{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]))}
    out.update(cm.update_numbers(cfg, got, ref))
    return out


def decide(run, seen, numbers: dict) -> None:
    """Every number compared, beside its limit."""
    clock = seen["clock"]
    lim = run.cfg["limits"]["train"]
    losses = [s[2] for s in clock.stamps]
    # the run's last loss against its first: a loop that learns nothing
    numbers = dict(numbers, last_over_first_loss=losses[-1] / losses[0])
    for name, value in numbers.items():
        # one limit for every step's loss, unless a step has its own
        key = "loss_gap" if name.startswith("loss_gap_") \
            and name not in lim else name
        if key in lim:
            run.check(name, value, lim[key])
        else:
            run.say("number", name=name, value=value)
    run.check("nonfinite_losses",
              sum(1 for v in losses if not math.isfinite(v)), 0)
    run.check("compiles_in_window",
              run.compiles.inside(clock.t_open, clock.t_close), 0)


def run(run) -> dict:
    seen = drive(run)
    clock = seen["clock"]
    e2e = window_metrics(run, seen)
    e2e["setup_s"] = clock.t_open - run.t0
    t_ref = time.perf_counter()
    got = program_numbers(run, seen)
    ref = reference_numbers(run, got["batches"])
    decide(run, seen, compare(run.cell.cfg_mod, run.cfg, got, ref))
    run.say("reference", seconds=round(time.perf_counter() - t_ref, 2),
            losses_program=got["losses"], losses_reference=ref["losses"])
    trace = seen["trace"].reduce() if seen["trace"] is not None else None
    facts = {"trace": trace, "spans": seen["spans"],
             "batch": seen["batch"], "n_dev": seen["n_dev"],
             "window_steps": [st[0] for st in clock.stamps
                              if clock.t_open < st[1] <= clock.t_close],
             "memory_peak_bytes": seen["memory_peak_bytes"],
             "cfg": run.cfg, "traffic": run.traffic, "device": run.device,
             "flops_per_record":
                 run.cell.cfg_mod.model_flops_per_record(run.cfg)}
    return {"e2e": e2e, "facts": facts, "attempted": e2e["steps"],
            "failed": 0}


def control(run, precs=("fp8",)) -> dict:
    """For benchmark/control.py: this seed's sound numbers and the control's
    (the reference at a lower precision in the program's place), from the
    first iterations alone: no measured window."""
    seen = drive(run, window=False)
    got = program_numbers(run, seen)
    ref = reference_numbers(run, got["batches"])
    cm = run.cell.cfg_mod
    out = {"program": compare(cm, run.cfg, got, ref),
           "losses": got["losses"], "losses_reference": ref["losses"]}
    for prec in precs:
        out[prec] = compare(cm, run.cfg, reference_numbers(
            run, got["batches"], prec), ref)
    return out
