"""Driver ``decode_open``: requests arrive on a schedule fixed before the
window, whatever the engine answers, against one ``DecodeEngine``: a chat
or API front end behind which independent users arrive, and nobody waits
for anybody else's reply.

The requests are ``decode_closed``'s (its ``make_requests``: round after
round of one grid of lengths in seeded order).  The gaps between arrivals are
one fixed grid too (``arrival_gaps``): the quantiles of an exponential of
mean ``1 / arrival_rate_per_s``, each round of them in a seeded order of its
own, so bursts occur and every seed offers the same load.  One thread submits
each request at its time and never waits for a reply; ``slots`` +
``queue_limit`` waiting threads (the most the engine can hold) each take a
submitted request, block in ``result()`` and stamp its return, so a latency
is ``submit()`` to ``result()`` on the caller's clock as in ``decode_closed``.

Set-up is ``decode_closed``'s (seeded weights, the engine, one request for
each prefill bucket the traffic reaches); then arrivals start, the window
opens ``ramp_seconds`` later and closes ``--seconds`` after that, and
arrivals go on, so the load stays, until every request submitted inside the
window is answered (at most ``tail_wait_seconds``).  Tokens, latencies,
``failed`` and ``correct`` are ``decode_closed``'s (its ``window_metrics`` and
``decide`` on a copy of that module of this driver's own), with two exact
checks more: ``late_submits``, the window's submissions made more than
``late_submit_ms`` (5) after their time, held to a hundredth of them (the
driver is not the bottleneck), and ``rejected``, ``ServerOverloaded``
answers, held to 0 (the rate lies under what the engine sustains).

A traced run also keeps the program's own spans: an in-memory ``Tracer`` is
active from before the warm-up requests to the engine's stop, its events go
to ``facts["spans"]``, and ``facts["window"]`` is the window's opening and
closing on the tracer's clock (microseconds; the driver marks both with an
instant event).  An untraced run installs no tracer.
"""

from __future__ import annotations

import gc
import math
import os
import queue
import threading
import time

import numpy as np

from benchmark import harness

# a copy of decode_closed of this driver's own: its requests, window
# arithmetic, sampled rows and checks, driven by this file's ``drive``
base = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "decode_closed.py"), "bench_driver_decode_open_base")

OPEN_MARK, CLOSE_MARK = "benchmark.window_open", "benchmark.window_close"


def arrival_gaps(tr, seed: int, n: int) -> np.ndarray:
    """Seconds between consecutive arrivals, ``n`` of them.  One round is the
    ``grid[0] x grid[1]`` quantiles ``-ln(1 - (i + 0.5) / k) / rate`` of an
    exponential of mean ``1 / rate``, rescaled to sum to ``k / rate``; the
    schedule is round after round, each in an order of its own drawn from the
    seed (not the lengths' order).  So each round of ``k`` arrivals takes
    exactly ``k / rate`` seconds and holds every gap once, whatever the seed:
    short gaps follow each other (bursts), and every seed offers a window the
    same load."""
    k = tr["grid"][0] * tr["grid"][1]
    rate = float(tr["arrival_rate_per_s"])
    gaps = -np.log(1.0 - (np.arange(k) + 0.5) / k) / rate
    gaps *= (k / rate) / gaps.sum()
    r = np.random.default_rng([int(seed), 0x6172726976616C])
    rounds = [gaps[r.permutation(k)] for _ in range(-(-n // k))]
    return np.concatenate(rounds)[:n]


class Sent:
    """One submission: its time on the schedule, when ``submit()`` was
    called, and what came of it."""

    __slots__ = ("due", "t0", "prompt", "max_tokens", "t_done", "row",
                 "error")

    def __init__(self, due, t0, prompt, max_tokens):
        self.due, self.t0 = due, t0
        self.prompt, self.max_tokens = prompt, max_tokens
        self.t_done = self.row = self.error = None


class Arrivals(threading.Thread):
    """Submits request i at ``t_start + sum(gaps[:i + 1])`` and hands it to a
    waiter; a submission that is behind its time is made at once."""

    def __init__(self, engine, requests, gaps, t_start, stop, handoff):
        super().__init__(daemon=True)
        self.engine, self.requests = engine, requests
        self.due = t_start + np.cumsum(gaps)
        self.stop_flag, self.handoff = stop, handoff
        self.sent = []

    def run(self):
        for (prompt, max_tokens), due in zip(self.requests, self.due):
            wait = due - time.perf_counter()
            if wait > 0 and self.stop_flag.wait(wait):
                return
            if self.stop_flag.is_set():
                return
            s = Sent(float(due), time.perf_counter(), prompt, max_tokens)
            self.sent.append(s)
            try:
                self.handoff.put((s, self.engine.submit(prompt, max_tokens)))
            except Exception as e:  # noqa: BLE001 -- counted, not hidden
                s.t_done, s.error = time.perf_counter(), repr(e)


def _waiter(handoff):
    """One caller waiting for its reply; then the next submitted request."""
    while True:
        item = handoff.get()
        if item is None:
            return
        s, req = item
        try:
            row = np.asarray(req.result(600))
            s.t_done, s.row = time.perf_counter(), row
        except Exception as e:  # noqa: BLE001 -- counted, not hidden
            s.t_done, s.error = time.perf_counter(), repr(e)


def _mark(events, name: str):
    """The tracer's time of the instant event ``name`` (microseconds)."""
    return next((ev["ts"] for ev in events
                 if ev.get("ph") == "i" and ev.get("name") == name), None)


def drive(run):
    import jax
    from bigdl_tpu.serve import DecodeEngine
    from bigdl_tpu.utils import telemetry

    cm, cfg, tr = run.cell.cfg_mod, run.cfg, run.traffic
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(run.seed))
    model.attach(params, state)
    del params, state
    rate = float(tr["arrival_rate_per_s"])
    ramp, tail_wait = float(tr["ramp_seconds"]), float(tr["tail_wait_seconds"])
    grid = tr["grid"][0] * tr["grid"][1]
    # arrivals from the ramp's start to the latest the tail can end
    rounds = math.ceil(rate * (ramp + run.seconds + tail_wait) / grid) + 1
    requests = base.make_requests(cfg, dict(tr, rounds=rounds), run.seed)
    gaps = arrival_gaps(tr, run.seed, len(requests))
    tracer = None
    if run.trace:
        # the program's own spans, kept in memory (never flushed)
        tracer = telemetry.Tracer(harness.TRACE_DIR, flush_every=0,
                                  ring=1 << 20)
        telemetry.set_active(tracer)
    engine = DecodeEngine(model, slots=tr["slots"], page=tr["page"],
                          max_len=tr["max_len"],
                          queue_limit=tr["queue_limit"])
    engine.start()
    stop = threading.Event()
    handoff = queue.SimpleQueue()
    waiters = [threading.Thread(target=_waiter, args=(handoff,), daemon=True)
               for _ in range(tr["slots"] + tr["queue_limit"])]
    arrivals = None
    trace = harness.TraceWindow(run.cell.name) if run.trace else None
    try:
        # one request for each prefill bucket the traffic reaches, no other
        r = np.random.default_rng(run.seed + 1)
        longest = {}
        for prompt, _o in requests[:grid]:
            b = base._bucket(len(prompt))
            longest[b] = max(longest.get(b, 0), len(prompt))
        for b in sorted(longest):
            engine.submit(r.integers(0, cfg["vocab_size"], longest[b])
                          .astype(np.int32), 2).result(1200)
        run.say("warm", compile_s=round(run.compiles.seconds(), 2),
                setup_s=round(time.perf_counter() - run.t0, 2))
        for w in waiters:
            w.start()
        t_start = time.perf_counter() + 0.05
        arrivals = Arrivals(engine, requests, gaps, t_start, stop, handoff)
        arrivals.start()
        time.sleep(max(0.0, t_start + ramp - time.perf_counter()))
        t_open = time.perf_counter()
        telemetry.instant(OPEN_MARK)
        c_open = base._counts(engine)
        c_trace = None
        if trace is not None:
            time.sleep(min(2.0, run.seconds / 4))
            trace.start()
            c_a = base._counts(engine)
            time.sleep(min(float(tr["trace_seconds"]), run.seconds / 2))
            c_b = base._counts(engine)
            trace.stop()
            c_trace = {k: c_b[k] - c_a[k] for k in c_a}
            c_trace["seconds"] = trace.t_stop - trace.t_start
        time.sleep(max(0.0, t_open + run.seconds - time.perf_counter()))
        t_close = time.perf_counter()
        telemetry.instant(CLOSE_MARK)
        c_close = base._counts(engine)
        # the tail is of every request submitted inside the window: arrivals
        # go on, so the load stays as it was, until the last of those is
        # answered; none of this counts in the rate
        give_up = t_close + tail_wait
        while time.perf_counter() < give_up:
            time.sleep(0.05)
            if all(s.t_done is not None for s in list(arrivals.sent)
                   if t_open <= s.t0 < t_close):
                break
        t_tail = time.perf_counter()
    finally:
        stop.set()
        if trace is not None and trace.active:
            trace.stop()
        if arrivals is not None:
            arrivals.join(60)
        engine.stop(drain=False)
        if tracer is not None:
            telemetry.set_active(None)
        for w in waiters:
            handoff.put(None)
        for w in waiters:
            if w.ident is not None:
                w.join(60)
    peak = harness.memory_peak_bytes(run)
    threads = waiters + [arrivals]
    left = sum(1 for t in threads if t.is_alive())
    sent = arrivals.sent
    done = [(s.t0, s.t_done, s.prompt, s.max_tokens, s.row) for s in sent
            if s.row is not None]
    errors = [(s.t0, s.t_done, s.error) for s in sent if s.error is not None]
    in_window = [s for s in sent if t_open <= s.t0 < t_close]
    late_s = float(tr["late_submit_ms"]) / 1e3
    spans = window = None
    if tracer is not None:
        spans = tracer.events_tail(1 << 20)
        window = (_mark(spans, OPEN_MARK), _mark(spans, CLOSE_MARK))
    del engine
    model.params = model.state = model.grads = None
    gc.collect()
    return {"t_open": t_open, "t_close": t_close, "t_tail": t_tail,
            "done": done, "errors": errors, "threads_left": left,
            "trace": trace, "spans": spans, "window": window,
            "counts": {k: c_close[k] - c_open[k] for k in c_open},
            "trace_counts": c_trace, "memory_peak_bytes": peak,
            "window_submits": len(in_window),
            "late_submits": sum(1 for s in in_window
                                if s.t0 - s.due > late_s),
            "latest_submit_ms": max((s.t0 - s.due for s in in_window),
                                    default=0.0) * 1e3,
            "rejected": sum(1 for s in sent if s.error is not None
                            and "ServerOverloaded" in s.error)}


def decide(run, seen) -> None:
    base.decide(run, seen)
    run.say("arrivals", rate_per_s=run.traffic["arrival_rate_per_s"],
            window_submits=seen["window_submits"],
            latest_submit_ms=seen["latest_submit_ms"])
    run.check("late_submits", seen["late_submits"],
              0.01 * seen["window_submits"])
    run.check("rejected", seen["rejected"], 0)


def run(run) -> dict:
    seen = drive(run)
    e2e = base.window_metrics(run, seen)
    e2e["setup_s"] = seen["t_open"] - run.t0
    decide(run, seen)
    trace = seen["trace"].reduce() if seen["trace"] is not None else None
    if trace:
        # the device programs of the traced window: name, runs, seconds
        run.say("programs", modules=trace.get("modules"),
                **(seen["trace_counts"] or {}))
    facts = {"trace": trace, "spans": seen["spans"],
             "window": seen["window"],
             "counts": seen["counts"], "trace_counts": seen["trace_counts"],
             "slots": run.traffic["slots"],
             "memory_peak_bytes": seen["memory_peak_bytes"],
             "cfg": run.cfg, "traffic": run.traffic, "device": run.device}
    return {"e2e": e2e, "facts": facts,
            "attempted": e2e["completed"] + e2e["failed"],
            "failed": e2e["failed"]}


# for benchmark/control.py: decode_closed's reading of this seed's sound gap
# and the control's, after a short window of this driver's
base.drive = drive
control = base.control
