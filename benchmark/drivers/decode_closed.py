"""Driver ``decode_closed``: ``clients`` threads, each ``submit()`` ->
``result()`` -> next, against one ``DecodeEngine``.

Set-up builds the model with seeded weights, starts the engine, warms every
prefill bucket the traffic reaches with one request, then starts the
clients; the window opens once each client has had one request answered, so
it sees the steady state and not all arrivals at once.  After ``--seconds``
the window closes.

Tokens are those of requests completed inside the window, over its seconds.
Latencies (``submit()`` to ``result()``, time to the last token) are of
every request submitted inside it: after the close the clients go on, so
the load stays what it was, until the last of those is answered; that wait
counts in no rate.  A request that raises counts in ``failed`` and has no
latency.

``correct``: once the engine is stopped and freed, the plain reference runs
once over prompt + served tokens of a seeded sample of the finished requests
(the longest among them), and the widest gap by which a served token's
reference logit lies below the reference's best is held to its limit: the
tokens are greedy, and with random weights the top logits are near ties, so
tokens are not compared with tokens.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import harness


def _log_grid(lo: int, hi: int, n: int) -> np.ndarray:
    """n lengths spread log-uniformly over [lo, hi]: the same for every seed."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(lo * (hi / lo) ** q).astype(np.int64)


def make_requests(cfg, tr, seed: int) -> list:
    """The one list of (prompt tokens, max_tokens) that all clients draw
    from in turn, made from the seed before the window.  The lengths are one
    fixed grid: every pairing of ``grid[0]`` prompt lengths with ``grid[1]``
    output lengths, both log-uniform.  The list is round after round of the
    whole grid, each round in an order of its own drawn from the seed, so any
    run of consecutive submissions as long as the grid holds every cell once:
    every seed offers a window the same work in another order.  Tokens are
    uniform over the vocabulary."""
    r = np.random.default_rng(seed)
    plens = _log_grid(*tr["prompt_len"], tr["grid"][0])
    olens = _log_grid(*tr["output_len"], tr["grid"][1])
    cells = [(int(p), int(o)) for p in plens for o in olens]
    out = []
    for _ in range(tr["rounds"]):
        for i in r.permutation(len(cells)):
            p, o = cells[i]
            out.append((r.integers(0, cfg["vocab_size"], p)
                        .astype(np.int32), o))
    return out


def _bucket(t0: int) -> int:
    b = 8
    while b < t0:
        b *= 2
    return b


class Client(threading.Thread):
    """One caller: takes the list's next request when its last is answered."""

    def __init__(self, engine, requests, stop):
        super().__init__(daemon=True)
        self.engine, self.requests = engine, requests
        self.stop_flag, self.first_done = stop, threading.Event()
        self.waiting_since = None   # submit time of the request in flight
        self.done = []      # (t_submit, t_done, prompt, max_tokens, row)
        self.errors = []    # (t_submit, t_error, repr)

    def run(self):
        # next() on one shared iterator is atomic under the interpreter lock
        for prompt, max_tokens in self.requests:
            if self.stop_flag.is_set():
                return
            t0 = self.waiting_since = time.perf_counter()
            try:
                row = np.asarray(self.engine.submit(prompt, max_tokens)
                                 .result(600))
                self.done.append((t0, time.perf_counter(), prompt,
                                  max_tokens, row))
            except Exception as e:  # noqa: BLE001 -- counted, not hidden
                self.errors.append((t0, time.perf_counter(), repr(e)))
            self.waiting_since = None
            self.first_done.set()


def _counts(engine) -> dict:
    s = engine.stats()
    return {k: s[k] for k in ("prefill_steps", "decode_steps", "tokens_out",
                              "seqs_done", "seqs_failed")}


def drive(run):
    import jax
    from bigdl_tpu.serve import DecodeEngine

    cm, cfg, tr = run.cell.cfg_mod, run.cfg, run.traffic
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    params, state = harness.program_weights(cm, cfg, model,
                                            jax.random.key(run.seed))
    model.attach(params, state)
    del params, state
    requests = make_requests(cfg, tr, run.seed)
    engine = DecodeEngine(model, slots=tr["slots"], page=tr["page"],
                          max_len=tr["max_len"],
                          queue_limit=tr["queue_limit"])
    engine.start()
    stop = threading.Event()
    clients = []
    trace = harness.TraceWindow(run.cell.name) if run.trace else None
    try:
        # one request for each prefill bucket the traffic reaches, no other
        r = np.random.default_rng(run.seed + 1)
        longest = {}
        for prompt, _o in requests:
            b = _bucket(len(prompt))
            longest[b] = max(longest.get(b, 0), len(prompt))
        for b in sorted(longest):
            engine.submit(r.integers(0, cfg["vocab_size"], longest[b])
                          .astype(np.int32), 2).result(1200)
        run.say("warm", compile_s=round(run.compiles.seconds(), 2),
                setup_s=round(time.perf_counter() - run.t0, 2))
        shared = iter(requests)
        for _ in range(tr["clients"]):
            c = Client(engine, shared, stop)
            clients.append(c)
            c.start()
        for c in clients:
            if not c.first_done.wait(1200):
                raise RuntimeError("a client's first request was never "
                                   "answered")
        t_open = time.perf_counter()
        c_open = _counts(engine)
        c_trace = None
        if trace is not None:
            time.sleep(min(2.0, run.seconds / 4))
            trace.start()
            c_a = _counts(engine)
            time.sleep(min(float(tr["trace_seconds"]), run.seconds / 2))
            c_b = _counts(engine)
            trace.stop()
            c_trace = {k: c_b[k] - c_a[k] for k in c_a}
            c_trace["seconds"] = trace.t_stop - trace.t_start
        time.sleep(max(0.0, t_open + run.seconds - time.perf_counter()))
        t_close = time.perf_counter()
        c_close = _counts(engine)
        # the tail is of every request submitted inside the window: the
        # callers go on, so the load stays as it was, until the last of
        # those is answered; none of this counts in the rate
        give_up = t_close + float(tr["tail_wait_seconds"])
        while time.perf_counter() < give_up:
            time.sleep(0.05)
            since = [c.waiting_since for c in clients]
            if not any(t is not None and t < t_close for t in since):
                break
        t_tail = time.perf_counter()
    finally:
        stop.set()
        if trace is not None and trace.active:
            trace.stop()
        engine.stop(drain=False)
        for c in clients:
            c.join(60)
    peak = harness.memory_peak_bytes(run)
    left = sum(1 for c in clients if c.is_alive())
    done = [d for c in clients for d in c.done]
    errors = [e for c in clients for e in c.errors]
    del engine
    model.params = model.state = model.grads = None
    gc.collect()
    return {"t_open": t_open, "t_close": t_close, "t_tail": t_tail,
            "done": done,
            "errors": errors, "threads_left": left, "trace": trace,
            "counts": {k: c_close[k] - c_open[k] for k in c_open},
            "trace_counts": c_trace, "memory_peak_bytes": peak}


def window_metrics(run, seen) -> dict:
    t_open, t_close = seen["t_open"], seen["t_close"]
    length = t_close - t_open
    inside = lambda t: t_open <= t < t_close
    completed = [d for d in seen["done"] if inside(d[1])]
    tokens = sum(len(d[4]) - len(d[2]) for d in completed)
    # submitted inside the window, answered inside it or after its close
    lat_ms = [(d[1] - d[0]) * 1e3 for d in seen["done"] if inside(d[0])]
    failed = [e for e in seen["errors"] if inside(e[0]) or inside(e[1])]
    submitted = len(lat_ms) + sum(1 for e in failed if inside(e[0]))
    # (one the wait after the close gave up on fails when the engine stops)
    run.say("window", seconds=length, completed=len(completed),
            failed=len(failed), tokens=tokens, submitted=submitted,
            p95_samples=len(lat_ms),
            tail_wait_s=seen["t_tail"] - t_close,
            latency_ms_median=harness.quantile(lat_ms, 0.5) if lat_ms else None,
            errors=[e[2] for e in failed[:3]], **seen["counts"])
    return {"decode_tokens_per_s": tokens / length,
            "request_p95_ms": harness.quantile(lat_ms, 0.95) if lat_ms
            else None,
            "completed": len(completed),
            "failed": len(failed)}


def sample_rows(run, seen):
    """A seeded sample of the finished requests, the longest among them."""
    done = sorted(seen["done"], key=lambda d: (d[0], len(d[4])))
    n = min(int(run.traffic["sample_requests"]), len(done))
    longest = max(range(len(done)), key=lambda i: len(done[i][4]))
    r = np.random.default_rng(run.seed)
    pick = {longest} | set(r.permutation(len(done))[:n - 1].tolist())
    return [done[i] for i in sorted(pick)]


def logit_gaps(run, rows, prec: str = "f32"):
    """Reference logits over prompt + served tokens of each sampled request.
    Returns, over every generated position, the widest gap by which the
    served token's float32 logit lies below the float32 best; with ``prec``
    below f32 also the widest gap of the token that precision puts first
    (the control, which decodes nothing)."""
    import jax
    import jax.numpy as jnp
    cm, cfg = run.cell.cfg_mod, run.cfg
    width = run.traffic["prompt_len"][1] + run.traffic["output_len"][1]
    toks = np.zeros((len(rows), width), np.int32)
    for i, d in enumerate(rows):
        toks[i, :len(d[4])] = d[4]
    p0 = jax.jit(lambda k: cm.init_params(cfg, k))(jax.random.key(run.seed))
    ref = np.asarray(jax.jit(cm.logits_fn(cfg, "f32"))(p0, jnp.asarray(toks)))
    low = None if prec == "f32" else np.asarray(
        jax.jit(cm.logits_fn(cfg, prec))(p0, jnp.asarray(toks)))
    served_gap, low_gap, positions = 0.0, 0.0, 0
    for i, d in enumerate(rows):
        t0, n = len(d[2]), len(d[4])
        at = np.arange(t0 - 1, n - 1)       # logits that chose tokens t0..n-1
        best = ref[i, at].max(axis=-1)
        served_gap = max(served_gap, float(
            (best - ref[i, at, d[4][t0:n]]).max()))
        if low is not None:
            low_gap = max(low_gap, float(
                (best - ref[i, at, low[i, at].argmax(axis=-1)]).max()))
        positions += len(at)
    return served_gap, low_gap, positions


def decide(run, seen) -> None:
    lim = run.cfg["limits"]["decode"]
    rows = sample_rows(run, seen)
    t = time.perf_counter()
    gap, _low, positions = logit_gaps(run, rows)
    run.say("reference", seconds=round(time.perf_counter() - t, 2),
            requests=len(rows), served_tokens=positions)
    run.check("logit_gap", gap, lim["logit_gap"])
    run.check("wrong_row_lengths",
              sum(1 for d in seen["done"] if len(d[4]) != len(d[2]) + d[3]),
              0)
    run.check("compiles_in_window",
              run.compiles.inside(seen["t_open"], seen["t_close"]), 0)
    run.check("client_threads_left", seen["threads_left"], 0)


def run(run) -> dict:
    seen = drive(run)
    e2e = window_metrics(run, seen)
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
    this seed's sound gap and the control's on the same prompts and tokens."""
    seen = drive(run)
    rows = sample_rows(run, seen)
    gap, _low, positions = logit_gaps(run, rows)
    out = {"program": {"logit_gap": gap}, "served_tokens": positions}
    for prec in precs:
        out[prec] = {"logit_gap": logit_gaps(run, rows, prec)[1]}
    return out
