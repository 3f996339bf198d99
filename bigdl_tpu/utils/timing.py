"""Device timing for benchmarks.

JAX returns from a call as soon as the work is enqueued, so a timing has to
end in something that waits for the device.  Every helper here waits by
fetching a scalar derived from the result to the host, and the per-step
measurement DIFFERENCES two chained-run lengths, which cancels the constant
cost of that fetch and of the first dispatch:

    dt = (T(n2) - T(n1)) / (n2 - n1)

`jax.block_until_ready` waits for the device just as the fetch does: on the
v5e (chip_smoke.py's `sync` line, PR 22) an 8192^3 bf16 matmul took 6.6 ms
to block_until_ready and 7.3 ms to the fetch, against 0.2 ms to enqueue it.
The fetch is kept because the scalar is wanted anyway, not because the
other is unsafe.

Role in the reference: DistriOptimizer's per-iteration wall timing
(optim/DistriOptimizer.scala:293-297) is host-side around a synchronous Spark
job, so it never had this problem; a compiled async backend needs explicit
sync discipline.  Shared by `bench.py` and `bigdl_tpu/tools/perf.py`.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["fetch_scalar", "measure_chain", "measure_sync",
           "measure_step_seconds", "measure_roofline"]


def fetch_scalar(x) -> float:
    """Force completion of everything `x` depends on via a host byte fetch."""
    while isinstance(x, (list, tuple)):
        x = x[0]
    flat = x.ravel() if getattr(x, "ndim", 0) else x
    return float(np.asarray(flat[0] if getattr(flat, "ndim", 0) else flat))


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _progress(progress) -> None:
    """One measurement heartbeat: the caller's callback (if any) PLUS the
    process-default supervisor (utils/supervisor.notify) — benches get
    stall coverage for free, with no handle threading."""
    if progress:
        progress()
    from . import supervisor
    supervisor.notify()


def measure_chain(run, n1=4, n2=16, reps=3, progress=None):
    """Differenced chained timing of `run()` (must return a device value that
    depends on all prior `run()` calls, e.g. the loss of a step that threads
    its params).  Returns (seconds_per_run, details dict).  `progress` (no
    args, no output) is called after every rep so a caller's stall watchdog
    sees a heartbeat at least once per chain instead of one long silence;
    the active supervisor (utils/supervisor) is beaten either way."""
    fetch_scalar(run())  # drain queue + any lazy backend state
    _progress(progress)
    times = {}
    for n in (n1, n2):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = None
            for _ in range(n):
                out = run()
            fetch_scalar(out)
            best = min(best, time.perf_counter() - t0)
            _progress(progress)
        times[n] = best
    dt = (times[n2] - times[n1]) / (n2 - n1)
    overhead = max(times[n1] - n1 * dt, 0.0)
    return dt, {"n1": n1, "n2": n2, "t_n1": round(times[n1], 6),
                "t_n2": round(times[n2], 6),
                "fixed_overhead_seconds": round(overhead, 6)}


def measure_sync(run, iters=6, progress=None) -> float:
    """Median per-call timing with a host fetch per call (upper-bounds the
    true step time by one dispatch plus one device-to-host fetch).  Heartbeats like
    measure_chain: per-rep callback + active-supervisor notify."""
    fetch_scalar(run())
    _progress(progress)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fetch_scalar(run())
        ts.append(time.perf_counter() - t0)
        _progress(progress)
    ts.sort()
    return ts[len(ts) // 2]


def measure_step_seconds(run, n1=4, n2=16, reps=3, log=None, progress=None):
    """Best-effort step time: differenced chain, falling back to the synced
    median when the differencing is inconsistent (noise/backlog)."""
    dt, detail = measure_chain(run, n1=n1, n2=n2, reps=reps,
                               progress=progress)
    dt_sync = measure_sync(run, progress=progress)
    detail["step_seconds_sync"] = round(dt_sync, 6)
    if dt <= 0 or dt > dt_sync * 1.5:
        if log:
            log(f"chained dt={dt:.6f}s inconsistent with sync="
                f"{dt_sync:.6f}s; using sync timing")
        detail["fallback"] = "sync"
        dt = dt_sync
    return dt, detail


def measure_roofline(n=8192, reps=2, tolerance=1.25):
    """Measured bf16 matmul FLOP/s on the default device — the empirical
    peak used to calibrate MFU denominators.  Runs the measurement `reps`
    times; returns None (inconclusive) unless all agree within `tolerance`x,
    so a single differencing glitch cannot silently deflate every MFU."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    a = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16)
    scale = jnp.bfloat16(1.0 / (n ** 0.5))

    @partial(jax.jit, static_argnums=2)
    def chain(x, w, length):
        def body(c, _):
            return (c @ w) * scale, ()
        y, _ = jax.lax.scan(body, x, None, length=length)
        return y

    # compile both lengths before timing
    fetch_scalar(chain(a, b, 2))
    fetch_scalar(chain(a, b, 8))

    estimates = []
    for _ in range(reps):
        t2 = min(_timed(lambda: fetch_scalar(chain(a, b, 2)))
                 for _ in range(3))
        t8 = min(_timed(lambda: fetch_scalar(chain(a, b, 8)))
                 for _ in range(3))
        per_mm = (t8 - t2) / 6.0
        if per_mm <= 0:
            return None
        estimates.append(2.0 * (n ** 3) / per_mm)
    if max(estimates) > tolerance * min(estimates):
        return None  # irreproducible — refuse rather than mis-calibrate
    return sum(estimates) / len(estimates)
