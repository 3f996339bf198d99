"""Device timing for the tools that time a step by hand.

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
sync discipline.  Used by `bigdl_tpu/tools/{perf,scaling}.py`
and `utils/profiling.py`; the benchmark (`benchmark/`) has its own clock.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["fetch_scalar", "measure_step_seconds"]


def fetch_scalar(x) -> float:
    """Force completion of everything `x` depends on via a host byte fetch."""
    while isinstance(x, (list, tuple)):
        x = x[0]
    flat = x.ravel() if getattr(x, "ndim", 0) else x
    return float(np.asarray(flat[0] if getattr(flat, "ndim", 0) else flat))


def _beat() -> None:
    """One measurement heartbeat to the process-default supervisor
    (utils/supervisor.notify): a tool under supervision gets stall
    coverage with no handle threading."""
    from . import supervisor
    supervisor.notify()


def _measure_chain(run, n1, n2, reps):
    """Differenced chained timing of `run()` (must return a device value that
    depends on all prior `run()` calls, e.g. the loss of a step that threads
    its params).  Returns (seconds_per_run, details dict)."""
    fetch_scalar(run())  # drain queue + any lazy backend state
    _beat()
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
            _beat()
        times[n] = best
    dt = (times[n2] - times[n1]) / (n2 - n1)
    overhead = max(times[n1] - n1 * dt, 0.0)
    return dt, {"n1": n1, "n2": n2, "t_n1": round(times[n1], 6),
                "t_n2": round(times[n2], 6),
                "fixed_overhead_seconds": round(overhead, 6)}


def _measure_sync(run, iters=6) -> float:
    """Median per-call timing with a host fetch per call (upper-bounds the
    true step time by one dispatch plus one device-to-host fetch)."""
    fetch_scalar(run())
    _beat()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fetch_scalar(run())
        ts.append(time.perf_counter() - t0)
        _beat()
    ts.sort()
    return ts[len(ts) // 2]


def measure_step_seconds(run, n1=4, n2=16, reps=3):
    """Best-effort step time: differenced chain, falling back to the synced
    median when the differencing is inconsistent (noise/backlog), which the
    details dict then says (`fallback`)."""
    dt, detail = _measure_chain(run, n1, n2, reps)
    dt_sync = _measure_sync(run)
    detail["step_seconds_sync"] = round(dt_sync, 6)
    if dt <= 0 or dt > dt_sync * 1.5:
        detail["fallback"] = "sync"
        dt = dt_sync
    return dt, detail
