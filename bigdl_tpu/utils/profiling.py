"""Per-module profiling + compiled-step tracing.

Reference: `nn/abstractnn/AbstractModule.scala:193-217` — every module
accumulates `forwardTime`/`backwardTime` inside the `forward`/`backward`
wrappers and `getTimes()` returns (module, forwardTime, backwardTime)
triples; conv layers additionally track im2col/col2im time
(SpatialConvolution.scala:108-113).

TPU-native re-design: always-on per-layer timers are impossible inside one
fused XLA program (and would defeat the fusion that makes the step fast), so
profiling splits into two tools matching the two execution modes:

1. `ModuleProfiler` — EAGER per-module wall times.  Wraps every submodule's
   `apply` on the instance tree, synchronizing on each output (a host
   fetch, as utils/timing.py does), and measures per-leaf backward via
   `jax.vjp` on the captured inputs.  `model.get_times()` then mirrors the
   reference's `getTimes()` contract.

2. `trace_steps` — the compiled path: wraps N executions of the real train
   step in a profiler session, producing a TensorBoard-loadable xplane
   trace where XLA's own per-op breakdown lives (SURVEY.md §7.6).

`profiler_session` is the one way this package starts the JAX profiler, and
`xplane_rows` reads what it wrote back as plain rows
(`telemetry.idle_by_cause`, `tools/trace_report.py --xplane`).
"""

from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Any, Dict, List, Tuple

import jax

from .timing import fetch_scalar

__all__ = ["ModuleProfiler", "trace_steps", "profiler_session",
           "xplane_rows"]


def _sync(x) -> None:
    leaves = jax.tree.leaves(x)
    if not leaves or isinstance(leaves[0], jax.core.Tracer):
        return  # under a jax trace (e.g. facade backward's vjp): no-op
    try:
        fetch_scalar(leaves[0])
    except Exception:  # noqa: BLE001 — non-array leaves
        pass


class ModuleProfiler:
    """Eager per-module wall-time profiler (AbstractModule.getTimes role).

    Usage:
        with ModuleProfiler(model) as prof:
            model.forward(x)
        for mod, fwd_s, bwd_s in prof.get_times():
            ...

    Forward times are recorded live (each submodule's apply is wrapped and
    synced).  Backward times are measured on demand from the captured
    (params, state, input) of each call via jax.vjp — the facade's whole-
    model vjp cannot attribute time to submodules, exactly like the
    reference cannot attribute MKL time across JNI calls without its
    per-layer wrappers.
    """

    def __init__(self, model, measure_backward: bool = True):
        self.model = model
        self.measure_backward = measure_backward
        self.fwd: Dict[int, float] = {}
        self.bwd: Dict[int, float] = {}
        self.calls: Dict[int, Tuple] = {}
        self._mods: List = []
        self._saved: List[Tuple] = []

    def __enter__(self):
        # identity-deduped walk: a shared module instance (weight sharing)
        # is wrapped and restored exactly once (Module.unique_modules)
        self._mods = list(self.model.unique_modules())
        for m in self._mods:
            orig = m.apply
            # remember whether apply was already an instance attribute
            # (nested profiler / custom wrapper) so __exit__ restores it
            self._saved.append((m, m.__dict__.get("apply")))

            def timed(params, state, input, *, training=False, rng=None,
                      _m=m, _orig=orig):
                leaves = jax.tree.leaves((params, input))
                if any(isinstance(l, jax.core.Tracer) for l in leaves):
                    # under a jax trace (facade backward's vjp, jit):
                    # timing is meaningless and captured tracers would leak
                    return _orig(params, state, input, training=training,
                                 rng=rng)
                t0 = time.perf_counter()
                out, ns = _orig(params, state, input, training=training,
                                rng=rng)
                _sync(out)
                key = id(_m)
                self.fwd[key] = self.fwd.get(key, 0.0) + \
                    (time.perf_counter() - t0)
                self.calls[key] = (params, state, input, training, rng)
                return out, ns

            m.apply = timed
        return self

    def __exit__(self, *exc):
        for m, prev_instance_apply in self._saved:
            if prev_instance_apply is not None:
                m.apply = prev_instance_apply  # restore outer wrapper
            else:
                # deleting the instance attr re-exposes the class method
                m.__dict__.pop("apply", None)
        self._saved = []
        if self.measure_backward and not any(exc):
            self._measure_backward()
        # publish on the model for the get_times() parity accessor
        for m in self._mods:
            m._profile_times = (self.fwd.get(id(m), 0.0),
                                self.bwd.get(id(m), 0.0))
        return False

    def _measure_backward(self):
        import jax.numpy as jnp
        for m in self._mods:
            rec = self.calls.get(id(m))
            if rec is None or getattr(m, "modules", None):
                continue  # containers: reported as sum of leaves
            params, state, input, training, rng = rec

            def f(p, x, _m=m, _s=state, _t=training, _r=rng):
                out, _ = _m.apply(p, _s, x, training=_t, rng=_r)
                return out

            try:
                out, vjp = jax.vjp(f, params, input)
                ct = jax.tree.map(lambda o: jnp.ones_like(o), out)
                t0 = time.perf_counter()
                grads = vjp(ct)
                _sync(grads)
                self.bwd[id(m)] = time.perf_counter() - t0
            except Exception:  # noqa: BLE001 — non-differentiable layers
                continue
        # containers: sum of their leaves (reference reports the wrapper
        # time, which includes children)
        for m in self._mods:
            if getattr(m, "modules", None):
                self.bwd[id(m)] = sum(
                    self.bwd.get(id(c), 0.0) for c in m.unique_modules()
                    if c is not m)

    def get_times(self) -> List[Tuple[Any, float, float]]:
        """(module, forward_seconds, backward_seconds) per submodule —
        the reference's getTimes() shape (AbstractModule.scala:197)."""
        return [(m, self.fwd.get(id(m), 0.0), self.bwd.get(id(m), 0.0))
                for m in self._mods]

    def summary(self, top: int = 20) -> str:
        rows = sorted(self.get_times(), key=lambda r: -(r[1] + r[2]))[:top]
        lines = [f"{'module':40s} {'fwd_ms':>9s} {'bwd_ms':>9s}"]
        for m, f, b in rows:
            lines.append(f"{m.name[:40]:40s} {f*1e3:9.3f} {b*1e3:9.3f}")
        return "\n".join(lines)


def trace_steps(run, n: int, logdir: str):
    """Run `run()` n times under jax.profiler.trace (SURVEY.md §7.6).

    `run` must return a device value; the last output is host-fetched so the
    trace covers real execution.  View with TensorBoard's profile plugin or
    xprof on `logdir`.
    """
    out = None
    with profiler_session(logdir):
        for _ in range(n):
            out = run()
        if out is not None:
            _sync(out)
    return logdir


@contextlib.contextmanager
def profiler_session(logdir: str):
    """A JAX profiler session that does not stall the host: the Python
    tracer off and no HLO protos (with the defaults the host stopped for a
    second at a time on a v5e and the device read 85 % idle; PERF.md, PR
    24), the host tracer at level 1, which records the
    ``TraceAnnotation`` every open telemetry span holds (``bigdl:<name>``)
    and so puts the program's spans on the device trace's clock.  The
    host tracer is not free either: a loop that copies a 154 MB batch to
    the device every step ran four times slower inside such a session
    (ResNet-50, PERF.md, PR 25), one with small batches as before."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def xplane_rows(trace_dir: str) -> list:
    """Events of the newest ``*.xplane.pb`` under ``trace_dir`` as rows
    ``[plane, line, name, start_ns, duration_ns]``: every event of the
    device planes, and of the host's planes the program's own spans
    (names that start with ``telemetry.ANNOTATION_PREFIX``).  Raises
    FileNotFoundError where no trace file is."""
    from .telemetry import ANNOTATION_PREFIX
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"{trace_dir}: no *.xplane.pb file found")
    rows = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            # a host line is one thread, and the trace names every thread
            # of a Python process alike: the line's place tells them apart
            label = line.name if device else f"{line.name}#{i}"
            for ev in line.events:
                if device or ev.name.startswith(ANNOTATION_PREFIX):
                    rows.append([plane.name, label, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows
