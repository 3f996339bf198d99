"""What every cell shares: finding a cell's files by the names in
BENCHMARK.json, the run's clock and observations, the device check, the
compile clock, the profiler window, quantiles, the checks that decide
``correct`` and the one result line.

Nothing here knows a model, a traffic mix or a per-layer metric: those are
files of their own (README.md), found by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
#: profiler output of the traced run; inside the checkout, listed in .gitignore
TRACE_DIR = os.path.join(REPO_ROOT, ".bench_trace")
NOT_MEASURED = "not measured"


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (metric files have dots in
    their names, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, workload: str, bench_dir: str = BENCH_DIR,
                 benchmark_json: str = None):
        self.bench_dir = bench_dir
        root = os.path.dirname(bench_dir)
        self.bench = load_json(benchmark_json
                               or os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config_name = cfg_entry["name"]
        self.cfg = load_json(os.path.join(root, cfg_entry["file"]))
        self.cfg_mod = load_module(
            os.path.join(bench_dir, "configs", self.config_name + ".py"),
            "bench_config_" + self.config_name)
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        self.driver_mod = load_module(
            os.path.join(bench_dir, "drivers",
                         self.traffic["driver"] + ".py"),
            "bench_driver_" + self.traffic["driver"])

    def metric_names(self, kind: str) -> list:
        """Names of the ``end_to_end`` or ``per_layer`` metrics this cell
        reports: those without a ``workloads`` key, and those that list it."""
        return [m["name"] for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def units(self) -> dict:
        return {m["name"]: m["unit"]
                for k in ("end_to_end", "per_layer") for m in self.bench[k]}

    def layer_readers(self) -> dict:
        """name -> module, one for each file of layer_metrics/."""
        out = {}
        d = os.path.join(self.bench_dir, "layer_metrics")
        for fn in sorted(os.listdir(d)):
            if fn.endswith(".py"):
                mod = load_module(os.path.join(d, fn),
                                  "bench_metric_" + fn[:-3].replace(".", "_"))
                out[mod.NAME] = mod
        return out


class Run:
    """One run of one cell: arguments, clock, observations, checks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t0: float):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.rehearse, self.t0 = bool(trace), bool(rehearse), t0
        self.cfg = dict(cell.cfg)
        self.traffic = dict(cell.traffic)
        if rehearse:
            # the tests' tiny sizes, kept as data beside the real ones
            self.cfg.update(cell.cfg.get("rehearse", {}))
            self.traffic.update(cell.traffic.get("rehearse", {}))
        self.checks = []      # (name, value, limit, ok)
        self.device = None
        self.compiles = CompileClock()

    def say(self, obs: str, **fields) -> None:
        """One observation of this run, on a line of its own."""
        print(json.dumps({"obs": obs,
                          "t": round(time.perf_counter() - self.t0, 2),
                          **fields}), flush=True)

    def check(self, name: str, value, limit) -> bool:
        """One number compared beside its limit (every run prints each): it
        passes when it is at or under the limit."""
        ok = bool(value <= limit)
        self.checks.append((name, value, limit, ok))
        self.say("check", name=name, value=value, limit=limit, ok=ok)
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c[3] for c in self.checks)


class CompileClock:
    """Backend compiles of this process, with the time each ended, so a
    driver can count those that fell inside its window."""

    def __init__(self):
        self.events = []   # (perf_counter at end, seconds)
        self._armed = False

    def arm(self):
        if self._armed:
            return
        import jax.monitoring

        def on(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.perf_counter(), float(duration)))

        jax.monitoring.register_event_duration_secs_listener(on)
        self._armed = True

    def seconds(self) -> float:
        return sum(d for _t, d in self.events)

    def inside(self, t_open: float, t_close: float) -> int:
        return sum(1 for t, _d in self.events if t_open < t <= t_close)


def device_phase(run: Run) -> dict:
    """Engine.init() on what jax finds.  Anything but the cell's ``chips``
    TPU devices is an error, except in a rehearsal, which names its backend
    and measures nothing."""
    import jax
    from bigdl_tpu import Engine

    run.compiles.arm()
    mesh = Engine.init()
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    run.say("device", **info, mesh={k: int(v) for k, v in mesh.shape.items()},
            jax=jax.__version__, **compile_cache_state())
    if not run.rehearse:
        require_tpu(info, run.cell.chips)
        peaks(info["kind"])
    run.device = info
    return info


def compile_cache_state() -> dict:
    """Where the persistent compile cache is, its size cap (jax evicts the
    least recently used entries beyond it; -1 is no cap) and what it holds."""
    import jax
    d = jax.config.jax_compilation_cache_dir
    files = size = 0
    if d and os.path.isdir(d):
        for fn in os.listdir(d):
            try:
                size += os.path.getsize(os.path.join(d, fn))
                files += 1
            except OSError:
                pass
    return {"compile_cache_dir": d, "compile_cache_files": files,
            "compile_cache_bytes": size,
            "compile_cache_max_size":
                getattr(jax.config, "jax_compilation_cache_max_size", None)}


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise RuntimeError(f"the benchmark needs a TPU: jax found platform "
                           f"{info['platform']!r} ({info['kind']})")
    if info["count"] != chips:
        raise RuntimeError(f"the cell needs exactly {chips} device(s), jax "
                           f"found {info['count']}")


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise RuntimeError(f"device kind {device_kind!r} is not in "
                           "benchmark/peaks.json")
    return table[device_kind]


def program_weights(cm, cfg, model, key):
    """Seeded weights from the configuration's own scheme, made on the
    device in one jitted call and laid out as the program's tree."""
    import jax
    shapes_p, _shapes_s = jax.eval_shape(model.init, jax.random.key(0))
    treedef = jax.tree.structure(shapes_p)
    want = [(s.shape, s.dtype) for s in jax.tree.leaves(shapes_p)]

    @jax.jit
    def make(k):
        leaves = jax.tree.leaves(cm.init_params(cfg, k))
        return [x.astype(d) for x, (_s, d) in zip(leaves, want)]

    leaves = make(key)
    got = [(x.shape, x.dtype) for x in leaves]
    if got != want:
        bad = [(i, g, w) for i, (g, w) in enumerate(zip(got, want)) if g != w]
        raise RuntimeError(f"the configuration's weights do not fit the "
                           f"program's tree: {len(got)} leaves for "
                           f"{len(want)}; first mismatches {bad[:3]}")
    # running statistics and the like are the program's own initial ones
    # (its seeded parameters are not used and the compiler drops them)
    state = jax.jit(lambda k: model.init(k)[1])(key)
    return jax.tree.unflatten(treedef, leaves), state


def memory_peak_bytes(run: Run = None) -> int:
    """Peak bytes on the fullest device, as the backend reports it (0 where
    it reports nothing, as the CPU backend does): ``peak_bytes_in_use``, the
    live arrays (weights, optimizer state, caches, batches), plus
    ``peak_bytes_reserved``, what the loaded programs reserve for their
    temporaries.  The two are disjoint on the TPU backend (PR 24: a ResNet-50
    step reads 1.03 GB in use beside 9.08 GB reserved, and the largest free
    block is what the two leave of ``bytes_limit``)."""
    import jax
    best = 0
    for d in jax.devices():
        s = d.memory_stats() or {}
        if run is not None and d.id == jax.devices()[0].id:
            run.say("memory", **s)
        best = max(best, int(s.get("peak_bytes_in_use", 0) or 0)
                   + int(s.get("peak_bytes_reserved", 0) or 0))
    return best


def quantile(values, q: float) -> float:
    """The q-th quantile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("quantile of no values")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class TraceWindow:
    """A short profiler window inside a traced run.  ``start()`` and
    ``stop()`` are called by the driver from the thread that drives the
    device; ``reduced`` is trace_reduce's reduction of what was written."""

    def __init__(self, label: str):
        self.dir = os.path.join(TRACE_DIR, label)
        self.t_start = self.t_stop = None
        self.reduced = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # device events only: the Python and host tracers stall the host
        # for whole seconds, which the device would show as idle time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def reduce(self):
        from benchmark import trace_reduce
        path = trace_reduce.find_xplane(self.dir)
        self.reduced = trace_reduce.reduce_xplane(path) if path else None
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


def trace_label(name: str) -> str:
    """An operation's name as the trace gives it, cut to what fits a line:
    the trace names an XLA operation by its whole HLO text
    (``%fusion.12 = bf16[...] fusion(...), kind=kLoop, calls=...``); kept are
    its name, the first shape of its result and its kind."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:120]
    shape = rhs.lstrip("(").split("{")[0].split(" ")[0]
    kind = rhs.split("kind=")[1].split(",")[0] if "kind=" in rhs else \
        rhs.split("(")[0].split(" ")[-1]
    return f"{lhs} {shape} {kind}"[:120]


def result_line(run: Run, e2e: dict, facts: dict, attempted: int,
                failed: int) -> dict:
    """The contract's last line.  An untraced run carries the cell's
    end-to-end metrics, a traced run its per-layer metrics: each read by its
    own file of layer_metrics/, and left out where the reader finds nothing."""
    cell, units = run.cell, run.cell.units()
    metrics = {}
    if run.trace:
        wanted = set(cell.metric_names("per_layer"))
        for name, mod in cell.layer_readers().items():
            if name not in wanted:
                continue
            value = NOT_MEASURED if run.rehearse else mod.read(facts)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        for name in cell.metric_names("end_to_end"):
            if name in e2e:
                metrics[name] = {
                    "value": NOT_MEASURED if run.rehearse else e2e[name],
                    "unit": units[name]}
    device = dict(run.device)
    device["memory_peak_bytes"] = facts.get("memory_peak_bytes", 0)
    line = {"correct": run.correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    trace = facts.get("trace")
    if run.trace and trace and not run.rehearse:
        run.say("trace_kinds", kinds=trace["kinds"][:10])
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "device_ops": [[trace_label(n), s] for n, s in trace["ops"][:10]],
            "idle_gaps": [["unattributed", trace["window_s"]
                           - trace["busy_s"]]]}
    return line
