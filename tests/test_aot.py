"""AOT executable cache (bigdl_tpu/utils/aot.py — ISSUE 6 tentpole).

Covers: fingerprint keying (shape / dtype / mesh / jax-version change =>
miss), executable round-trip through the CRC-framed store, corrupted-entry
quarantine => silent recompile, bit-identical loss sequence with the cache
on vs off on the 5-step LeNet run, serve warmup from a populated cache
performing zero fresh lowers, composition with the XLA persistent cache,
and the cross-process acceptance run (second process: warmup + 2-step
train with zero fresh compiles, proven by the aot counters in the emitted
trace)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.common import set_seed
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import Adam, Optimizer, Trigger
from bigdl_tpu.utils import aot
from bigdl_tpu.utils.engine import Engine

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def aot_cache(tmp_path, monkeypatch):
    """A fresh cache dir armed via the env knob, counters zeroed, and the
    singleton dropped again afterwards (the tmp dir dies with the test)."""
    d = str(tmp_path / "aot")
    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", d)
    aot.reset()
    yield d
    aot.reset()


def _mnist_samples(n=160, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(0.0, 0.1, size=(n, 28, 28, 1)).astype(np.float32)
    ys = rng.integers(0, 10, size=n)
    return [Sample(xs[i], np.int32(ys[i])) for i in range(n)]


class _LossCapture:
    def __init__(self):
        self.losses = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(value)


def _train_lenet(samples, steps=5):
    from bigdl_tpu.models import LeNet5
    set_seed(7)
    model = LeNet5(10)
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(32, drop_last=True))
    cap = _LossCapture()
    opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(cap))
    opt.optimize()
    return cap.losses, [np.asarray(l) for l in jax.tree.leaves(model.params)]


# ----------------------------------------------------------------------
# fingerprint keying
# ----------------------------------------------------------------------

def test_fingerprint_sensitivity():
    """Every field the ISSUE names — avals (shape/dtype), mesh, jax
    version — flips the key; identical fields agree."""
    Engine.init()
    base = aot.base_fingerprint(Engine.mesh())
    f = dict(base)
    f["args"] = aot.aval_fingerprint(jnp.ones((8, 4)))
    k0 = aot.fingerprint(f)
    assert k0 == aot.fingerprint(dict(f))  # deterministic

    shp = dict(f, args=aot.aval_fingerprint(jnp.ones((16, 4))))
    dt = dict(f, args=aot.aval_fingerprint(jnp.ones((8, 4), jnp.bfloat16)))
    ver = dict(f, jax="99.99.0")
    mesh = dict(f, mesh={"shape": {"data": 4}, "axes": ["data"]})
    keys = {k0, aot.fingerprint(shp), aot.fingerprint(dt),
            aot.fingerprint(ver), aot.fingerprint(mesh)}
    assert len(keys) == 5  # all distinct


def test_module_fingerprint_structural():
    """Same architecture (fresh instances, different uids and weights) =>
    same fingerprint; different architecture or config => different.  No
    tracing happens — this is the zero-fresh-lowers key for serving."""
    from bigdl_tpu.models import LeNet5
    a = aot.module_fingerprint(LeNet5(10))
    b = aot.module_fingerprint(LeNet5(10))
    c = aot.module_fingerprint(LeNet5(12))  # class-count config change
    d = aot.module_fingerprint(nn.Sequential().add(nn.Linear(4, 2)))
    assert a == b
    assert len({a, c, d}) == 3


# ----------------------------------------------------------------------
# store / load / quarantine
# ----------------------------------------------------------------------

def test_roundtrip_hit_and_identical_result(aot_cache):
    Engine.init()

    def f(x):
        return jnp.tanh(x @ x.T) * 2 + 1

    x = jnp.ones((33, 7))
    lowered = jax.jit(f).lower(x)
    cold = aot.cached_compile(lowered, label="t.roundtrip",
                              example_args=(x,))
    want = np.asarray(cold(x))
    s = aot.stats()
    assert (s["misses"], s["stores"], s["hits"]) == (1, 1, 0)

    jax.clear_caches()
    warm = aot.cached_compile(jax.jit(f).lower(x), label="t.roundtrip",
                              example_args=(x,))
    s = aot.stats()
    assert s["hits"] == 1 and s["compiles"] == 1  # no second compile
    np.testing.assert_array_equal(np.asarray(warm(x)), want)


def test_corrupt_entry_quarantined_and_recompiled(aot_cache):
    """Bit rot in a cache entry must cost one recompile, never a crash:
    the CRC frame catches it, the entry is renamed *.corrupt, and the
    fresh compile re-stores a good entry."""
    Engine.init()

    def f(x):
        return x * 3 + 1

    x = jnp.ones((5, 5))
    aot.cached_compile(jax.jit(f).lower(x), label="t.corrupt",
                       example_args=(x,))
    cache = aot.get_cache()
    (key,) = cache.entries()
    path = cache._path(key)
    blob = open(path, "rb").read()
    with open(path, "wb") as fh:  # flip bytes mid-payload
        fh.write(blob[:100] + bytes([blob[100] ^ 0xFF]) + blob[101:])

    jax.clear_caches()
    warm = aot.cached_compile(jax.jit(f).lower(x), label="t.corrupt",
                              example_args=(x,))
    np.testing.assert_array_equal(np.asarray(warm(x)), np.asarray(f(x)))
    s = aot.stats()
    assert s["corrupt"] == 1 and s["hits"] == 0 and s["compiles"] == 2
    assert os.path.exists(path + ".corrupt")  # quarantined, not deleted
    assert key in cache.entries()  # re-stored after the recompile


def test_remote_scheme_cache_dir(monkeypatch):
    """The cache rides file_io, so a remote (fsspec) cache dir works —
    memory:// stands in for gs:// exactly as in the checkpoint tests."""
    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", "memory://aotcache")
    aot.reset()
    try:
        Engine.init()

        def f(x):
            return x * x + 1

        x = jnp.ones((6, 2))
        aot.cached_compile(jax.jit(f).lower(x), label="t.mem",
                           example_args=(x,))
        assert aot.stats()["stores"] == 1
        assert len(aot.get_cache().entries()) == 1
        jax.clear_caches()
        warm = aot.cached_compile(jax.jit(f).lower(x), label="t.mem",
                                  example_args=(x,))
        assert aot.stats()["hits"] == 1
        np.testing.assert_array_equal(np.asarray(warm(x)),
                                      np.full((6, 2), 2.0))
    finally:
        aot.reset()


def test_jax_version_change_is_miss(aot_cache, monkeypatch):
    Engine.init()

    def f(x):
        return x + 2

    x = jnp.ones((3,))
    aot.cached_compile(jax.jit(f).lower(x), label="t.ver",
                       example_args=(x,))
    jax.clear_caches()
    monkeypatch.setattr(jax, "__version__", "99.99.0")
    aot.cached_compile(jax.jit(f).lower(x), label="t.ver",
                       example_args=(x,))
    s = aot.stats()
    assert s["hits"] == 0 and s["misses"] == 2 and s["stores"] == 2


def test_disabled_is_default_and_inert(tmp_path):
    assert not aot.enabled()
    assert aot.get_cache() is None

    def f(x):
        return x - 1

    x = jnp.ones((4,))
    out = aot.cached_compile(jax.jit(f).lower(x), label="t.off",
                             example_args=(x,))(x)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4,)))
    assert not os.listdir(str(tmp_path))  # nothing written anywhere


# ----------------------------------------------------------------------
# train-step integration
# ----------------------------------------------------------------------

def test_train_bit_identical_cache_off_cold_warm(aot_cache, monkeypatch):
    """The 5-step LeNet loss sequence and final params are bit-identical
    across cache OFF, cache COLD (compile + store) and cache WARM
    (deserialized executable) — the cached program is the same XLA
    binary, so the arithmetic cannot drift."""
    Engine.init()
    samples = _mnist_samples()

    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", "")
    losses_off, params_off = _train_lenet(samples)

    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", aot_cache)
    aot.reset()
    losses_cold, params_cold = _train_lenet(samples)
    s = aot.stats()
    assert s["stores"] >= 1 and s["hits"] == 0

    jax.clear_caches()
    losses_warm, params_warm = _train_lenet(samples)
    s = aot.stats()
    assert s["hits"] >= 1
    assert s["compiles"] == s["stores"]  # the warm run compiled nothing new

    assert losses_off == losses_cold == losses_warm  # exact, not allclose
    for o, c, w in zip(params_off, params_cold, params_warm):
        np.testing.assert_array_equal(o, c)
        np.testing.assert_array_equal(o, w)


def test_composes_with_xla_persistent_cache(aot_cache, tmp_path, monkeypatch):
    """Satellite: the AOT layer composes with, not fights, the XLA
    persistent cache — with both armed, a cold run stores an AOT entry
    (its compile having gone THROUGH the XLA cache, which fills too) and
    a warm run hits the AOT layer without consulting XLA at all."""
    from bigdl_tpu.utils.platform import enable_compilation_cache
    xla_dir = str(tmp_path / "xla")
    prior = jax.config.jax_compilation_cache_dir
    # what jax does at import when the variable is set: the function then
    # sets no directory of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", xla_dir)
    jax.config.update("jax_compilation_cache_dir", xla_dir)
    monkeypatch.setenv("BIGDL_TPU_XLA_CACHE", "1")  # conftest turns it off
    try:
        Engine.init()  # arms it
        assert enable_compilation_cache() == xla_dir

        def f(x):
            return jnp.sin(x) @ jnp.cos(x).T

        x = jnp.ones((17, 9))
        aot.cached_compile(jax.jit(f).lower(x), label="t.compose",
                           example_args=(x,))
        assert aot.stats()["stores"] == 1
        assert os.listdir(xla_dir), "XLA persistent cache did not fill"
        jax.clear_caches()
        aot.cached_compile(jax.jit(f).lower(x), label="t.compose",
                           example_args=(x,))
        assert aot.stats()["hits"] == 1
        # an AOT miss that XLA serves out of ITS cache is not stored again
        # one level up: re-serialized, that executable loses its kernels
        (key,) = aot.get_cache().entries()
        os.remove(aot.get_cache()._path(key))
        jax.clear_caches()
        out = aot.cached_compile(jax.jit(f).lower(x), label="t.compose",
                                 example_args=(x,))
        assert aot.stats()["stores"] == 1 and not aot.get_cache().entries()
        np.testing.assert_allclose(np.asarray(out(x)), np.asarray(f(x)),
                                   rtol=1e-6)
    finally:
        # fully un-latch: restore the config AND drop the initialized
        # cache object, or the rest of the suite keeps writing into this
        # test's tmp dir
        jax.config.update("jax_compilation_cache_dir", prior)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()


def test_rejected_executable_is_warned_once_not_quarantined(
        aot_cache, monkeypatch, caplog):
    """An intact entry the runtime refuses to load is NOT bit rot: it
    stays on disk, is logged once at WARNING with the runtime's error,
    counts under `errors`, and the caller compiles instead."""
    import logging

    from jax.experimental import serialize_executable as se

    def f(x):
        return x * 3 + 1

    x = jnp.ones((5,))
    aot.cached_compile(jax.jit(f).lower(x), label="t.rej", example_args=(x,))
    (key,) = aot.get_cache().entries()
    path = aot.get_cache()._path(key)

    def refuse(*a, **kw):
        raise RuntimeError("runtime says no")

    monkeypatch.setattr(se, "deserialize_and_load", refuse)
    monkeypatch.setattr(aot, "_rejected_warned", False)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        for _ in range(2):
            jax.clear_caches()
            out = aot.cached_compile(jax.jit(f).lower(x), label="t.rej",
                                     example_args=(x,))
            np.testing.assert_array_equal(np.asarray(out(x)), 4.0)
    s = aot.stats()
    assert s["errors"] == 2 and s["corrupt"] == 0 and s["hits"] == 0
    assert os.path.exists(path) and not os.path.exists(path + ".corrupt")
    warned = [r for r in caplog.records if "rejected" in r.getMessage()]
    assert len(warned) == 1 and "runtime says no" in warned[0].getMessage()


def test_entry_records_its_device_assignment(aot_cache):
    """A one-device executable stored from an 8-device process loads bound
    to ITS device, not to every device of the process."""
    dev = jax.devices()[3]
    x = jax.device_put(jnp.ones((4,)), dev)
    cold = aot.cached_compile(jax.jit(lambda v: v + 1).lower(x),
                              label="t.dev", example_args=(x,))
    assert aot._device_ids(cold) == [dev.id]
    jax.clear_caches()
    warm = aot.cached_compile(jax.jit(lambda v: v + 1).lower(x),
                              label="t.dev", example_args=(x,))
    assert aot.stats()["hits"] == 1
    assert aot._device_ids(warm) == [dev.id]
    assert warm(x).devices() == {dev}


# ----------------------------------------------------------------------
# serve warmup
# ----------------------------------------------------------------------

def test_serve_warmup_from_cache_zero_fresh_lowers(aot_cache):
    """A populated cache turns the serve bucket ladder into cache reads:
    the second warmup performs ZERO fresh lowers (the forward key is the
    structural module fingerprint + avals — no tracing), zero misses,
    zero compiles; and the warm server answers correctly."""
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.serve import InferenceServer
    Engine.init()
    set_seed(5)
    ex = np.zeros((28, 28, 1), np.float32)

    s1 = InferenceServer(LeNet5(10).build(), max_batch=16, example=ex)
    s1.warmup()
    first = aot.stats()
    assert first["stores"] >= 1 and first["lowers"] >= 1

    jax.clear_caches()
    set_seed(5)
    model2 = LeNet5(10).build()  # fresh instance, same arch+weights
    s2 = InferenceServer(model2, max_batch=16, example=ex)
    s2.warmup()
    after = aot.stats()
    assert after["lowers"] == first["lowers"], "warm warmup lowered"
    assert after["misses"] == first["misses"], "warm warmup missed"
    assert after["compiles"] == first["compiles"], "warm warmup compiled"
    assert after["hits"] > first["hits"]

    with s2:
        x = np.random.default_rng(3).normal(
            size=(28, 28, 1)).astype(np.float32)
        out = s2.predict(x)
    assert out.shape == (10,)
    assert np.isfinite(out).all()


def test_server_stats_carry_aot_ledger(aot_cache):
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.serve import InferenceServer
    Engine.init()
    ex = np.zeros((28, 28, 1), np.float32)
    srv = InferenceServer(LeNet5(10).build(), max_batch=8, example=ex)
    srv.warmup()
    ledger = srv.stats()["aot"]
    assert ledger["stores"] >= 1
    assert set(ledger) == {"hits", "misses", "stores", "lowers",
                           "compiles", "corrupt"}


# ----------------------------------------------------------------------
# the cross-process acceptance run
# ----------------------------------------------------------------------

_ACCEPTANCE = textwrap.dedent("""
    import json, os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from bigdl_tpu.utils.platform import force_cpu
    force_cpu(8)
    os.environ["BIGDL_TPU_AOT_CACHE"] = {cache!r}
    os.environ["BIGDL_TPU_XLA_CACHE"] = {xla!r}
    os.environ["BIGDL_TPU_TRACE"] = {trace!r}
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import set_seed
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.optim import Adam, Optimizer, Trigger
    from bigdl_tpu.serve import InferenceServer
    from bigdl_tpu.utils import aot, telemetry
    from bigdl_tpu.utils.engine import Engine

    Engine.init()
    set_seed(1)
    tracer = telemetry.maybe_start()
    # serve bucket ladder warmup
    ex = np.zeros((28, 28, 1), np.float32)
    srv = InferenceServer(LeNet5(10).build(), max_batch=16, example=ex)
    srv.warmup()
    # 2-step train run
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(28, 28, 1)).astype(np.float32),
                      np.int32(i % 10)) for i in range(64)]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32,
                                                            drop_last=True))
    opt = (Optimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
           .set_optim_method(Adam(1e-3))
           .set_end_when(Trigger.max_iteration(2)))
    opt.optimize()
    tracer.close()
    print(json.dumps(aot.stats()))
""")


def test_second_process_warm_starts_with_zero_compiles(tmp_path):
    """ISSUE 6 acceptance: a second process pointed at a populated
    BIGDL_TPU_AOT_CACHE executes InferenceServer.warmup() AND a 2-step
    train run with zero fresh XLA compiles — verified both by the
    process's own counters and by the aot hit/miss counter track in the
    trace it emitted."""
    cache = str(tmp_path / "aot")

    def run(tag):
        trace = str(tmp_path / f"trace_{tag}")
        code = _ACCEPTANCE.format(repo=_REPO_ROOT, cache=cache, trace=trace,
                                  xla="0")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=600,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        return (json.loads(r.stdout.strip().splitlines()[-1]), trace)

    cold, _ = run("cold")
    assert cold["stores"] >= 2  # train step + at least one forward bucket
    assert cold["compiles"] >= 2

    warm, trace = run("warm")
    assert warm["compiles"] == 0, warm
    assert warm["misses"] == 0, warm
    assert warm["lowers"] == 1, warm  # ONLY the train step's hlo-key lower
    assert warm["hits"] >= cold["stores"] - 1

    # the emitted trace carries the proof too: the aot counter track's
    # final sample shows hits>0, misses==0
    events = json.load(open(os.path.join(
        trace, "trace.0.json")))["traceEvents"]
    samples = [e["args"] for e in events
               if e.get("ph") == "C" and e.get("name") == "aot"]
    assert samples, "no aot counter samples in the emitted trace"
    assert samples[-1]["misses"] == 0
    assert samples[-1]["hits"] >= 1
    assert not any(e.get("name") == "compile" for e in events
                   if e.get("ph") == "X"), "warm process compiled"


def test_engine_init_xla_cache_under_the_aot_layer(tmp_path):
    """Both caches on, as a chip process with BIGDL_TPU_AOT_CACHE gets them
    from `Engine.init()`: an AOT miss whose executable XLA read back from
    its persistent cache is NOT stored one level up (re-serialized, an
    XLA:CPU executable fails at its first call with `Function ... not
    found`), so every later process still loads only entries that came
    from a fresh compile, and runs them."""
    xla_dir = str(tmp_path / "xla")

    def run(aot_dir, tag):
        code = _ACCEPTANCE.format(
            repo=_REPO_ROOT, cache=str(tmp_path / aot_dir),
            trace=str(tmp_path / f"trace_{tag}"), xla="1")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=600,
                           env={**os.environ, "JAX_PLATFORMS": "cpu",
                                "JAX_COMPILATION_CACHE_DIR": xla_dir})
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run("aot_a", "cold")             # both caches cold: fresh compiles
    assert cold["stores"] == cold["compiles"] >= 2
    assert os.listdir(xla_dir), "Engine.init() did not arm the XLA cache"
    xla_warm = run("aot_b", "xla_warm")     # AOT cold, XLA warm
    assert xla_warm["compiles"] == cold["compiles"]
    assert xla_warm["stores"] == 0, xla_warm
    again = run("aot_b", "again")           # nothing to trip over in aot_b
    assert again["hits"] == 0 and again["stores"] == 0, again
    warm = run("aot_a", "aot_warm")         # AOT warm: loads, and runs
    assert warm["compiles"] == 0 and warm["hits"] >= cold["stores"] - 1, warm


# ----------------------------------------------------------------------
# the train counter track
# ----------------------------------------------------------------------

def test_train_track_in_trace_and_report(tmp_path, monkeypatch):
    """A traced two-step run: the `train` counter track holds what the host
    observed and nothing that reads as a device's utilisation, and
    tools/trace_report.py prints it."""
    from bigdl_tpu.utils import telemetry
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv("BIGDL_TPU_TRACE", str(trace_dir))
    Engine.init()
    _train_lenet(_mnist_samples(), steps=2)

    merged = telemetry.merge_traces(str(trace_dir))
    counters = [e for e in merged["traceEvents"]
                if e.get("ph") == "C" and e.get("name") == "train"]
    assert len(counters) == 2
    for e in counters:
        assert set(e["args"]) <= {"data_wait_s", "step_s", "records_per_sec",
                                  "prefetch_queue_depth", "ran_ahead",
                                  "pipe_bubble_fraction"}
        assert e["args"]["step_s"] > 0 and e["args"]["records_per_sec"] > 0
    # the second step was called before the first one's loss was read
    assert [e["args"]["ran_ahead"] for e in counters] == [0.0, 1.0]

    r = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "trace_report.py"), str(trace_dir)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": _REPO_ROOT})
    assert r.returncode == 0, r.stderr
    assert "train.step_s" in r.stdout and "mfu" not in r.stdout
    assert "train: steps=2  ran_ahead=0.5" in r.stdout
