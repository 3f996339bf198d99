"""Engine.init device-discovery watchdog (BIGDL_TPU_DEVICE_TIMEOUT).

jax.devices() blocks for as long as the backend takes to start — forever
when another process holds the chip, or while a multi-host runtime waits
for a peer that never comes; the opt-in time limit turns the silent hang
into an actionable TimeoutError.  Engine state is reset around every test
by conftest's autouse fixture.
"""

import time

import pytest

from bigdl_tpu.utils import engine as engine_mod
from bigdl_tpu.utils.engine import Engine


def test_transparent_on_healthy_backend(monkeypatch):
    import jax
    monkeypatch.setenv("BIGDL_TPU_DEVICE_TIMEOUT", "60")
    mesh = Engine.init()
    assert mesh.devices.size == jax.device_count()


def test_timeout_fires_on_hanging_backend(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_DEVICE_TIMEOUT", "0.2")

    class _HangingJax:
        @staticmethod
        def devices():
            time.sleep(30)
            return []

    monkeypatch.setattr(engine_mod, "jax", _HangingJax)
    t0 = time.time()
    with pytest.raises(TimeoutError, match="BIGDL_TPU_DEVICE_TIMEOUT"):
        Engine._discover_devices()
    assert time.time() - t0 < 5


def test_probe_exception_propagates(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_DEVICE_TIMEOUT", "5")

    class _FailingJax:
        @staticmethod
        def devices():
            raise RuntimeError("backend exploded")

    monkeypatch.setattr(engine_mod, "jax", _FailingJax)
    with pytest.raises(RuntimeError, match="backend exploded"):
        Engine._discover_devices()


def test_disabled_by_default(monkeypatch):
    """timeout <= 0 (the default) must not spawn a watchdog thread at all:
    multi-host init legitimately blocks until every process joins."""
    import jax
    monkeypatch.delenv("BIGDL_TPU_DEVICE_TIMEOUT", raising=False)
    devs = Engine._discover_devices()
    assert len(devs) == jax.device_count()


def test_invalid_timeout_value_raises(monkeypatch):
    """A typo'd value ('60s') must raise, not silently disable the guard —
    silent disablement reproduces exactly the hang the knob prevents."""
    monkeypatch.setenv("BIGDL_TPU_DEVICE_TIMEOUT", "60s")
    with pytest.raises(ValueError, match="not a number of seconds"):
        Engine._discover_devices()


def test_disabled_default_spawns_no_thread(monkeypatch):
    """timeout unset must take the direct path (multi-host init blocks in
    jax.devices() legitimately until all processes join — a probe thread
    there would be wrong), pinned by making Thread creation explode."""
    import threading
    import jax

    def boom(*a, **k):
        raise AssertionError("watchdog thread spawned with timeout unset")

    monkeypatch.delenv("BIGDL_TPU_DEVICE_TIMEOUT", raising=False)
    monkeypatch.setattr(threading, "Thread", boom)
    devs = Engine._discover_devices()
    assert len(devs) == jax.device_count()
