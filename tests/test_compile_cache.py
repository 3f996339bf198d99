"""Persistent XLA compilation cache (utils/platform.enable_compilation_cache).

Where it goes: `JAX_COMPILATION_CACHE_DIR` when set (the code then sets no
directory of its own), else one fixed path inside the checkout.  Entries must
be written there and reused across processes.  Driven in subprocesses so the
cache config lands before any compile, as in real runs.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env_extra, drop=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    for k in ("BIGDL_TPU_XLA_CACHE",) + tuple(drop):  # conftest's "0"
        env.pop(k, None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=180, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_COMPILE = f"""
    import json, os, sys, time
    sys.path.insert(0, {REPO!r})
    import jax
    from bigdl_tpu.utils.platform import enable_compilation_cache
    path = enable_compilation_cache()
    import jax.numpy as jnp
    @jax.jit
    def f(x):
        return jnp.tanh(x @ x) * 2 + 1
    float(f(jnp.ones((333, 333))).sum())
    print(json.dumps({{"path": path,
                      "config": jax.config.jax_compilation_cache_dir}}))
"""


def test_cache_written_and_reused_across_processes(tmp_path):
    cache = str(tmp_path / "xla")
    env = {"JAX_COMPILATION_CACHE_DIR": cache}
    assert _run(_COMPILE, env)["path"] == cache
    entries = os.listdir(cache)
    assert entries, "no cache entries written"
    mtimes = {e: os.path.getmtime(os.path.join(cache, e)) for e in entries}
    _run(_COMPILE, env)  # second process: must REUSE, not rewrite, the entry
    # Only the "-cache" payload files hold the compiled executable; the
    # "-atime" bookkeeping sidecar is REWRITTEN on every hit by design —
    # asserting on it would fail exactly when the cache works.
    payload = [e for e in os.listdir(cache)
               if e.startswith("jit_f") and e.endswith("-cache")]
    assert payload
    for e in payload:
        assert os.path.getmtime(os.path.join(cache, e)) == mtimes.get(e), \
            "jit_f cache entry rewritten on warm run"


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env_set", "env_unset"])
def test_where_the_cache_goes(tmp_path, env_set):
    """env set -> jax keeps the directory it read from the environment and
    the code sets none; env unset -> the one fixed path in the checkout."""
    code = f"""
        import json, sys
        sys.path.insert(0, {REPO!r})
        import jax
        calls = []
        real = jax.config.update
        def spy(name, val):
            calls.append(name)
            return real(name, val)
        jax.config.update = spy
        from bigdl_tpu.utils import platform
        path = platform.enable_compilation_cache()
        print(json.dumps({{
            "path": path, "config": jax.config.jax_compilation_cache_dir,
            "fixed": platform.CHECKOUT_CACHE_DIR,
            "set_dir": "jax_compilation_cache_dir" in calls}}))
    """
    if env_set:
        d = str(tmp_path / "from_env")
        out = _run(code, {"JAX_COMPILATION_CACHE_DIR": d})
        assert out["path"] == d and out["config"] == d
        assert not out["set_dir"], "code set a directory of its own"
    else:
        out = _run(code, {}, drop=("JAX_COMPILATION_CACHE_DIR",))
        assert out["fixed"] == os.path.join(REPO, ".jax_cache")
        assert out["path"] == out["fixed"] == out["config"]
        assert out["set_dir"]


def test_cache_disabled_by_env(monkeypatch):
    import jax
    monkeypatch.setenv("BIGDL_TPU_XLA_CACHE", "0")
    prior = jax.config.jax_compilation_cache_dir
    from bigdl_tpu.utils.platform import enable_compilation_cache
    assert enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == prior
