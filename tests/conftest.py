"""Test environment: 8 virtual CPU devices so distributed machinery is exercised
without TPU hardware — the TPU-native version of the reference's
`Engine.setNodeAndCore(4, 4)` simulate-a-cluster-in-one-JVM trick
(DistriOptimizerSpec.scala:33-41, SURVEY.md §4).

`JAX_PLATFORMS=cpu` in the environment is enough to keep the suite off an
accelerator (the driver's own test command sets it); it is set here too so a
bare `pytest tests/` does the same, and `force_cpu(8)` asks for the eight
virtual devices through jax.config before any backend is initialised.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# hermetic runs: `Engine.init()` arms JAX's persistent compile cache at a
# fixed path in the checkout, which would carry one run's executables into
# the next (tests count compiles, stores and hits).  The tests of the cache
# itself (test_compile_cache.py, test_aot.py) turn it on against a directory
# of their own.
os.environ.setdefault("BIGDL_TPU_XLA_CACHE", "0")

import jax  # noqa: E402

from bigdl_tpu.utils.platform import force_cpu  # noqa: E402

if not force_cpu(8):
    # backend already initialized — only acceptable if it is ALREADY the
    # 8-device CPU config (e.g. re-entrant collection); fail loudly instead
    # of running the suite on the wrong backend
    assert jax.default_backend() == "cpu" and jax.device_count() >= 8, (
        f"jax backend initialized before conftest: "
        f"{jax.default_backend()} x {jax.device_count()}")

import sys

# repo root on sys.path ONCE for every test module: examples/ (and any
# sibling repo content) stays importable when the suite runs against a
# pip-installed bigdl_tpu from outside the repo
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.environ.get("BIGDL_TPU_TEST_INSTALLED"):
    # packaging validation: the pip-installed wheel in site-packages must
    # win for bigdl_tpu — strip any repo-root entries (python -m pytest
    # from the repo puts one at sys.path[0]) and append instead, then
    # PROVE the import really came from outside the source tree; a silent
    # source-tree pass would validate nothing
    sys.path = [p for p in sys.path
                if os.path.abspath(p or os.getcwd()) != _REPO_ROOT]
    sys.path.append(_REPO_ROOT)
    import bigdl_tpu  # noqa: E402

    _origin = os.path.abspath(bigdl_tpu.__file__)
    # compare against the package SOURCE dir, not the whole repo root: an
    # in-repo virtualenv (repo/.venv/.../site-packages) is a legitimate
    # install location
    assert not _origin.startswith(
        os.path.join(_REPO_ROOT, "bigdl_tpu") + os.sep), (
        "BIGDL_TPU_TEST_INSTALLED=1 but bigdl_tpu resolved from the source "
        f"tree ({_origin}); install the wheel and run from outside the repo")
elif _REPO_ROOT not in sys.path:
    # dev default: the SOURCE tree must win even when some stale wheel
    # happens to be installed, or edits would go silently untested
    sys.path.insert(0, _REPO_ROOT)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_engine():
    from bigdl_tpu.utils.engine import Engine
    Engine.reset()
    yield
    Engine.reset()


def spawn_multihost_workers(worker_src: str, tmp_path, n: int = 2,
                            timeout: int = 420):
    """Run `worker_src` as n real OS processes joined via the
    BIGDL_TPU_COORDINATOR env contract; returns the last JSON line each
    worker printed.  Shared by the multi-host integration tests."""
    import json
    import os
    import socket
    import subprocess
    import sys

    worker = tmp_path / "mh_worker.py"
    worker.write_text(worker_src)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env_base = {**os.environ,
                "PYTHONPATH": _REPO_ROOT,
                "BIGDL_TPU_COORDINATOR": f"127.0.0.1:{port}",
                "BIGDL_TPU_NUM_PROCESSES": str(n)}
    procs = [subprocess.Popen(
        [sys.executable, str(worker)],
        env={**env_base, "BIGDL_TPU_PROCESS_ID": str(i)},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(n)]
    # drain pipes CONCURRENTLY: workers run distributed barriers, so a
    # sequential communicate() deadlocks if a later worker fills its 64KB
    # pipe while an earlier one waits in a collective
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=n) as pool:
        results = list(pool.map(
            lambda p: (p, *p.communicate(timeout=timeout)), procs))
    outs = []
    for p, out, err in results:
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        outs.append(json.loads(line))
    return outs
