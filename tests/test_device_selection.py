"""Device selection says what it found and never makes a device up.

`utils/flops.device_peak_flops` (the MFU denominator), the Pallas routes'
interpret flag, and bench.py's exit code when a requested config fails.
"""

import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", (197e12, "table")),
    ("tpu", "TPU v5p", (459e12, "table")),
    ("tpu", "TPU v4", (275e12, "table")),
    ("cpu", "cpu", (1e12, "nominal")),
    ("tpu", "TPU v9 turbo", ValueError),
], ids=["v5e", "v5p", "v4", "cpu", "unknown_tpu"])
def test_device_peak_flops(monkeypatch, platform, kind, want):
    """A TPU that is not in the peaks table is an error, not the CPU's
    nominal 1e12 under a utilisation's name."""
    from bigdl_tpu.utils.flops import device_peak_flops
    monkeypatch.delenv("BIGDL_TPU_PEAK_FLOPS", raising=False)
    if want is ValueError:
        with pytest.raises(ValueError, match="TPU v9 turbo"):
            device_peak_flops(_device(platform, kind))
    else:
        assert device_peak_flops(_device(platform, kind)) == want


def test_unknown_tpu_is_not_swallowed_by_the_mfu_counter(monkeypatch):
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu import Engine
    from bigdl_tpu.optim import Optimizer
    Engine.init(devices=[jax.devices()[0]])
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_device("tpu", "TPU v9 turbo")])
    opt = Optimizer(nn.Linear(2, 2), None, nn.MSECriterion())
    with pytest.raises(ValueError, match="no bf16 peak known"):
        opt._arm_mfu(lambda *a: None, (), Engine.mesh())


@pytest.mark.parametrize("where", ["bn", "convbn"])
def test_interpret_is_never_true_on_a_tpu(monkeypatch, where):
    """BN_IMPL=pallas_interpret is for CPU tests; on platform `tpu` the
    place that computes `interpret` refuses it."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn import fused
    monkeypatch.setenv("BIGDL_TPU_BN_IMPL", "pallas_interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(AssertionError, match="pallas_interpret on a TPU"):
        if where == "convbn":
            fused._engagement(True, 8)
        else:
            bn = nn.SpatialBatchNormalization(4).build()
            bn._route_pallas(bn.params, bn.state,
                             jnp.zeros((2, 3, 3, 4)), (0, 1, 2),
                             "pallas_interpret")


@pytest.mark.parametrize("bad,rc", [(["lenet"], 1), ([], 0)],
                         ids=["one_config_errors", "all_ok"])
def test_bench_exit_code_names_a_failed_config(bad, rc):
    """A requested config that errors is recorded under config_errors, the
    line still lands, and the exit code is no longer 0."""
    code = textwrap.dedent(f"""
        import os, sys
        sys.argv = ["bench.py"]
        import bench
        BAD = {bad!r}
        def fake(name, build, peak):
            if name in BAD:
                raise RuntimeError("boom in " + name)
            return {{"name": name, "images_per_sec": 1.0, "mode": "train",
                    "mfu": None, "model_flops_per_step": 1.0}}
        bench._bench_config = fake
        rc = bench.main(["--configs", "lenet", "textcnn", "--no-scaling",
                         "--platform", "cpu"])
        sys.stdout.flush()
        os._exit(rc)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=180, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert r.returncode == rc, r.stderr[-1500:]
    assert len(lines) == 1 and "textcnn" in lines[0]["configs"]
    assert ("lenet" in lines[0].get("config_errors", {})) == bool(bad)
