"""chip_smoke.py's phases at a tiny size on the CPU, through the same
functions the chip runs, and the script's refusals.

What only the chip can show (the device, the Pallas kernel in the compiled
step, times) is not asserted here; tests/test_chip_compile.py compiles the
kernels for a described chip and the chip run itself is `python
chip_smoke.py` through the builder's tool.
"""

import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: the ImageNet graph needs its 224 pixels; everything else shrinks
TINY = chip_smoke.Sizes(
    resnet_depth=18, classes=10, image=224, batch=8, train_iters=4,
    serve_buckets=(2, 4), serve_waves=(1, 2, 3),
    vocab=128, max_len=32, d_model=32, heads=2, layers=2, lm_batch=8,
    lm_iters=30, lm_alphabet=16, decode_slots=2, decode_page=16,
    prompt_lens=(3, 5, 9), gen_tokens=4, matmul_n=128)


@pytest.fixture
def fresh_policy():
    from bigdl_tpu.common import get_policy, set_policy
    prior = get_policy()
    yield
    set_policy(prior)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_train_then_serve_tiny(fresh_policy, capsys):
    from bigdl_tpu import Engine
    Engine.init(devices=[jax.devices()[0]])
    model, x = chip_smoke.train_phase(TINY, seed=0)
    chip_smoke.serve_phase(TINY, model, x)
    obs = {o["obs"]: o for o in _lines(capsys)}
    assert obs["train"]["iterations"] == 4
    assert obs["train"]["last_loss"] < obs["train"]["first_loss"]
    assert [w["bucket_rows"] for w in obs["serve"]["waves"]] == [2, 2, 4]
    assert obs["serve"]["compile_s_after_warmup"] == 0


def test_lm_tiny_with_the_kernel_interpreted(fresh_policy, capsys,
                                            monkeypatch):
    """The LM phase with the Pallas flash kernel in the step, interpreted:
    the route a TPU takes by default, steered here by the test."""
    import bigdl_tpu.ops.attention as att
    from bigdl_tpu import Engine
    monkeypatch.setattr(att, "flash_attention", functools.partial(
        att.flash_attention, use_pallas=True, interpret=True))
    Engine.init(devices=[jax.devices()[0]])
    chip_smoke.lm_phase(TINY, seed=0, expect_kernel=False)
    obs = {o["obs"]: o for o in _lines(capsys)}
    assert obs["lm.train"]["last_loss"] < 1.0 < obs["lm.train"]["first_loss"]
    assert obs["lm.decode"]["matches_oracle"]
    assert obs["lm.decode"]["tokens_out"] == 3 * TINY.gen_tokens


def test_data_parallel_tiny_on_virtual_devices(fresh_policy, capsys):
    chip_smoke.data_parallel_phase(TINY, seed=0)
    obs = {o["obs"]: o for o in _lines(capsys)}
    n = len(jax.devices())
    assert obs["data_parallel.mesh"]["batch_on_devices"] == list(range(n))
    assert obs["data_parallel.mesh"]["collectives"]["all-reduce"] >= 1
    assert obs["data_parallel.one"]["devices"] == 1
    assert obs["data_parallel"]["first_loss_gap"] <= 0.1


def test_sync_phase_tiny(capsys):
    """What the phase returns and prints.  Which of the three times is the
    smallest is a statement about a chip (the enqueue returns before the
    device ends): on a loaded CPU a 128^3 matmul orders them any way."""
    out = chip_smoke.sync_phase(TINY)
    assert set(out) == {"block", "fetch", "enqueue"}
    assert all(v > 0 for v in out.values())
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"obs": "sync"')]
    assert set(line) == {"obs", "t", "matmul_n", "block_until_ready_s",
                         "host_fetch_s", "enqueue_only_s",
                         "tflops_block_until_ready", "tflops_host_fetch"}
    assert line["matmul_n"] == TINY.matmul_n
    assert (line["block_until_ready_s"], line["host_fetch_s"],
            line["enqueue_only_s"]) == (out["block"], out["fetch"],
                                        out["enqueue"])


@pytest.mark.parametrize("text,ok", [
    ("x = tpu_custom_call(a)\ny = tpu_custom_call(b)\n", True),
    ("x = tpu_custom_call(a)\n", False),
    ("fusion(a, b)", False),
], ids=["one_per_layer", "too_few", "none"])
def test_require_flash_kernel(text, ok):
    if ok:
        assert chip_smoke.require_flash_kernel(text, layers=2) == 2
    else:
        with pytest.raises(RuntimeError, match="tpu_custom_call"):
            chip_smoke.require_flash_kernel(text, layers=2)


@pytest.mark.parametrize("info,chips,ok", [
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, True),
    ({"platform": "cpu", "kind": "cpu", "count": 1}, 1, False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 1, False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4, False),
], ids=["tpu", "cpu", "too_many", "too_few"])
def test_require_tpu(info, chips, ok):
    if ok:
        chip_smoke.require_tpu(info, chips)
    else:
        with pytest.raises(RuntimeError):
            chip_smoke.require_tpu(info, chips)


def test_script_refuses_ok_when_platform_is_not_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {"ok": False}
    assert "needs a TPU" in r.stderr


def test_a_phase_that_raises_fails_the_run(monkeypatch, capsys):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_phase", lambda chips: tpu)

    def boom(sz):
        raise ValueError("phase fell over")

    monkeypatch.setattr(chip_smoke, "sync_phase", boom)
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == {"ok": False}
    assert '"ok": true' not in out and "phase fell over" in err


def test_importing_the_package_initialises_no_backend():
    """What lets a launcher's parent stay off the chip: importing bigdl_tpu
    and every sub-package touches no JAX backend."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import bigdl_tpu
        import bigdl_tpu.nn, bigdl_tpu.optim, bigdl_tpu.serve
        import bigdl_tpu.models, bigdl_tpu.dataset, bigdl_tpu.parallel
        import bigdl_tpu.ops, bigdl_tpu.utils, bigdl_tpu.tools
        import bigdl_tpu.interop, bigdl_tpu.visualization
        import chip_smoke
        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized(), \\
            sorted(xla_bridge._backends)
        print("clean")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().endswith("clean")
