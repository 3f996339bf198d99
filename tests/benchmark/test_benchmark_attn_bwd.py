"""`attn_bwd_ms.train` (ISSUE 46): the reader of the flash-attention
backward's kernels on hand-made ``facts``, as the v5e's trace names them in
the train step, and its entry in BENCHMARK.json.  (The other readers' cases
are in test_benchmark_layer_readers.py, which a PR that adds a metric leaves
as it is.)"""

import json
import os

import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "attn_bwd_ms.train"


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics", NAME + ".py"),
        "reader_attn_bwd_ms_train")


# the compiled step's own lines (tests/test_chip_compile.py compiles them)
DKV = ("%transpose_jvp_flash_bwd_dkv__.{} = (bf16[128,1024,64]{{2,1,0:T(8,128)"
       "(2,1)}}, bf16[128,1024,64]{{2,1,0:T(8,128)(2,1)}}) custom-call("
       "%bitcast.8, %bitcast.11, %bitcast.14, %broadcast.2, %pallas_call.9, "
       '%reshape.16), custom_call_target="tpu_custom_call"')
DQ = ("%transpose_jvp_flash_bwd_dq__.{} = bf16[128,1024,64]{{2,1,0:T(8,128)"
      "(2,1)}} custom-call(%bitcast.7, %bitcast.10, %bitcast.13, "
      '%broadcast.2, %pallas_call.9, %reshape.16), '
      'custom_call_target="tpu_custom_call"')
# as XLA wrapped `selective_scan` round its neighbour in PR 45
WRAPPED = ("%transpose_jvp_flash_bwd_dq__.{} = (bf16[128,1024,64]{{2,1,0}}, "
           "f32[8,16,1024]{{2,1,0}}) fusion(%bitcast.7, %bitcast.10), "
           "kind=kCustom, calls=%fused_computation.3")
OTHERS = [
    ["%fusion.12 = bf16[8,1024,1024]{2,1,0} fusion(%p), kind=kLoop", 1.0],
    # the forward kernel, another custom call, and fusions that only carry
    # the name: a loop fusion, and an operand named after the kernel
    ['%jvp_flash_fwd_.1 = (bf16[128,1024,64]{2,1,0}, f32[128,1,1024]{2,1,0}) '
     'custom-call(%bitcast.6), custom_call_target="tpu_custom_call"', 0.3],
    ['%custom-call.4 = f32[8]{0} custom-call(%x), '
     'custom_call_target="other"', 0.5],
    ["%flash_bwd_fusion.2 = f32[8]{0} fusion(%y), kind=kLoop", 0.5],
    ["%fusion.9 = f32[8]{0} fusion(%transpose_jvp_flash_bwd_dq__.1), "
     "kind=kCustom", 0.5],
]


def _trace(ops):
    return {"busy_s": 4.0, "window_s": 4.1, "ops": OTHERS + ops,
            "modules": [["jit_step", 10.0, 2.5], ["jit__unstack", 10.0, 1e-5]]}


@pytest.mark.parametrize("ops,kernel_s", [
    ([[DKV.format(1), 0.05], [DQ.format(1), 0.03],
      [DKV.format(24), 0.07], [DQ.format(24), 0.05]], 0.2),
    ([[WRAPPED.format(3), 0.12], [DKV.format(3), 0.2]], 0.32),
    ([[DQ.format(2), 0.04]], 0.04),
], ids=["custom-calls", "a-wrapped-kernel", "one-kernel"])
def test_backward_is_its_share_of_busy_times_the_step(reader, ops, kernel_s):
    # kernel_s of 4.0 busy seconds, of a 250 ms step
    assert reader.read({"trace": _trace(ops)}) == pytest.approx(
        kernel_s / 4.0 * 250.0)


@pytest.mark.parametrize("facts", [
    # the parent commit: a `jnp` scan, no operation of the name
    {"trace": _trace([])},
    {"trace": _trace([[DQ.format(1).replace("flash_bwd_dq", "custom-call"),
                       0.08]])},
    {"trace": dict(_trace([[DQ.format(1), 0.08]]), modules=[])},
    {"trace": dict(_trace([[DQ.format(1), 0.08]]), busy_s=0.0)},
    {"trace": None},
    {},
], ids=["no-kernel", "unnamed", "no-modules", "no-busy", "no-trace", "empty"])
def test_backward_reader_finds_nothing_and_does_not_raise(reader, facts):
    assert reader.read(facts) is None


def test_the_entry_is_appended_for_the_cell_that_trains_attention(reader):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": reader.UNIT, "better": "lower",
                     "source": "device_trace", "layer": reader.LAYER,
                     "moves": reader.MOVES, "workloads": ["gpt2m.train"]}
    fwd = {m["name"]: m for m in bench["per_layer"]}["flash_fwd_ms.train"]
    assert (entry["layer"], entry["moves"]) == (fwd["layer"], fwd["moves"])
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("ssm_state_roofline_pct.decode")
