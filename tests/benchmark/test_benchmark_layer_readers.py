"""The per-layer readers that read the program's spans and the names of its
device programs (ISSUE 25), each on hand-made ``facts``: the shape the
drivers pass, the profiler's two inflated iterations, a parent commit that
has neither the spans nor the names."""

import os

import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reader(name):
    return harness.load_module(
        os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def _span(name, neval, ts_ms, dur_ms):
    return {"name": name, "cat": "phase", "ph": "X", "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "pid": 0, "tid": 0,
            "args": {"neval": neval}}


def _loop_spans(summary_ms):
    """One iteration every 140 ms: 8 ms of data, 3 of prepare, ``dispatch``
    0.5 + 0.01 x neval, 100 of loss fetch, ``summary_ms[neval]`` (default 2),
    1 of triggers, 0.4 in no child."""
    out, t = [], 0.0
    for n in range(1, 13):
        disp = 0.5 + 0.01 * n
        summ = summary_ms.get(n, 2.0)
        parts = [("data", 8.0), ("prepare", 3.0), ("dispatch", disp),
                 ("loss_fetch", 100.0), ("summary", summ), ("triggers", 1.0)]
        at = t + 0.2
        for name, dur in parts:
            out.append(_span(name, n, at, dur))
            at += dur
        out.append(_span("iteration", n, t, at + 0.2 - t))
        # `step` overlaps them and is no child; the counter is no span
        out.append(_span("step", n, t + 9.0, 104.0))
        out.append({"name": "train", "ph": "C", "ts": at * 1e3, "pid": 0,
                    "tid": 0, "args": {"step_s": 0.1}})
        t += 140.0
    return out


@pytest.mark.parametrize("name,want", [
    # iteration less data and loss_fetch: 3 + dispatch + 2 + 1 + 0.4; the
    # window's steps are 3..10, the median lies between steps 6 and 7
    ("loop_host_ms.train", 3.0 + (0.5 + 0.065) + 2.0 + 1.0 + 0.4),
    ("dispatch_ms.train", 0.565),
])
def test_loop_readers_take_the_median_over_the_window(name, want):
    reader = _reader(name)
    facts = {"spans": _loop_spans({}), "window_steps": list(range(3, 11))}
    assert reader.read(facts) == pytest.approx(want)
    # the driver's hook starts and stops the profiler inside the `summary`
    # of two of the window's iterations: a second each, and the median
    # does not move by more than a step's worth of `dispatch`
    inflated = {"spans": _loop_spans({5: 1200.0, 9: 900.0}),
                "window_steps": list(range(3, 11))}
    assert reader.read(inflated) == pytest.approx(want, abs=0.011)
    # iterations outside the window are not read
    outside = {"spans": _loop_spans({1: 5000.0, 12: 5000.0}),
               "window_steps": list(range(3, 11))}
    assert reader.read(outside) == pytest.approx(want)


@pytest.mark.parametrize("name", ["loop_host_ms.train", "dispatch_ms.train"])
def test_loop_readers_find_nothing_on_a_parent_commit(name):
    """The parent's loop has `data` and `step` only."""
    reader = _reader(name)
    old = [e for e in _loop_spans({}) if e["name"] in ("data", "step", "train")]
    assert reader.read({"spans": old, "window_steps": [3, 4, 5]}) is None
    assert reader.read({"spans": _loop_spans({}), "window_steps": []}) is None
    assert reader.read({"spans": [], "window_steps": [3]}) is None
    assert reader.read({}) is None


def test_prefill_share_is_of_all_programs_by_name():
    reader = _reader("prefill_share_pct.decode")
    modules = [["jit_decode_prefill", 7.0, 0.90], ["jit_decode_step", 12.0, 0.08],
               ["jit_convert_element_type", 30.0, 0.02]]
    assert reader.read({"trace": {"modules": modules}}) == pytest.approx(90.0)
    # a parent commit: both programs are `jit_fn`
    assert reader.read({"trace": {"modules": [["jit_fn", 19.0, 0.98]]}}) is None
    assert reader.read({"trace": {"modules": []}}) is None
    assert reader.read({"trace": None}) is None
    assert reader.read({}) is None


def test_flash_forward_is_its_share_of_busy_times_the_step():
    reader = _reader("flash_fwd_ms.train")
    # as the v5e's trace names it inside the train step (PR 25)
    call = ("%jvp_flash_fwd_.{} = bf16[128,1024,64]{{2,1,0:T(8,128)(2,1)}} "
            "custom-call(bf16[128,1024,64]{{2,1,0:T(8,128)(2,1)S(1)}} "
            "%bitcast.4535, bf16[128,1024,64]{{2,1,0:T(8,128)(2,1)S(1)}} "
            '%bitcast.4559), custom_call_target="tpu_custom_call"')
    ops = [["%fusion.12 = bf16[8,1024,1024]{2,1,0} fusion(%p), kind=kLoop",
            1.0],
           [call.format(1), 0.03], [call.format(23), 0.05],
           # not the kernel: another custom call, and a fusion of its name
           ['%custom-call.4 = f32[8]{0} custom-call(%x), '
            'custom_call_target="other"', 0.5],
           ["%flash_fwd_fusion.2 = f32[8]{0} fusion(%y)", 0.5]]
    trace = {"busy_s": 4.0, "window_s": 4.1, "ops": ops,
             "modules": [["jit_step", 10.0, 4.09], ["jit__unstack", 10.0, 1e-5]]}
    # 0.08 s of 4.0 busy: 2 % of a 409 ms step
    assert reader.read({"trace": trace}) == pytest.approx(0.02 * 409.0)
    # a parent commit: the kernel has no name of its own
    unnamed = dict(trace, ops=[ops[0], [call.format(1).replace(
        "jvp_flash_fwd_.1", "custom-call.7"), 0.08]])
    assert reader.read({"trace": unnamed}) is None
    assert reader.read({"trace": dict(trace, modules=[])}) is None
    assert reader.read({"trace": None}) is None
    assert reader.read({}) is None
