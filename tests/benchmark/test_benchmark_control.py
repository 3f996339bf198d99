"""``correct`` has to be able to come out false.

Two kinds of test, both at the tests' tiny sizes on the CPU, both through
the harness with its look for a chip skipped (``--rehearse``):

* the timed path broken underneath (an optimizer step that returns its state
  unchanged; a token altered where the engine samples it): the run ends and
  its last line says ``"correct": false``;
* the control (the plain reference computed one precision lower, in the
  program's place) read beside sound runs by ``benchmark/control.py``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def fresh_policy():
    from bigdl_tpu.common import get_policy, set_policy
    prior = get_policy()
    yield
    set_policy(prior)


def _last_line(capsys, workload):
    from benchmark import run as bench_run
    rc = bench_run.main(["--workload", workload, "--seed", "2147483777",
                         "--seconds", "1", "--trace", "0", "--rehearse"])
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    checks = {c["name"]: c for c in map(json.loads, out)
              if c.get("obs") == "check"}
    return rc, json.loads(out[-1]), checks


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys, fresh_policy):
    from bigdl_tpu.optim import Adam
    monkeypatch.setattr(Adam, "update",
                        lambda self, grads, params, state, lr: (params, state))
    monkeypatch.setattr(Adam, "update_fused",
                        lambda self, grads, params, state, lr,
                        constraint=None: (params, state))
    rc, line, checks = _last_line(capsys, "gpt2m.train")
    assert rc == 0 and line["correct"] is False
    # nothing moved: the whole change of the parameters is missing
    assert checks["dparam_norm_gap"]["ok"] is False
    assert checks["dparam_norm_gap"]["value"] == pytest.approx(1.0)
    assert checks["grad_sign_gap"]["ok"] is False
    # every leaf was left as it was, and the loop learnt nothing
    assert checks["leaves_unchanged"]["value"] > 0
    assert checks["last_over_first_loss"]["ok"] is False


def test_an_altered_token_is_not_correct(monkeypatch, capsys, fresh_policy):
    from bigdl_tpu.serve import DecodeEngine
    sound = DecodeEngine._sample

    def altered(self, seq, logits_row):
        return (sound(self, seq, logits_row) + 1) % len(logits_row)

    monkeypatch.setattr(DecodeEngine, "_sample", altered)
    rc, line, checks = _last_line(capsys, "gpt2m.decode")
    assert rc == 0 and line["correct"] is False
    assert checks["logit_gap"]["ok"] is False
    assert checks["wrong_row_lengths"]["ok"] is True


def test_one_leaf_that_is_never_updated_is_not_correct(monkeypatch, capsys,
                                                       fresh_policy):
    """A fault in one leaf: the classifier's weight keeps its seeded value
    while every other leaf is updated.  The median leaf sees nothing; the
    worst leaf over the weights and the count of unchanged leaves do."""
    import jax
    from bigdl_tpu.optim import SGD
    sound = SGD.update

    def frozen_head(self, grads, params, state, lr):
        new, st = sound(self, grads, params, state, lr)
        leaves, tree = jax.tree.flatten(new)
        leaves[-1] = jax.tree.leaves(params)[-1]
        return jax.tree.unflatten(tree, leaves), st

    monkeypatch.setattr(SGD, "update", frozen_head)
    monkeypatch.setattr(SGD, "update_fused",
                        lambda self, grads, params, state, lr,
                        constraint=None: frozen_head(self, grads, params,
                                                     state, lr))
    rc, line, checks = _last_line(capsys, "resnet50.train")
    assert rc == 0 and line["correct"] is False
    assert checks["dparam_norm_gap_median"]["ok"] is True
    assert checks["leaves_unchanged"]["value"] == 1
    assert checks["dparam_norm_gap_weights"]["ok"] is False
    assert checks["dparam_norm_gap_weights"]["value"] == pytest.approx(1.0)


def test_the_sound_paths_are_correct(capsys, fresh_policy):
    """The same two runs with nothing broken, so that the two tests above
    fail for the fault and for nothing else."""
    for workload in ("gpt2m.train", "gpt2m.decode", "resnet50.train"):
        rc, line, _checks = _last_line(capsys, workload)
        assert rc == 0 and line["correct"] is True, workload


def _control(workload, seeds, extra=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", BIGDL_TPU_XLA_CACHE="0")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control.py"),
         "--workload", workload, "--seeds", ",".join(map(str, seeds)),
         "--rehearse", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"seed"')]
    assert [r["seed"] for r in rows] == list(seeds)
    return rows


def _fails(numbers, limits):
    held = {k: limits.get(k, limits.get("loss_gap")
                          if k.startswith("loss_gap_") else None)
            for k in numbers}
    return [k for k, v in numbers.items()
            if held[k] is not None and v > held[k]]


@pytest.mark.parametrize("workload,config", [
    ("resnet50.train", "resnet50_imagenet"), ("gpt2m.train", "gpt2_medium")])
def test_the_control_is_not_correct_where_sound_runs_are(workload, config):
    """The reference computed with fp8 operands, put in the program's place,
    fails one of the cell's numbers on every seed; the program passes all of
    them.  (At the tests' sizes; the chip's readings at the cells' own sizes
    are in PERF.md.)"""
    cfg = json.load(open(os.path.join(REPO, "benchmark", "configs",
                                      config + ".json")))
    limits = cfg["rehearse"]["limits"]["train"]
    for row in _control(workload, (11, 12, 13)):
        assert _fails(row["program"], limits) == [], row
        assert _fails(row["fp8"], limits), row


#: the tests' tiniest LM (2 x 32) is too narrow for fp8 to show beside the
#: bfloat16 log-probabilities' own quantisation; this one is wide enough.
#: Read on the CPU over seeds 1-4 (PR 24): the program's widest gap 0.015
#: at most, the fp8 control's 0.036 at least.
MID_LM = ("n_embd=256", "n_inner=1024", "n_layer=6", "n_head=4",
          "vocab_size=4099")
MID_LM_LIMIT = 0.025


def test_the_decode_control_is_not_correct_where_sound_runs_are():
    extra = ["--seconds", "3", "--set", "output_len=[8,16]",
             "--set", "prompt_len=[4,16]"]
    for kv in MID_LM:
        extra += ["--set-cfg", kv]
    for row in _control("gpt2m.decode", (1, 2, 3), extra):
        assert row["served_tokens"] >= 30
        assert row["program"]["logit_gap"] < MID_LM_LIMIT, row
        assert row["fp8"]["logit_gap"] > MID_LM_LIMIT, row
