"""A prefill over a group (ISSUE 42): `[n, P]` prompts of mixed lengths into
scattered slots leave what n prefills of one row leave, in every kind of
layer that keeps decode state and in a whole model's program; a row that
fills a program up writes nothing and is counted nowhere."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.models import decode as kv
from bigdl_tpu.nn.module import prefill_rows, write_prompt_rows
from bigdl_tpu.parallel.expert import GatedMoE

ROWS, LENGTH, P = 6, 16, 8
#: three prompts of one bucket, then a row that fills the program up: its
#: slot lies past the cache's rows and it has no real position
SLOTS, LENGTHS = [4, 0, 2, ROWS], [5, 8, 3, 0]


def _layers():
    return {
        "attention": nn.MultiHeadAttention(32, 4, causal=True,
                                           num_kv_heads=2),
        "latent": nn.LatentAttention(32, 2, q_lora_rank=16, kv_lora_rank=16,
                                     qk_nope_head_dim=8, qk_rope_head_dim=8,
                                     v_head_dim=8),
        "mamba": nn.Mamba2Mixer(32, heads=4, head_dim=8, groups=2, state=8,
                                chunk=4),
        "delta": nn.GatedDeltaNet(32, k_heads=2, v_heads=4, k_head_dim=8,
                                  v_head_dim=8, chunk=4),
        "experts": GatedMoE(32, 16, 8, 2, n_shared=1, held=(2, 4)),
    }


def _loud_cache(layer, key):
    """The layer's leaves for ROWS rows, every entry non-zero: a write that
    should not have happened shows."""
    spec = layer.decode_state(ROWS, LENGTH)
    if not spec:
        return None
    keys = jax.random.split(key, len(spec))
    return {n: 1.0 + jax.random.uniform(k, leaf.shape,
                                        leaf.dtype or jnp.float32)
            for k, (n, leaf) in zip(keys, sorted(spec.items()))}


@pytest.mark.parametrize("kind", ["attention", "latent", "mamba", "delta",
                                  "experts"])
def test_a_group_prefill_leaves_what_one_row_prefills_leave(kind):
    layer = _layers()[kind]
    params = layer._init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (len(SLOTS), P, 32))
    cache = _loud_cache(layer, jax.random.key(5))
    slot, length = jnp.asarray(SLOTS), jnp.asarray(LENGTHS)
    y, got = layer.decode_prefill(params, x, cache, slot, length)
    want, reports = cache, []
    for i in range(3):
        yi, want = layer.decode_prefill(params, x[i:i + 1], want,
                                        slot[i:i + 1], length[i:i + 1])
        # a row's real positions come out as they do alone (its pads too,
        # but nothing reads them)
        np.testing.assert_allclose(y[i, :LENGTHS[i]], yi[0, :LENGTHS[i]],
                                   rtol=1e-5, atol=1e-5)
        reports.append(want)
    assert np.isfinite(np.asarray(y)).all()
    if cache is None:
        # the experts' report: the real tokens' counts summed, the choices
        # row by row; the fill-up row is counted nowhere
        counts, chosen = got
        np.testing.assert_array_equal(
            counts, sum(np.asarray(c) for c, _i in reports))
        assert int(counts.sum()) == 2 * sum(LENGTHS)
        for i, (_c, idx) in enumerate(reports):
            np.testing.assert_array_equal(chosen[i], idx[0])
        return
    for n in cache:
        np.testing.assert_allclose(got[n], want[n], rtol=1e-5, atol=1e-5)
        # rows no prompt entered hold what they held: the fill-up row wrote
        # nothing anywhere (an index past the rows would clamp to the last
        # one under a dynamic-update-slice)
        np.testing.assert_array_equal(np.asarray(got[n])[[1, 3, 5]],
                                      np.asarray(cache[n])[[1, 3, 5]])
        assert not np.array_equal(np.asarray(got[n])[4],
                                  np.asarray(cache[n])[4])


def test_scalar_slot_and_length_stand_for_every_row():
    x = jnp.zeros((3, P, 4))
    slot, length = prefill_rows(x, 2, 5)
    np.testing.assert_array_equal(slot, [2, 2, 2])
    np.testing.assert_array_equal(length, [5, 5, 5])
    cache = jnp.zeros((4, LENGTH, 4))
    new = write_prompt_rows(cache, jnp.asarray([3, 4, 0]), x + 1.0)
    # rows 3 and 0 take their P positions from the start, slot 4 is past
    # the cache's rows and is dropped, nothing else moves
    new = np.asarray(new)
    assert new[3, :P].min() == new[0, :P].min() == 1.0
    assert not new[[1, 2]].any() and not new[:, P:].any()
    # a lone row (a one-row program has nothing to fill up) is written by a
    # dynamic-update-slice, which the compiler fuses into the product that
    # computes the window; several rows by one scatter
    ops = lambda n: {e.primitive.name for e in jax.make_jaxpr(
        lambda c, s, a: write_prompt_rows(c, s, a))(
            cache, jnp.arange(n), x[:n] + 1.0).eqns}
    assert "dynamic_update_slice" in ops(1) and "scatter" not in ops(1)
    assert "scatter" in ops(3) and "dynamic_update_slice" not in ops(3)
    np.testing.assert_array_equal(
        write_prompt_rows(cache, jnp.asarray([2]), x[:1] + 1.0),
        cache.at[2, :P].set(1.0))


def _models():
    from test_decode_state import _ds, _lm, _nemo
    from test_qwen3_next import build, small_cfg
    return {"lm": _lm, "ds": _ds, "nemo": _nemo,
            "qwen": lambda: build(small_cfg())}


@pytest.mark.parametrize("make", ["lm", "ds", "nemo", "qwen"])
def test_the_group_program_gives_each_row_its_own_logits_and_report(make):
    """`models/decode._prefill` over `[n, P]`: each row's logits are those
    of its own position `t0 - 1`, the caches and the experts' report what n
    one-row calls leave, whatever the slots' order."""
    m = _models()[make]()
    if m.params is None:
        m.build(jax.random.key(7))
    vocab = 64 if make != "qwen" else 211
    toks = np.zeros((4, P), np.int32)
    r = np.random.default_rng(11)
    for i, n in enumerate(LENGTHS):
        toks[i, :n] = r.integers(1, vocab, n)
    fresh = lambda: tuple(kv.init_kv_cache(m, ROWS, LENGTH, jnp.float32))
    slot, t0 = jnp.asarray(SLOTS), jnp.asarray(LENGTHS)
    logits, caches, report = kv._prefill(m, m.params, m.state,
                                         jnp.asarray(toks), fresh(), slot, t0)
    assert logits.shape == (4, vocab)
    want, counts = fresh(), 0
    for i in range(3):
        li, want, ri = kv._prefill(m, m.params, m.state,
                                   jnp.asarray(toks[i:i + 1]), want,
                                   slot[i:i + 1], t0[i:i + 1])
        np.testing.assert_allclose(logits[i], li[0], rtol=2e-5, atol=2e-5)
        if ri is not None:
            counts = counts + np.asarray(ri[0])
            for a, b in zip(report[1], ri[1]):      # a layer's choices
                np.testing.assert_array_equal(a[i], b[0])
    for got, ref in zip(caches, want):
        for n in got:
            np.testing.assert_allclose(got[n], ref[n], rtol=2e-5, atol=2e-5)
            assert not np.asarray(got[n])[[1, 3, 5]].any()
    if report is not None:
        np.testing.assert_array_equal(report[0], counts)
    else:
        assert make == "lm"


def test_the_prefill_table_tool_times_nothing_off_the_chip():
    """`tools/prefill_rows.py` reads device times: where jax finds no TPU
    and the CPU was not asked for by name, it exits 1 and prints no line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "prefill_rows.py"),
         "--workload", "gpt2m.decode", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert run.returncode == 1 and run.stdout == ""
    assert "nothing was timed" in run.stderr
