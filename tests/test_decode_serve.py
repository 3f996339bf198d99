"""Continuous-batching decode serving (bigdl_tpu/serve/decode.py — ISSUE 18).

The generative serving contract under test (docs/serving.md "Generative
decode"):
  - a persistent step loop over fixed KV-cache slots: sequences join via
    prefill into a free slot, every tick decodes ALL active slots in one
    kernel call, and a finished sequence frees its slot the SAME step;
  - greedy outputs BIT-match the offline ``cached_generate`` oracle per
    sequence, regardless of what else shares the batch (the per-slot
    masked attention gives stale cache rows exactly zero weight);
  - the (batch-slots, cache-page) ladder grows the cache mid-flight and
    the footprint is exact and observable (``cache_bytes_per_slot``);
  - prefill and decode are SEPARATE jitted executables with separate
    compile cards (``decode.prefill`` / ``decode.step``);
  - the prefill is ONE pass over the padded prompt bucket (no loop in the
    program, the head on one row); its tokens equal the per-position
    oracle's at every edge of a bucket, and its logits agree within a
    float32 tolerance (ISSUE 26);
  - admission is a per-sequence ``DecodeQueue``: bounded, deadline =
    time-to-last-token (shed typed at dequeue), tenant token buckets;
  - a ``serve.decode@<slot>`` chaos fault fails ONE sequence typed and
    the other slots keep decoding with zero loss;
  - under a (1,1,2) tp mesh the per-device KV cache halves and greedy
    tokens match the single-device run.
"""

import os

import numpy as np
import jax
import pytest

from bigdl_tpu.models import decode as kv
from bigdl_tpu.models.decode import cached_generate, init_kv_cache
from bigdl_tpu.models.transformer_lm import TransformerLM
from bigdl_tpu.serve import (DecodeEngine, DecodeQueue, QuotaExceeded,
                             RequestTimeout, ServeError, SlotFault,
                             TraceEvent, page_ladder, pad_rows, read_trace,
                             write_trace)
from bigdl_tpu.utils import aot as aot_mod
from bigdl_tpu.utils import chaos, hlostats


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=64, max_len=64, d_model=32,
                         num_heads=2, num_layers=2).build(jax.random.key(0))


@pytest.fixture(scope="module")
def lm_odd():
    # a max_len that is no power of two (a prompt bucket can exceed the
    # cache and the model's own positions) and a vocabulary whose size no
    # other axis of the model has
    return TransformerLM(vocab_size=80, max_len=40, d_model=32,
                         num_heads=2, num_layers=2).build(jax.random.key(3))


def _prompts(n, lo=3, hi=10, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _oracle(lm, prompt, max_tokens):
    return cached_generate(lm, prompt, max_tokens,
                           max_len=len(prompt) + max_tokens)


def _spy_on_compiles(monkeypatch):
    """label -> (AOT key fields, lowered text) of every executable built
    from here on."""
    seen = {}
    real = aot_mod.get_or_compile

    def spy(key_fields, lower_fn, *, label, card_extra=None):
        seen[label] = (dict(key_fields), lower_fn().as_text())
        return real(key_fields, lower_fn, label=label,
                    card_extra=card_extra)

    monkeypatch.setattr(aot_mod, "get_or_compile", spy)
    return seen


# ---------------------------------------------------------------------------
# pad_rows trailing-axis padding (satellite: serve/batcher.py)
# ---------------------------------------------------------------------------

def test_pad_rows_trailing_axis_pads_with_zeros():
    arr = np.arange(6, dtype=np.int32).reshape(2, 3)
    out = pad_rows(arr, 4, length=8)
    assert out.shape == (4, 8)
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out[:2, :3], arr)
    # rows pad by repeating the last row (the legacy fixed-batch
    # contract); the NEW trailing axis pads with zeros
    np.testing.assert_array_equal(out[2:, :3], np.tile(arr[-1], (2, 1)))
    assert not out[:, 3:].any()


def test_pad_rows_length_zero_rows_and_dtype():
    # zero-row input: row padding alone can't invent the trailing size,
    # so the length= form must (the legacy no-length call keeps its
    # empty-array behavior)
    out = pad_rows(np.zeros((0, 3), np.float16), 2, length=5)
    assert out.shape == (2, 5) and out.dtype == np.float16
    assert not out.any()


def test_pad_rows_refuses_to_truncate():
    with pytest.raises(ValueError, match="refusing to truncate"):
        pad_rows(np.ones((2, 9), np.float32), 2, length=4)


# ---------------------------------------------------------------------------
# DecodeQueue admission (per-sequence queue under the step loop)
# ---------------------------------------------------------------------------

def test_decode_queue_take_is_nonblocking_and_bounded():
    q = DecodeQueue(queue_limit=8)
    reqs = [q.submit({"max_tokens": 4, "i": i}) for i in range(3)]
    assert q.take(0) == []
    got = q.take(2)
    assert [r.payload["i"] for r in got] == [0, 1]
    assert q.take(5) == [reqs[2]]
    assert q.take(1) == []  # empty: returns, never parks


def test_decode_queue_sheds_expired_deadline_at_dequeue():
    t = [0.0]
    q = DecodeQueue(queue_limit=8, clock=lambda: t[0])
    late = q.submit({"max_tokens": 4}, deadline=1.0)
    live = q.submit({"max_tokens": 4}, deadline=50.0)
    t[0] = 2.0
    got = q.take(2)
    assert got == [live]
    with pytest.raises(RequestTimeout):
        late.result(0.1)
    assert q.shed_timeout == 1


def test_decode_queue_retry_after_scales_with_token_budget():
    q = DecodeQueue(queue_limit=64)
    q.note_service(100, 1.0)  # EMA learns 10ms/token
    q.submit({"max_tokens": 200})
    q.submit({"max_tokens": 200})
    # 400 queued tokens at ~10ms/token >> the 0.05s floor
    assert q.retry_after_s() >= 1.0


# ---------------------------------------------------------------------------
# the engine: page ladder, oracle parity, same-step slot reuse
# ---------------------------------------------------------------------------

def test_page_ladder_pow2_pages_capped_at_max_len():
    assert page_ladder(16, 128) == (16, 32, 64, 128)
    assert page_ladder(16, 100) == (16, 32, 64, 100)
    assert page_ladder(8, 8) == (8,)
    with pytest.raises(ValueError):
        page_ladder(0, 64)


def test_continuous_batching_bit_matches_oracle(lm):
    # 5 mixed-length sequences through 2 slots: forces same-step slot
    # reuse AND mixed in-flight positions; every output must equal the
    # offline single-sequence oracle bit for bit
    prompts = _prompts(5, seed=1)
    budgets = [4, 7, 3, 6, 5]
    with DecodeEngine(lm, slots=2, page=8) as eng:
        handles = [eng.submit(p, mt) for p, mt in zip(prompts, budgets)]
        outs = [h.result(120.0) for h in handles]
        st = eng.stats()
    for p, mt, out in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(out, _oracle(lm, p, mt))
    assert st["seqs_done"] == 5 and st["seqs_failed"] == 0
    assert st["prefill_steps"] == 5  # one prefill per admitted sequence
    assert st["tokens_out"] == sum(budgets)


# ---------------------------------------------------------------------------
# the greedy token chosen on the device (ISSUE 33)
# ---------------------------------------------------------------------------

def test_a_greedy_and_a_sampling_slot_side_by_side(lm):
    # what the request says decides its row's way: the greedy slot takes
    # the device's token and no row of it is fetched; the sampling slot's
    # rows are fetched and go through sample_next with its own seed
    greedy, sampled = _prompts(2, lo=5, hi=9, seed=21)
    with DecodeEngine(lm, slots=2, page=32) as eng:
        a = eng.submit(greedy, 9)
        b = eng.submit(sampled, 7, temperature=0.8, top_k=5, seed=3)
        outs = a.result(120.0), b.result(120.0)
        st = eng.stats()
    np.testing.assert_array_equal(outs[0], _oracle(lm, greedy, 9))
    np.testing.assert_array_equal(outs[1], cached_generate(
        lm, sampled, 7, max_len=len(sampled) + 7, temperature=0.8, top_k=5,
        rng=jax.random.PRNGKey(3)))
    assert st["tokens_device_sampled"] == 9 and st["logit_rows_fetched"] == 7
    assert st["tokens_out"] == 16


def test_greedy_traffic_fetches_no_row_and_the_track_says_so(lm, tmp_path):
    from bigdl_tpu.utils import telemetry
    tr = telemetry.Tracer(str(tmp_path))
    telemetry.set_active(tr)
    try:
        with DecodeEngine(lm, slots=2, page=8) as eng:
            for h in [eng.submit(p, 4) for p in _prompts(3, seed=22)]:
                h.result(120.0)
            st = eng.stats()
    finally:
        telemetry.set_active(None)
    assert st["logit_rows_fetched"] == 0
    assert st["tokens_device_sampled"] == st["tokens_out"] == 12
    track = [e["args"] for e in tr.events_tail(4096)
             if e.get("ph") == "C" and e["name"] == "serve.decode"]
    assert track[-1]["tokens_device_sampled"] == 12
    assert track[-1]["logit_rows_fetched"] == 0
    tr.flush()
    bd = telemetry.phase_breakdown(telemetry.merge_traces(str(tmp_path)))
    assert bd["decode"]["tokens_device_sampled"] == 12
    line = [ln for ln in telemetry.format_report(bd).splitlines()
            if ln.startswith("decode:")][0]
    assert "logit_rows_fetched=" in line and "tokens_device_sampled=" in line


def test_the_row_handed_to_sample_fetches_itself_only_when_read():
    from bigdl_tpu.serve.decode import _LogitRow
    logits = jax.numpy.arange(3 * 64, dtype=jax.numpy.float32).reshape(3, 64)
    row = _LogitRow(np.int32(63), logits, 1)
    assert len(row) == 64 and row.token == 63
    np.testing.assert_array_equal(np.asarray(row), np.arange(64, 128))
    np.testing.assert_array_equal(np.asarray(_LogitRow(0, logits[2])),
                                  np.arange(128, 192))


def test_both_programs_return_tokens_and_their_keys_say_so(lm, monkeypatch):
    seen = _spy_on_compiles(monkeypatch)
    eng = DecodeEngine(lm, slots=2, page=16)
    eng._step_exe(16)
    eng._prefill_exe(1, 8, 16)
    for label, logits in (("decode.step", "tensor<2x64xf32>"),
                          ("decode.prefill", "tensor<1x64xf32>")):
        fields, text = seen[label]
        main = [ln for ln in text.splitlines() if "func.func public @main"
                in ln][0]
        args, results = main.split("->")
        # both leave the slots' tokens beside the logits, and take the
        # vector the call before left (ISSUE 40): one such argument each
        assert results.index(logits) < results.index("tensor<2xi32>"), results
        assert args.count("tensor<2xi32>") == 1, args
        # a warm AOT directory written before the programs returned tokens
        # holds them under keys without this field, and one written while
        # the host fed every token under another value of it
        assert fields["tokens"] == "argmax_carried"
        assert aot_mod.fingerprint(fields) != aot_mod.fingerprint(
            {k: v for k, v in fields.items() if k != "tokens"})
        assert aot_mod.fingerprint(fields) != aot_mod.fingerprint(
            dict(fields, tokens="argmax"))


# ---------------------------------------------------------------------------
# the loop runs a call ahead of its reads (ISSUE 40)
# ---------------------------------------------------------------------------

class _Left:
    """What a fake program leaves on the device: it says when it is read."""

    def __init__(self, log, name, value):
        self.log, self.name, self.value = log, name, value

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.name))
        return np.asarray(self.value, dtype=dtype)


def _run_queued(eng, requests, **kw):
    """Every request queued and the queue closed before the loop starts: the
    engine works from its first pass to its last and never waits."""
    handles = [eng.submit(p, mt, **dict(kw, **own))
               for p, mt, own in requests]
    eng.queue.close(drain=True)
    eng.start()
    eng.stop()
    return handles


def test_the_next_step_is_called_before_the_last_ones_tokens_are_read(lm):
    # programs that record when they are called and when what they left is
    # read; each leaves its own number as every slot's token
    log, n = [], {"step": 0, "prefill": 0}

    def fake(kind):
        def exe(params, state, caches, tokens, *rest):
            n[kind] += 1
            name = f"{kind}{n[kind]}"
            log.append(("call", name))
            return "logits", _Left(log, name, [n[kind]] * 2), caches, None
        return exe

    eng = DecodeEngine(lm, slots=2, page=16)
    eng._step_exe = lambda cache_len: fake("step")
    eng._prefill_exe = lambda rows, bucket, cache_len: fake("prefill")
    eng._prefill_programs = lambda bucket, cache_len: (1,)
    prompts = _prompts(2, seed=31)
    handles = _run_queued(eng, [(p, 5, {}) for p in prompts])
    st = eng.stats()
    assert st["decode_steps"] == 4 and st["prefill_steps"] == 2
    at = {e: i for i, e in enumerate(log)}
    # both prefills and the first step are called before anything is read,
    # and step k + 1 before step k's tokens come down
    assert log[:3] == [("call", "prefill1"), ("call", "prefill2"),
                       ("call", "step1")]
    for k in range(1, 4):
        assert at[("call", f"step{k + 1}")] < at[("read", f"step{k}")], log
    assert [e for e in log if e[0] == "read"] == [
        ("read", "prefill1"), ("read", "prefill2")] + [
        ("read", f"step{k}") for k in range(1, 5)]
    assert st["steps_ahead"] == st["decode_steps"]
    # each row: its prompt, its prefill's token, then the steps' in order
    for i, (p, h) in enumerate(zip(prompts, handles)):
        np.testing.assert_array_equal(
            h.result(1.0), np.concatenate([p, [i + 1, 1, 2, 3, 4]]))
    assert st["tokens_out"] == 10 and st["active"] == 0


def test_a_mixed_load_gets_the_oracles_rows_request_by_request(lm):
    # greedy, greedy that stops at an EOS, sampling with a seed, budgets of
    # one and two tokens, seven requests through two slots
    prompts = _prompts(7, lo=3, hi=12, seed=32)
    full = _oracle(lm, prompts[1], 9)
    gen = [int(t) for t in full[len(prompts[1]):]]
    k = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
    requests = [(prompts[0], 6, {}),
                (prompts[1], 9, {"eos_token": gen[k]}),
                (prompts[2], 5, {"temperature": 0.8, "top_k": 5, "seed": 3}),
                (prompts[3], 1, {}),
                (prompts[4], 2, {}),
                (prompts[5], 1, {"temperature": 0.7, "seed": 9}),
                (prompts[6], 7, {"temperature": 1.1, "top_k": 3, "seed": 4})]
    eng = DecodeEngine(lm, slots=2, page=32)
    rows = [h.result(1.0) for h in _run_queued(eng, requests)]
    st = eng.stats()
    for (p, mt, own), row in zip(requests, rows):
        if "temperature" in own:
            want = cached_generate(
                lm, p, mt, max_len=len(p) + mt, temperature=own["temperature"],
                top_k=own.get("top_k", 0), rng=jax.random.PRNGKey(own["seed"]))
        else:
            want = _oracle(lm, p, mt)
            if "eos_token" in own:
                want = want[: len(p) + k + 1]
        np.testing.assert_array_equal(row, want)
    # only tokens that were received count: the row a slot computed past its
    # EOS, before the host had seen it, is nobody's
    received = sum(len(r) - len(req[0]) for r, req in zip(rows, requests))
    assert st["tokens_out"] == received == 6 + k + 1 + 5 + 1 + 2 + 1 + 7
    assert st["tokens_device_sampled"] == 6 + k + 1 + 1 + 2
    assert st["logit_rows_fetched"] == 5 + 1 + 7
    assert st["seqs_done"] == 7 and st["active"] == 0
    assert 0 < st["steps_ahead"] < st["decode_steps"]


def test_a_pass_that_holds_a_sampling_slot_reads_before_it_calls(lm):
    # a sampled token exists on the host alone: no step of such a load is
    # called with a call before it unread, and the rows are the oracle's
    prompts = _prompts(3, seed=33)
    eng = DecodeEngine(lm, slots=2, page=16)
    handles = _run_queued(
        eng, [(p, 4, {"temperature": 0.9, "top_k": 4, "seed": 11 + i})
              for i, p in enumerate(prompts)])
    st = eng.stats()
    assert st["decode_steps"] > 0 and st["steps_ahead"] == 0
    for i, (p, h) in enumerate(zip(prompts, handles)):
        np.testing.assert_array_equal(h.result(1.0), cached_generate(
            lm, p, 4, max_len=len(p) + 4, temperature=0.9, top_k=4,
            rng=jax.random.PRNGKey(11 + i)))
    # a greedy load beside it runs ahead at every step
    eng = DecodeEngine(lm, slots=2, page=16)
    _run_queued(eng, [(p, 4, {}) for p in prompts])
    assert eng.stats()["steps_ahead"] == eng.stats()["decode_steps"] > 0


@pytest.mark.parametrize("fault", ["chaos", "step", "prefill"])
def test_a_fault_with_a_read_pending_leaves_no_request_unanswered(lm, fault):
    # a slot's chaos fault, a step that throws and a prefill that throws,
    # each while calls wait unread: every request is answered, with its
    # oracle's row or a typed error, and no slot stays taken
    prompts = _prompts(5, seed=34)
    eng = DecodeEngine(lm, slots=2, page=16)
    real = {"step": eng._step_exe, "prefill": eng._prefill_exe}
    count = {"step": 0, "prefill": 0}

    def throwing(kind, at):
        def build(*dims):
            exe = real[kind](*dims)

            def call(*args):
                count[kind] += 1
                if count[kind] == at:
                    raise RuntimeError(f"the {kind} broke")
                return exe(*args)
            return call
        return build

    if fault == "step":
        eng._step_exe = throwing("step", 3)
    elif fault == "prefill":
        eng._prefill_exe = throwing("prefill", 3)
    with chaos.scoped("serve.decode@1=fail@3" if fault == "chaos" else ""):
        handles = _run_queued(eng, [(p, 6, {}) for p in prompts])
    st = eng.stats()
    failed = 0
    for p, h in zip(prompts, handles):
        assert h.done()
        try:
            np.testing.assert_array_equal(h.result(1.0), _oracle(lm, p, 6))
        except (chaos.ChaosFault, SlotFault, RuntimeError) as e:
            failed += 1
            assert fault in ("step", "prefill") or \
                isinstance(e, chaos.ChaosFault)
    # the step's fault is nobody's: both sequences in flight fail; the
    # others take one sequence each
    assert failed == (2 if fault == "step" else 1)
    assert st["seqs_failed"] == failed and st["seqs_done"] == 5 - failed
    assert st["active"] == 0 and not eng._unread
    assert all(s is None for s in eng._slots)


def test_stop_with_a_step_in_flight_returns_every_row_whole(lm):
    prompts = _prompts(4, seed=35)
    eng = DecodeEngine(lm, slots=2, page=16).start()
    handles = [eng.submit(p, 6) for p in prompts]
    eng.stop(drain=True)            # returns once the loop has read it all
    for p, h in zip(prompts, handles):
        assert h.done()
        np.testing.assert_array_equal(h.result(0.0), _oracle(lm, p, 6))
    assert not eng._unread and eng.stats()["tokens_out"] == 24


# ---------------------------------------------------------------------------
# the one-pass admission prefill (ISSUE 26)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t0,pb", [(1, 8), (7, 8), (8, 8), (9, 16),
                                   (15, 16), (16, 16), (17, 32)])
def test_prefill_matches_oracle_at_bucket_edges(lm, t0, pb):
    # a prompt of one token, one that fills its bucket, one short of it,
    # and the first of the next bucket
    prompt = np.random.default_rng(100 + t0).integers(
        1, 64, size=t0).astype(np.int32)
    with DecodeEngine(lm, slots=2, page=8) as eng:
        out = eng.generate(prompt, 6)
        st = eng.stats()
    np.testing.assert_array_equal(out, _oracle(lm, prompt, 6))
    assert (st["prompt_tokens"], st["prefill_positions"]) == (t0, pb)


@pytest.mark.parametrize("t0", [17, 33, 37])
def test_prefill_bucket_longer_than_the_cache(lm_odd, t0):
    # max_len 40: buckets 32 and 64 meet caches of 32 and 40 positions,
    # and the model has no position past 39; only the cache's length runs
    prompt = np.random.default_rng(200 + t0).integers(
        1, 80, size=t0).astype(np.int32)
    with DecodeEngine(lm_odd, slots=2, page=8) as eng:
        assert eng.ladder == (8, 16, 32, 40)
        out = eng.generate(prompt, 3)
        st = eng.stats()
    np.testing.assert_array_equal(out, _oracle(lm_odd, prompt, 3))
    assert st["prefill_positions"] == min(64 if t0 > 32 else 32,
                                          st["cache_len"])


def test_slot_reused_by_a_shorter_prompt_after_a_longer_one(lm):
    # one slot: the second sequence finds the first one's k and v (and its
    # own bucket's pads) in the rows beyond its prompt; they weigh nothing
    long, short = _prompts(2, lo=13, hi=15, seed=12)
    short = short[:3]
    with DecodeEngine(lm, slots=1, page=32) as eng:
        first = eng.submit(long, 10)
        second = eng.submit(short, 12)
        outs = first.result(120.0), second.result(120.0)
        st = eng.stats()
    np.testing.assert_array_equal(outs[0], _oracle(lm, long, 10))
    np.testing.assert_array_equal(outs[1], _oracle(lm, short, 12))
    assert st["cache_len"] == 32 and st["prefill_steps"] == 2


@pytest.mark.parametrize("t0", [1, 5, 8])
def test_prefill_logits_and_cache_match_the_per_position_oracle(lm, t0):
    # the same products as [8, E] x [E, .] and as [1, E] x [E, .] may
    # differ in the last bits: float32 tolerance, stated
    prompt = np.random.default_rng(300 + t0).integers(
        1, 64, size=t0).astype(np.int32)
    step = kv._get_step(lm, 1, 16, np.float32)
    ref_caches = tuple(init_kv_cache(lm, 1, 16, np.float32))
    for pos in range(t0):
        ref, ref_caches = step(lm.params, lm.state, ref_caches,
                               prompt[pos:pos + 1], pos)
    eng = DecodeEngine(lm, slots=3, page=16, cache_dtype=np.float32)
    toks = np.zeros(8, np.int32)
    toks[:t0] = prompt
    logits, tokens, caches, _counts = eng._prefill_exe(1, 8, 16)(
        eng._params, eng._state, eng._fresh_caches(16),
        np.array([5, 6, 7], np.int32), toks[None], np.array([1], np.int32),
        np.array([t0], np.int32))
    assert logits.shape == (1, 64)
    # the slots' token vector comes back with this slot's row set
    np.testing.assert_array_equal(tokens, [5, np.argmax(logits[0]), 7])
    np.testing.assert_allclose(logits[0], ref[0], rtol=1e-5, atol=1e-5)
    for got, want in zip(caches, ref_caches):
        for n in "kv":
            arr = np.asarray(got[n])
            np.testing.assert_allclose(arr[1, :t0],
                                       np.asarray(want[n])[0, :t0],
                                       rtol=1e-5, atol=1e-5)
            # the pads' rows are written and finite, nothing beyond the
            # bucket is, and the other slots are untouched
            assert np.isfinite(arr).all()
            assert not arr[1, 8:].any() and not arr[[0, 2]].any()


def test_prefill_program_has_no_loop_and_one_row_at_the_head(lm_odd,
                                                             monkeypatch):
    seen = _spy_on_compiles(monkeypatch)
    eng = DecodeEngine(lm_odd, slots=2, page=16)
    eng._prefill_exe(1, 8, 16)
    fields, text = seen["decode.prefill"]
    hist = hlostats.op_histogram(text)
    assert "while" not in hist and hist["dot_general"] > 0
    # every product over the vocabulary (80 wide, like no other axis)
    heads = [ln for ln in text.splitlines()
             if "dot_general" in ln and "x80x" in ln]
    assert len(heads) == 1, heads
    assert heads[0].rstrip().endswith("-> tensor<1x1x80xf32>")
    # ... while the blocks' products run over the bucket's 8 positions
    assert any("dot_general" in ln and "-> tensor<1x8x32x" in ln
               for ln in text.splitlines())
    # the key tells this program from the per-position one of the same name
    assert fields["body"] == "one_pass_group" and fields["rows"] == 1
    assert aot_mod.fingerprint(fields) != aot_mod.fingerprint(
        {k: v for k, v in fields.items() if k != "body"})


def test_stats_count_prompt_tokens_and_prefill_positions(lm):
    lens = [1, 3, 8, 9, 16, 20]
    prompts = [np.random.default_rng(400 + n).integers(
        1, 64, size=n).astype(np.int32) for n in lens]
    with DecodeEngine(lm, slots=2, page=32) as eng:
        for h in [eng.submit(p, 2) for p in prompts]:
            h.result(120.0)
        st = eng.stats()
    # two slots: a take of one fills no group, so every call carries one
    # request and no row fills a program up
    assert st["prefill_rows"] == st["prefill_steps"] == len(lens)
    assert st["prompt_tokens"] == sum(lens) == 57
    assert st["prefill_positions"] == 8 + 8 + 8 + 16 + 16 + 32


def test_moe_model_prefills_in_one_pass():
    # MoEFFN is a leaf: it takes its experts' capacity from the tokens it
    # sees, and in one pass those are the bucket's positions, pads too
    # (one at a time, as the oracle runs, nothing ever drops).  With room
    # for every token the two agree; docs/serving.md has the caveat.
    from bigdl_tpu.parallel.expert import MoEFFN
    moe = TransformerLM(vocab_size=64, max_len=64, d_model=32, num_heads=2,
                        num_layers=2, num_experts=4).build(jax.random.key(1))
    for ffn in kv._modules_of_type(moe, MoEFFN):
        ffn.capacity_factor = 4.0     # C = T: no token can overflow
    prompts = _prompts(3, lo=3, hi=12, seed=13)
    with DecodeEngine(moe, slots=2, page=8) as eng:
        outs = [h.result(120.0)
                for h in [eng.submit(p, 5) for p in prompts]]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(moe, p, 5))


def test_prefill_raises_on_an_unknown_container(lm):
    from bigdl_tpu.nn.module import Container

    class Odd(Container):
        pass

    with pytest.raises(NotImplementedError, match="unsupported container"):
        kv._prefill(Odd(), (), (), np.zeros(8, np.int32), (), 0, 1)


def test_eos_frees_slot_same_step(lm):
    prompt = _prompts(1, seed=2)[0]
    full = _oracle(lm, prompt, 8)
    gen = [int(t) for t in full[len(prompt):]]
    # the EOS is a token the oracle emits for the FIRST time after its
    # first step, picked from the oracle's own output (which token that is
    # depends on the weights, and they on the jax version)
    k = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
    with DecodeEngine(lm, slots=1, page=8) as eng:
        out = eng.generate(prompt, 8, eos_token=gen[k])
        st = eng.stats()
    # truncated AT the EOS token (inclusive), budget unspent
    np.testing.assert_array_equal(out, full[: len(prompt) + k + 1])
    assert st["tokens_out"] == k + 1 < 8


def test_cache_grows_through_the_page_ladder(lm):
    import time as _time
    short, long = _prompts(2, lo=4, hi=6, seed=3)
    with DecodeEngine(lm, slots=2, page=8, min_step_s=0.01) as eng:
        # sequence A occupies a slot at the 32-page; once it is IN
        # FLIGHT, B needs the 64 bucket -> a mid-flight concat grow
        # (idle re-page would be a fresh alloc, cache_grows stays 0)
        ha = eng.submit(short, 25)
        deadline = _time.monotonic() + 60.0
        while eng.stats()["active"] == 0:
            assert _time.monotonic() < deadline, "A never admitted"
            _time.sleep(0.002)
        assert eng.stats()["cache_len"] == 32
        hb = eng.submit(long, 50)
        first, out = ha.result(120.0), hb.result(120.0)
        st = eng.stats()
    np.testing.assert_array_equal(first, _oracle(lm, short, 25))
    np.testing.assert_array_equal(out, _oracle(lm, long, 50))
    assert st["cache_len"] == 64 and st["cache_grows"] >= 1
    # exact structural footprint: layers x {k,v} x heads x len x head_dim
    assert st["cache_bytes_per_slot"] == 2 * 2 * 2 * st["cache_len"] * 16 * 4


def test_batch_admission_mode_is_run_to_completion(lm):
    prompts = _prompts(4, seed=4)
    with DecodeEngine(lm, slots=2, page=8, admission="batch") as eng:
        handles = [eng.submit(p, 4) for p in prompts]
        outs = [h.result(120.0) for h in handles]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(lm, p, 4))
    with pytest.raises(ValueError, match="admission"):
        DecodeEngine(lm, admission="sometimes")


def test_prefill_and_decode_emit_separate_compile_cards(lm, monkeypatch):
    from bigdl_tpu.utils import hlostats
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CARDS", "1")
    hlostats.reset()
    try:
        with DecodeEngine(lm, slots=2, page=8) as eng:
            eng.generate(_prompts(1, seed=5)[0], 3)
        ledger = hlostats.ledger()
        assert ledger.get("decode.prefill", 0) >= 1
        assert ledger.get("decode.step", 0) >= 1
    finally:
        hlostats.reset()


# ---------------------------------------------------------------------------
# typed rejection, deadlines, quotas, chaos
# ---------------------------------------------------------------------------

def test_submit_rejects_bad_requests_typed(lm):
    eng = DecodeEngine(lm, slots=1, page=8)  # never started: pure checks
    with pytest.raises(ServeError, match="non-empty"):
        eng.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ServeError, match="max_tokens"):
        eng.submit(np.ones(3, np.int32), 0)
    with pytest.raises(ServeError, match="max_len"):
        eng.submit(np.ones(3, np.int32), 1000)
    with pytest.raises(ValueError, match="max_len"):
        DecodeEngine(lm, max_len=4096)  # beyond the PE cap


def test_queue_deadline_times_out_typed(lm):
    # slot pinned busy by a long sequence at a paced step floor; the
    # queued request's time-to-last-token deadline passes before a slot
    # frees -> typed RequestTimeout at dequeue, engine keeps serving
    prompt = _prompts(1, seed=6)[0]
    with DecodeEngine(lm, slots=1, page=8, min_step_s=0.02) as eng:
        slow = eng.submit(prompt, 30)
        late = eng.submit(prompt, 4, deadline_ms=40.0)
        with pytest.raises(RequestTimeout):
            late.result(120.0)
        np.testing.assert_array_equal(slow.result(120.0),
                                      _oracle(lm, prompt, 30))
        assert eng.stats()["queue"]["shed_timeout"] == 1


def test_tenant_quota_rejects_typed(lm):
    with DecodeEngine(lm, slots=1, page=8, tenant_qps=0.001,
                      tenant_burst=1) as eng:
        prompt = _prompts(1, seed=7)[0]
        first = eng.submit(prompt, 2, tenant="team-a")
        with pytest.raises(QuotaExceeded):
            eng.submit(prompt, 2, tenant="team-a")
        first.result(120.0)


def test_chaos_slot_fault_fails_one_sequence_others_bit_match(lm):
    # the serve.decode@<slot> drill: slot 1's sequence dies typed, the
    # slot frees, every OTHER sequence still bit-matches the oracle
    prompts = _prompts(4, seed=8)
    with chaos.scoped("serve.decode@1=fail@2"):
        with DecodeEngine(lm, slots=2, page=8) as eng:
            handles = [eng.submit(p, 5) for p in prompts]
            failed, survived = [], []
            for p, h in zip(prompts, handles):
                try:
                    survived.append((p, h.result(120.0)))
                except chaos.ChaosFault:
                    failed.append(h)
            st = eng.stats()
    assert len(failed) == 1 and st["seqs_failed"] == 1
    assert len(survived) == 3 and st["seqs_done"] == 3
    for p, out in survived:
        np.testing.assert_array_equal(out, _oracle(lm, p, 5))


# ---------------------------------------------------------------------------
# tp-sharded decode (satellite: (1,1,2) mesh parity + halved cache)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_tp_sharded_cached_generate_matches_single_device(lm):
    from bigdl_tpu.parallel import MeshLayout
    mesh = MeshLayout(1, 1, 2).build_mesh(jax.devices()[:2])
    prompt = _prompts(1, seed=9)[0]
    ref = _oracle(lm, prompt, 6)
    got = cached_generate(lm, prompt, 6, max_len=len(prompt) + 6,
                          mesh=mesh)
    # greedy TOKENS match (the tp o-projection all-reduce reorders float
    # sums, so logits are close-not-equal; argmax is the contract)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_tp_sharded_kv_cache_halves_per_device(lm):
    from bigdl_tpu.parallel import MeshLayout
    mesh = MeshLayout(1, 1, 2).build_mesh(jax.devices()[:2])
    caches = init_kv_cache(lm, batch=2, max_len=32, mesh=mesh)
    for cache in caches:
        for arr in (cache["k"], cache["v"]):
            # head axis (2 heads) split exactly in half over tp
            assert len(arr.sharding.device_set) == 2
            shard_bytes = {s.data.nbytes for s in arr.addressable_shards}
            assert shard_bytes == {arr.nbytes // 2}


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_tp_sharded_engine_matches_single_device_and_keeps_sharding(lm):
    from bigdl_tpu.parallel import MeshLayout
    mesh = MeshLayout(1, 1, 2).build_mesh(jax.devices()[:2])
    prompts = _prompts(3, lo=3, hi=12, seed=14)
    with DecodeEngine(lm, slots=2, page=32, mesh=mesh) as eng:
        outs = [h.result(120.0)
                for h in [eng.submit(p, 5) for p in prompts]]
        caches = eng._caches
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(lm, p, 5))
    want = init_kv_cache(lm, 2, 32, caches[0]["k"].dtype, mesh=mesh)
    for got, ref in zip(caches, want):
        for n in "kv":
            # after three admissions and their steps: still heads over tp,
            # half the bytes on each device
            assert got[n].sharding.is_equivalent_to(ref[n].sharding, 4)
            assert {s.data.nbytes for s in got[n].addressable_shards} \
                == {got[n].nbytes // 2}


# ---------------------------------------------------------------------------
# trace + telemetry integration
# ---------------------------------------------------------------------------

def test_trace_event_gen_metadata_round_trips(tmp_path):
    path = str(tmp_path / "gen.trace")
    ev = TraceEvent(0.5, np.arange(4, dtype=np.int32), tenant="t",
                    priority=2, deadline_ms=100.0,
                    gen={"max_tokens": 8, "temperature": 0.0})
    write_trace(path, [ev, TraceEvent(0.1, np.ones(2, np.float32))])
    header, events = read_trace(path)
    assert header["count"] == 2
    assert events[0].gen == {"max_tokens": 8, "temperature": 0.0}
    assert events[1].gen is None  # non-generative events unchanged
    np.testing.assert_array_equal(events[0].payload,
                                  np.arange(4, dtype=np.int32))


def test_engine_records_gen_trace(lm, tmp_path):
    path = str(tmp_path / "rec.trace")
    prompt = _prompts(1, seed=10)[0]
    with DecodeEngine(lm, slots=1, page=8) as eng:
        eng.record_trace(path)
        eng.generate(prompt, 3, tenant="team-a")
        eng.stop_trace()
    _, events = read_trace(path)
    assert len(events) == 1 and events[0].tenant == "team-a"
    assert events[0].gen["max_tokens"] == 3
    np.testing.assert_array_equal(events[0].payload, prompt)


def test_http_generate_route_bit_matches_and_types_errors(lm):
    import json
    import sys
    import urllib.error
    import urllib.request

    import bigdl_tpu.nn as nn
    from bigdl_tpu.serve import InferenceServer
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import serve_http

    model = nn.Sequential().add(nn.Linear(4, 3)).build(jax.random.key(0))
    server = InferenceServer(model, example=np.zeros((4,), np.float32))
    server.start()
    engine = DecodeEngine(lm, slots=2, page=8).start()
    server.decode_engine = engine  # what main() --generate wires up
    httpd = serve_http.serve_forever(server, "127.0.0.1", 0)
    try:
        port = httpd.server_address[1]
        prompt = [3, 9, 21, 5]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"prompt": prompt, "max_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        resp = json.loads(urllib.request.urlopen(req, timeout=60).read())
        ref = _oracle(lm, np.asarray(prompt, np.int32), 5)
        assert resp["tokens"] == ref.tolist() and resp["generated"] == 5
        st = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/stats", timeout=10).read())
        assert st["decode"]["seqs_done"] == 1
        # typed rejection surfaces as HTTP 400, not a 500
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"prompt": [],
                                 "max_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
        assert exc.value.code == 400
    finally:
        httpd.shutdown()
        engine.stop()
        server.stop()


def test_decode_counter_track_promotes_to_report_section(lm):
    from bigdl_tpu.utils import telemetry
    bd = telemetry.phase_breakdown({"traceEvents": [
        {"ph": "C", "name": "serve.decode", "ts": 1.0,
         "args": {"tokens_per_s": 350.0, "fill": 0.75,
                  "cache_bytes_per_slot": 16384}},
    ]})
    assert bd["decode"]["tokens_per_s"] == 350.0
    assert bd["decode"]["fill"] == 0.75
    assert "decode:" in telemetry.format_report(bd)
    # and the live engine actually emits the track
    with DecodeEngine(lm, slots=1, page=8) as eng:
        eng.generate(_prompts(1, seed=11)[0], 2)
        st = eng.stats()
    assert st["tokens_per_s"] > 0 and st["cache_bytes_per_slot"] > 0


# ---------------------------------------------------------------------------
# admission in groups: a pass's prompts of one bucket share a call (ISSUE 42)
# ---------------------------------------------------------------------------

def _mixed(n, seed):
    """n requests over three prompt buckets (8, 16, 32) and output budgets
    that free the slots at different steps."""
    r = np.random.default_rng(seed)
    return [(r.integers(1, 64, size=int(r.choice([3, 7, 8, 9, 14, 20])))
             .astype(np.int32), int(r.integers(2, 9)), {})
            for _ in range(n)]


@pytest.mark.parametrize("slots,n", [(2, 7), (128, 60)])
def test_grouped_admission_yields_the_oracles_tokens(lm, slots, n):
    # whatever groups the passes form (two slots: none, a take that small
    # fills nothing; 128 slots: groups of up to four, programs filled up),
    # every request gets generate()'s row
    requests = _mixed(n, seed=50 + slots)
    eng = DecodeEngine(lm, slots=slots, page=64, queue_limit=64)
    handles = _run_queued(eng, requests)
    for (p, mt, _kw), h in zip(requests, handles):
        np.testing.assert_array_equal(h.result(1.0), _oracle(lm, p, mt))
    st = eng.stats()
    assert st["prefill_rows"] == n == st["seqs_done"]
    # some call carried several, where the engine is wide enough to group
    assert (st["prefill_steps"] < n) == (slots == 128)


def test_a_lone_request_is_called_in_the_pass_that_takes_it(lm):
    eng = DecodeEngine(lm, slots=128, page=64)
    assert eng._prefill_programs(8, 64) == (1, 4)      # a group program
    h = eng.submit(_prompts(1, seed=61)[0], 3)
    assert eng._tick()                      # the engine's own pass, by hand
    assert eng._group_target() == eng._take_cap() == 9
    st = eng.stats()
    # nothing waited for company: its prefill and its first step are called
    assert st["prefill_steps"] == st["prefill_rows"] == 1
    assert st["decode_steps"] == 1 and st["slot_steps_held"] == 0
    eng.queue.close(drain=True)
    while eng._tick():
        pass
    assert len(h.result(1.0)) == len(h.payload["prompt"]) + 3
    # two more arrive together on the idle engine: no more wait than slots
    # are free, so both go at once, in one call
    eng = DecodeEngine(lm, slots=128, page=64)
    for p in _prompts(2, lo=3, hi=8, seed=62):
        eng.submit(p, 3)
    eng._tick()
    st = eng.stats()
    assert (st["prefill_steps"], st["prefill_rows"]) == (1, 2)
    assert st["slot_steps_held"] == 0


def test_a_backlog_fills_groups_and_holds_no_more_than_its_share(lm):
    from bigdl_tpu.serve.decode import _HELD_SHARE
    # one bucket, 320 requests for 128 slots, budgets of 24-56 tokens: slots
    # come free a few a step with more requests waiting than slots free
    r = np.random.default_rng(71)
    requests = [(r.integers(1, 64, size=int(r.integers(5, 9)))
                 .astype(np.int32), int(r.integers(24, 57)), {})
                for _ in range(320)]
    eng = DecodeEngine(lm, slots=128, page=64, queue_limit=512)
    _run_queued(eng, requests)
    st = eng.stats()
    assert st["seqs_done"] == st["prefill_rows"] == 320
    assert st["prefill_rows"] / st["prefill_steps"] > 2.0
    # a take of n slots freed one after another holds (n - 1) / 2 on
    # average: the cap on a take is sized so that this stays under the share
    assert eng._take_cap() == eng._group_target() == 9
    held = st["slot_steps_held"] / (st["decode_steps"] * 128)
    assert 0 < held <= _HELD_SHARE, held
    assert eng._hold_until is None


def test_a_hold_ends_when_the_remaining_counts_said_it_would(lm):
    eng = DecodeEngine(lm, slots=128, page=64, queue_limit=256)
    eng._prefill_programs(8, 64)
    # 128 requests take every slot; one of them is short
    ps = _prompts(136, lo=4, hi=8, seed=81)
    eng.submit(ps[0], 3)
    for p in ps[1:128]:
        eng.submit(p, 30)
    eng._tick()
    assert eng.stats()["active"] == 128
    for p in ps[128:]:                      # eight more wait: a backlog
        eng.submit(p, 2)                    # (fewer than the cap's nine)
    eng._tick()                             # the short one's last call
    eng._tick()                             # one slot free, want 8: held
    assert eng._hold_until is not None and eng.slot_steps_held == 1
    # the next slots free 27 steps on: the hold is given up there and then,
    # with the one request that fits
    until = eng._hold_until
    assert until - eng.decode_steps >= 20
    while eng._hold_until is not None:
        eng._tick()
    assert eng.decode_steps <= until + 1 and eng.prefill_rows >= 129
    eng.queue.close(drain=True)
    while eng._tick():
        pass
    assert eng.stats()["seqs_done"] == 136


def test_a_burst_after_one_request_a_bucket_compiles_nothing(lm):
    eng = DecodeEngine(lm, slots=128, page=64, queue_limit=64)
    with eng:
        # what a deployment's warm-up does: one request a bucket, one at a
        # time; the engine compiles each bucket's group program with it
        for n in (5, 12):
            eng.submit(np.arange(1, n + 1, dtype=np.int32), 2).result(120.0)
        before, programs = eng.stats()["aot"], set(eng._exe)
        assert {k[1:3] for k in programs if k[0] == "prefill"} == {
            (1, 8), (4, 8), (1, 16), (4, 16)}
        burst = [eng.submit(p, 4) for p in _prompts(40, lo=3, hi=16, seed=91)]
        for h in burst:
            h.result(120.0)
        st = eng.stats()
    assert st["aot"] == before and set(eng._exe) == programs
    assert st["prefill_rows"] == 42 and st["prefill_steps"] < 30


def test_the_rows_of_a_bucket_follow_from_the_shapes(lm, monkeypatch):
    import bigdl_tpu.serve.decode as sd
    from bigdl_tpu.utils.flops import jaxpr_flops
    eng = DecodeEngine(lm, slots=256, page=64)
    # positions a call: the longest single prompt the engine must take
    assert eng._group_positions == 64 and eng._take_cap() == 17
    assert eng._prefill_programs(8, 64) == (1, 8)
    assert eng._prefill_programs(16, 64) == (1, 4)
    # a pair gets no program of its own: every program costs set-up
    assert eng._prefill_programs(32, 64) == (1,)
    assert eng._prefill_programs(64, 64) == (1,)
    # a bucket cut to a shorter cache counts as what is computed
    assert eng._prefill_programs(32, 16) == (1, 4)
    # no group is wider than half the requests a backlogged pass may wait
    # for: an engine of few slots holds none and compiles no group program
    assert DecodeEngine(lm, slots=128, page=64)._prefill_programs(8, 64) \
        == (1, 4)
    small = DecodeEngine(lm, slots=32, page=64)
    small._call_positions = None            # decided before any trace
    assert small._take_cap() == 3 and small._prefill_programs(8, 64) == (1,)
    # a call whose one row's arithmetic outweighs the weights it streams
    # does not group (the count is the one-row program's own); the budget
    # is in positions, and the memory guard is part of it
    positions, traced = eng._call_positions(8, 64)
    assert positions == eng._group_positions == 64
    flops = jaxpr_flops(traced.jaxpr)
    assert flops > 0 and eng._weight_bytes == sum(
        a.nbytes for a in jax.tree.leaves(lm.params))
    monkeypatch.setattr(sd, "_FLOPS_PER_WEIGHT_BYTE",
                        4.5 * flops / eng._weight_bytes)
    assert DecodeEngine(lm, slots=256, page=64)._prefill_programs(8, 64) \
        == (1, 4)
    monkeypatch.setattr(sd, "_FLOPS_PER_WEIGHT_BYTE", 0.0)
    wide = DecodeEngine(lm, slots=256, page=64)
    assert wide._prefill_programs(8, 64) == (1,)
    assert wide._group_target() == 1        # nothing groups: nothing is held
