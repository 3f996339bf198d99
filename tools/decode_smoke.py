#!/usr/bin/env python
"""Continuous-batching decode smoke: the throughput claim, gated.

End-to-end drill for the generative serving layer (serve/decode.py,
serve/batcher.DecodeQueue, serve/tracefile gen events — docs/serving.md
"Generative decode"), exit-coded, ONE JSON line:

  1. **trace round-trip** — a mixed-length generation workload (short
     4-token completions interleaved with long 64-token ones, per-event
     ``gen`` metadata) is written through the recordio trace format and
     read back (CRC-verified) before replay.
  2. **bit-match** — the trace replays against a continuous-batching
     ``DecodeEngine``; every sequence's greedy output must BIT-match
     the offline ``cached_generate`` oracle (models/decode.py).  This
     run also pays all compiles, so the timed runs below are warm.
  3. **continuous vs static** — the same trace replays twice more,
     warm: once against continuous admission (sequences join/leave per
     step), once against ``admission='batch'`` (run-to-completion
     static batching, the pre-continuous baseline).  The SLO is
     self-calibrating — per-sequence deadline (time-to-last-token) =
     1.7x the slowest CONTINUOUS sequence, so the gate tracks machine
     speed instead of guessing it; the static run gets that deadline
     armed in the engine (late queue entries shed typed).  Continuous
     must win STRICTLY on both tokens/s and SLO attainment — finished
     rows in a static batch waste device steps, and the schedule shows
     it.
  4. **steady state** — a SECOND process (same shared AOT cache dir)
     serves a bucket-covering workload and must report ZERO fresh
     lowers and ZERO cache misses: every (slots, cache-page) and
     (prompt-bucket, cache-page) executable warm-starts from disk.
     Prefill and decode must also have emitted SEPARATE compile cards.

Pacing: ``min_step_s`` pins the per-tick floor (6 ms), so the
continuous-vs-static comparison is a schedule property, not a CPU-load
coin flip (the scale_smoke.py discipline).

A CPU drill (``--platform`` defaults to cpu); safe anywhere (tiny
model, seconds of wall clock, no accelerator needed).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

#: deterministic per-tick pacing floor (seconds) — the capacity lever
#: that makes continuous-vs-static a schedule property
MIN_STEP_S = 0.006
SLOTS = 4
PAGE = 16
SHORT = {"t0": 5, "max_tokens": 4}
LONG = {"t0": 9, "max_tokens": 64}
#: SLO calibration margin over the slowest continuous-run sequence
DEADLINE_MARGIN = 1.7


def _model():
    from bigdl_tpu.models.transformer_lm import TransformerLM
    m = TransformerLM(vocab_size=128, max_len=256, d_model=32,
                      num_heads=2, num_layers=2)
    m.build()
    return m


def _workload(np):
    """16 sequences, 4 arrival groups of 4: one all-short group first
    (the early ticks must exercise the SMALL cache page), then three
    groups led by a long sequence (they force the page grow and, under
    static batching, hold their group hostage for ~64 ticks)."""
    from bigdl_tpu.serve.tracefile import TraceEvent
    events = []
    rng = np.random.default_rng(7)
    kinds = ["S", "S", "S", "S", "L", "S", "S", "S",
             "L", "S", "S", "S", "L", "S", "S", "S"]
    tenants = ["team-a", "team-b"]
    for i, kind in enumerate(kinds):
        spec = LONG if kind == "L" else SHORT
        prompt = rng.integers(1, 128, size=spec["t0"]).astype(np.int32)
        # a 50 ms gap before the first long: the all-short prefix must
        # finish its small-page ticks before the grow
        dt = 0.0 if i == 0 else (0.05 if i == 4 else 0.002)
        events.append(TraceEvent(
            dt, prompt, tenant=tenants[i % 2], priority=i % 3,
            gen={"max_tokens": spec["max_tokens"], "temperature": 0.0,
                 "top_k": 0}))
    return events


def _mk_submit(np, eng, deadline_ms=None):
    def submit(e):
        gen = e.gen or {}
        return eng.submit(np.asarray(e.payload, np.int32),
                          int(gen.get("max_tokens", 16)),
                          deadline_ms=deadline_ms,
                          tenant=e.tenant, priority=e.priority,
                          temperature=float(gen.get("temperature", 0.0)),
                          top_k=int(gen.get("top_k", 0)))
    return submit


def _run(np, model, events, admission, deadline_ms=None):
    """Replay the trace against a fresh engine; returns (outcomes,
    engine stats, tokens/s over the run's wall clock)."""
    from bigdl_tpu.serve import DecodeEngine
    from bigdl_tpu.serve.tracefile import replay, resolve_outcomes
    eng = DecodeEngine(model, slots=SLOTS, page=PAGE,
                       admission=admission, min_step_s=MIN_STEP_S)
    t0 = time.perf_counter()
    with eng:
        outcomes = replay(events, _mk_submit(np, eng, deadline_ms),
                          speed=1.0)
        resolve_outcomes(outcomes, timeout=120.0)
        wall = time.perf_counter() - t0
        st = eng.stats()
    return outcomes, st, st["tokens_out"] / max(wall, 1e-9)


def _child(cache_dir: str) -> int:
    """Second-process steady state: serve a bucket-covering workload
    through the SHARED AOT cache and report the ledger — the parent
    asserts zero fresh lowers / zero misses."""
    import numpy as np
    from bigdl_tpu.serve import DecodeEngine
    from bigdl_tpu.utils import aot
    model = _model()
    rng = np.random.default_rng(11)
    eng = DecodeEngine(model, slots=SLOTS, page=PAGE,
                       min_step_s=MIN_STEP_S)
    with eng:
        # two shorts first (small-page buckets), then a long (page
        # grow) + shorts at the grown page — the same bucket set the
        # parent warmed, in the same order
        for spec in (SHORT, SHORT):
            eng.generate(rng.integers(1, 128, size=spec["t0"]),
                         spec["max_tokens"], timeout=60)
        hs = [eng.submit(rng.integers(1, 128, size=spec["t0"]),
                         spec["max_tokens"])
              for spec in (LONG, SHORT, SHORT)]
        for h in hs:
            h.result(120)
        st = eng.stats()
    print(json.dumps({"aot": st["aot"], "tokens_out": st["tokens_out"],
                      "cache_dir": cache_dir}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu",
                    help="jax platform (default cpu: the steady-state leg "
                         "starts a second process, and a chip belongs to "
                         "one)")
    ap.add_argument("--cache-dir", default=None,
                    help="shared AOT cache dir (default: a fresh "
                         "tempdir)")
    ap.add_argument("--child", action="store_true",
                    help="steady-state probe mode (second process)")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        try:
            jax.config.update("jax_platforms", args.platform)
        except RuntimeError:
            pass

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="decode_aot_")
    os.environ["BIGDL_TPU_AOT_CACHE"] = cache_dir
    os.environ.setdefault("BIGDL_TPU_COMPILE_CARDS", "1")

    if args.child:
        return _child(cache_dir)

    import numpy as np
    from bigdl_tpu.models.decode import cached_generate
    from bigdl_tpu.serve.tracefile import read_trace, write_trace
    from bigdl_tpu.utils import hlostats

    t_all = time.perf_counter()
    model = _model()
    rec: dict = {"metric": "decode_smoke", "slots": SLOTS, "page": PAGE,
                 "min_step_ms": MIN_STEP_S * 1e3}

    # 1. trace round-trip (CRC-framed recordio, gen metadata preserved)
    trace_path = os.path.join(tempfile.mkdtemp(prefix="decode_trace_"),
                              "gen.trace")
    events = _workload(np)
    write_trace(trace_path, events, meta={"kind": "decode-smoke"})
    header, events = read_trace(trace_path)
    rec["recorded"] = header["count"]
    roundtrip_ok = len(events) == 16 and all(
        e.gen and "max_tokens" in e.gen for e in events)

    # 2. continuous warm-up run: bit-match vs the offline oracle (and
    #    every executable lowered+compiled+stored exactly once here)
    outcomes, st_cal, _tps = _run(np, model, events, "continuous")
    bit_match = True
    for o in outcomes:
        got = o.handle.result(1.0)
        gen = o.event.gen
        ref = cached_generate(model, np.asarray(o.event.payload, np.int32),
                              gen["max_tokens"],
                              max_len=len(o.event.payload)
                              + gen["max_tokens"])
        if not np.array_equal(np.asarray(got), ref):
            bit_match = False
    rec["bit_match"] = bit_match
    rec["warmup"] = {"cache_grows": st_cal["cache_grows"],
                     "prefill_steps": st_cal["prefill_steps"],
                     "decode_steps": st_cal["decode_steps"]}

    # 3. warm continuous run calibrates the SLO; static run gets the
    #    calibrated deadline armed in the engine
    from bigdl_tpu.serve.tracefile import slo_report
    cont_out, cont_st, cont_tps = _run(np, model, events, "continuous")
    lat_max = max(o.latency_s for o in cont_out)
    deadline_ms = max(DEADLINE_MARGIN * lat_max * 1e3, 100.0)
    rec["deadline_ms"] = round(deadline_ms, 1)
    stat_out, stat_st, stat_tps = _run(np, model, events, "batch",
                                       deadline_ms=deadline_ms)
    cont_rep = slo_report(cont_out, default_deadline_ms=deadline_ms)
    stat_rep = slo_report(stat_out, default_deadline_ms=deadline_ms)
    rec["continuous"] = {"tokens_per_s": round(cont_tps, 1),
                         "attainment": cont_rep["attainment"],
                         "served": cont_rep["served"],
                         "shed": cont_rep["shed"],
                         "p99_ms": cont_rep.get("p99_ms"),
                         "fill_steps": cont_st["decode_steps"]}
    rec["static"] = {"tokens_per_s": round(stat_tps, 1),
                     "attainment": stat_rep["attainment"],
                     "served": stat_rep["served"],
                     "shed": stat_rep["shed"],
                     "p99_ms": stat_rep.get("p99_ms"),
                     "fill_steps": stat_st["decode_steps"]}

    # separate prefill/decode compile cards (hlostats armed above)
    labels = set(hlostats.ledger())
    cards_ok = "decode.prefill" in labels and "decode.step" in labels

    # 4. second-process steady state through the shared AOT cache
    env = dict(os.environ, BIGDL_TPU_AOT_CACHE=cache_dir)
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--cache-dir", cache_dir]
    if args.platform:
        cmd += ["--platform", args.platform]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=300, env=env)
    child = {}
    if proc.returncode == 0:
        try:
            child = json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            child = {}
    rec["steady_state"] = {"rc": proc.returncode,
                           "aot": child.get("aot"),
                           "tokens_out": child.get("tokens_out")}
    child_aot = child.get("aot") or {}

    checks = {
        "recorded_trace_roundtrips": roundtrip_ok,
        "greedy_bit_matches_oracle": bit_match,
        "tokens_per_s_strictly_higher": cont_tps > stat_tps,
        "attainment_strictly_higher":
            (cont_rep["attainment"] or 0) > (stat_rep["attainment"] or 0),
        "separate_compile_cards": cards_ok,
        "steady_state_zero_fresh_lowers":
            proc.returncode == 0 and child_aot.get("lowers") == 0
            and child_aot.get("misses") == 0,
    }
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    rec["wall_s"] = round(time.perf_counter() - t_all, 1)
    print(json.dumps(rec))
    sys.stdout.flush()
    if not rec["ok"] and proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:])
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
