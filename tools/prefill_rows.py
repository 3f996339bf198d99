#!/usr/bin/env python3
"""What does one call of `DecodeEngine`'s prefill cost by (rows, bucket)?

An admission prefill streams every weight once a call, whatever it carries,
so several prompts in one call share that pass; what a row adds is its own
arithmetic.  This builds one decode cell of the benchmark at its real sizes
(seeded weights, the traffic's slots and cache) on the TPU, and times the
engine's own prefill executables (a time is a device's: the tool exits 1
where the first device jax finds is not of `--platform`, `tpu` unless the
CPU is asked for by name to rehearse the code, and every line carries the
device it was read on): for each prompt bucket the
traffic reaches and each row count up to `--rows` (and `--positions` a
call), `--calls` calls one after
another on seeded prompts that fill their bucket, the wall clock over them
divided by their number (the device runs them back to back: the host's
dispatch hides behind the call in flight).  One JSON line a (rows, bucket):

* ``platform``, ``device_kind``: the device the line was read on;
* ``call_ms``: one call; ``row_ms``: that over its rows;
* ``flops_per_weight_byte``: the call's multiply-adds, rows x what the
  engine's rule counts from the one-row program's trace
  (`DecodeEngine._call_positions`), over the bytes of the weights: what
  `serve/decode.py` `_FLOPS_PER_WEIGHT_BYTE` is held against;
* ``ladder``: the rows the engine itself compiled for the bucket;
* ``memory_peak_bytes``: the device's peak so far (in use + reserved);
  left out where the device reports no memory statistics.

    python3 tools/prefill_rows.py --workload nemo3.decode --rows 8
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=8,
                    help="time 1, 2, 4, ... up to this many rows a call")
    ap.add_argument("--positions", type=int, default=4096,
                    help="and no more than this many positions (rows x "
                         "bucket) a call")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="the device the times are read on; cpu only to "
                         "rehearse the code: its times are no device's")
    args = ap.parse_args()

    if args.platform == "cpu":
        from bigdl_tpu.utils.platform import force_cpu
        force_cpu()
    import jax
    import jax.numpy as jnp
    from benchmark import harness
    from bigdl_tpu import Engine
    from bigdl_tpu.serve import DecodeEngine
    from bigdl_tpu.serve.decode import _prompt_bucket
    from bigdl_tpu.utils.flops import jaxpr_flops

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"prefill_rows: asked for {args.platform!r}, jax found "
              f"{dev.platform!r} ({dev.device_kind}); nothing was timed",
              file=sys.stderr)
        return 1
    cell = harness.Cell(args.workload)
    cm, cfg, tr = cell.cfg_mod, dict(cell.cfg), dict(cell.traffic)
    if args.rehearse:
        cfg.update(cell.cfg.get("rehearse", {}))
        tr.update(cell.traffic.get("rehearse", {}))
    Engine.init()
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    model.attach(*harness.program_weights(cm, cfg, model,
                                          jax.random.key(args.seed)))
    # the buckets between the traffic's shortest and longest prompt
    lo, hi = (_prompt_bucket(int(n)) for n in tr["prompt_len"])
    buckets = [lo << i for i in range((hi // lo).bit_length())]
    eng = DecodeEngine(model, slots=tr["slots"], page=tr["page"],
                       max_len=tr["max_len"], queue_limit=tr["queue_limit"])
    eng._ensure_cache(tr["max_len"], idle=True)
    L = eng._cache_len
    rng = np.random.default_rng(args.seed + 1)
    for pb in buckets:
        ladder = eng._prefill_programs(pb, L)
        P = min(pb, L)
        row_flops = jaxpr_flops(eng._call_positions(pb, L)[1].jaxpr)
        rows = 1
        while rows <= min(args.rows, eng.slots, max(args.positions // P, 1)):
            exe = eng._prefill_exe(rows, pb, L)
            toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (rows, P))
                               .astype(np.int32))
            slot = jnp.arange(rows, dtype=jnp.int32)
            t0 = jnp.full(rows, P, jnp.int32)
            tokens = eng._tokens
            for timed in (False, True):        # once to warm, then timed
                t = time.perf_counter()
                for _ in range(args.calls if timed else 1):
                    _lg, tokens, eng._caches, _rep = exe(
                        eng._params, eng._state, eng._caches, tokens, toks,
                        slot, t0)
                jax.block_until_ready(tokens)
                call_s = (time.perf_counter() - t) / args.calls
            line = {
                "workload": args.workload, "platform": dev.platform,
                "device_kind": dev.device_kind, "bucket": pb, "rows": rows,
                "call_ms": round(call_s * 1e3, 3),
                "row_ms": round(call_s * 1e3 / rows, 3),
                "flops_per_weight_byte": round(
                    rows * row_flops / eng._weight_bytes, 1),
                "ladder": list(ladder)}
            stats = dev.memory_stats()
            if stats:
                line["memory_peak_bytes"] = (
                    stats.get("peak_bytes_in_use", 0)
                    + stats.get("peak_bytes_reserved", 0))
            print(json.dumps(line), flush=True)
            rows *= 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
