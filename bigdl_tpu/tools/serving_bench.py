"""Serving microbench: KV-cache decode vs full re-forward, float vs int8.

Run on a TPU, in one process (one JSON line per config, bench.py conventions):

    python -m bigdl_tpu.tools.serving_bench [--d-model 512 --num-layers 8
        --max-len 1024 --batch 8 --num-tokens 64]

Measures tokens/sec for:
  full_fwd   — transformer_lm.greedy_generate (full [B, L] forward/token)
  kv_cache   — models/decode.cached_generate ([B, 1] step + cache)
  kv_int8    — cached decode on the quantize()-d model

The interesting ratios: kv_cache/full_fwd (the O(L) vs O(L^2) win) and
kv_int8/kv_cache (weight-bandwidth relief in the memory-bound regime).
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--d-model", type=int, default=512)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--num-layers", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--max-len", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--num-tokens", type=int, default=64)
    p.add_argument("--skip-full", action="store_true",
                   help="full re-forward is O(L^2)/token — skip when slow")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from ..common import DTypePolicy, set_policy
    from ..models import TransformerLM, cached_generate
    from ..models.transformer_lm import greedy_generate
    from ..quantize import quantize

    import jax.numpy as jnp
    from ..common import get_policy
    prev_policy = get_policy()
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    try:
        model = TransformerLM(
            vocab_size=args.vocab, max_len=args.max_len,
            d_model=args.d_model, num_heads=args.num_heads,
            num_layers=args.num_layers).build(jax.random.key(0))
        # 1-token prompt: the KV paths then run exactly num_tokens steps,
        # matching full_fwd's loop count — otherwise prompt prefill would
        # be charged against generated tokens and skew the ratio
        prompt = np.ones((args.batch, 1), np.int32)

        def bench(name, fn):
            fn()  # compile + warm
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            dt = min(times)  # bench.py convention: best of N, noise-robust
            toks = args.batch * args.num_tokens
            return {"path": name, "tokens_per_sec": round(toks / dt, 1),
                    "seconds": round(dt, 4)}

        results = []
        if not args.skip_full:
            results.append(bench("full_fwd", lambda: greedy_generate(
                model, prompt, args.num_tokens, args.max_len)))
        results.append(bench("kv_cache", lambda: cached_generate(
            model, prompt, args.num_tokens, args.max_len)))
        qmodel = quantize(model)
        results.append(bench("kv_int8", lambda: cached_generate(
            qmodel, prompt, args.num_tokens, args.max_len)))
    finally:
        set_policy(prev_policy)

    out = {"metric": "serving_decode_tokens_per_sec",
           "config": {k: getattr(args, k)
                      for k in ("d_model", "num_heads", "num_layers",
                                "vocab", "max_len", "batch", "num_tokens")},
           "device": jax.devices()[0].device_kind,
           "results": results}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
