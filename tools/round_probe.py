#!/usr/bin/env python3
"""Does the plain references' bfloat16 mode round on this device?

``benchmark/reference/common.py`` rounds operands and results by a cast to
bfloat16 and back.  A compiler may drop such a pair.  This prints, on the
device jax finds, the relative rms difference between the references' bf16
and f32 results (one product, a gated MLP, the same inside ``lax.map``) and
between the bf16 product and a host emulation of the rounding: about 0.002
and 0 where the rounding is kept (the CPU), 0 and 0.002 where it is dropped
(a TPU v5e; PERF.md Open question 22).

    python3 tools/round_probe.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from benchmark.reference.common import matmul
from benchmark.reference import deepseek_v2_share4 as ref
k = jax.random.split(jax.random.key(0), 4)
x = jax.random.normal(k[0], (4, 256, 5120), jnp.float32)
bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
w1, w2 = (bf(0.02 * jax.random.normal(k[i], (5120, 1536))) for i in (1, 2))
w3 = bf(0.02 * jax.random.normal(k[3], (1536, 5120)))
rel = lambda a, b: float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))
one = lambda p: jax.jit(lambda x: matmul(x, w1, p))(x[0])
mlp = lambda p: jax.jit(lambda x: ref.gated(x, w1, w2, w3, p))(x[0])
mapped = lambda p: jax.jit(lambda x: jax.lax.map(
    lambda r: ref.gated(r, w1, w2, w3, p), x))(x)
xr = np.asarray(bf(x[0]), np.float64)
host = (xr @ np.asarray(w1, np.float64)).astype(np.float32)
host = np.asarray(bf(jnp.asarray(host)))
print(json.dumps({"device": jax.devices()[0].device_kind,
    "one_product_bf16_vs_f32": rel(one("bf16"), one("f32")),
    "one_product_bf16_vs_host_emulation": rel(one("bf16"), jnp.asarray(host)),
    "gated_bf16_vs_f32": rel(mlp("bf16"), mlp("f32")),
    "gated_in_map_bf16_vs_f32": rel(mapped("bf16"), mapped("f32"))}))
