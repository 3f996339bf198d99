#!/usr/bin/env python3
"""Write out the lowered text of the decode cells' two programs, to compare
a change with its parent before any chip run.

A change to code that several models share (``models/decode.py``, the
containers, ``nn/module.py``, the recurrent layers' helpers) can alter the
program of a cell whose model it never names; the tests of that model still
pass, and the cell's numbers move.  This lowers ``decode_step`` and
``decode_prefill`` (one prompt a call, and two) of each decode cell's
configuration at its rehearse size, on the CPU, built as ``DecodeEngine``
builds them, and writes the text one file a program.  Run it from the root
of each checkout and compare:

    python3 tools/lower_cells.py /root/scratch/lowered/change
    (cd _parent && python3 tools/lower_cells.py /root/scratch/lowered/parent)
    diff -r /root/scratch/lowered/parent /root/scratch/lowered/change

(a parent that lacks this file: copy it there; it reads nothing but
``BENCHMARK.json`` and the harness).  No difference means the same program
up to the compiler; a difference names the cell and the program to look at.
Cells are every workload whose traffic has ``slots``, or those named.
"""

import os
import sys

sys.path.insert(0, os.getcwd())
os.environ["JAX_PLATFORMS"] = "cpu"

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402

from benchmark import harness                          # noqa: E402
from bigdl_tpu.models import decode as kv              # noqa: E402
from bigdl_tpu.serve.decode import _with_tokens        # noqa: E402


def lowered(cell) -> dict:
    """{program: text} of one decode cell at its rehearse size."""
    cfg = dict(cell.cfg, **cell.cfg.get("rehearse", {}))
    traffic = dict(cell.traffic, **cell.traffic.get("rehearse", {}))
    cell.cfg_mod.set_policy(cfg)
    model = cell.cfg_mod.build_model(cfg)
    params, state = jax.eval_shape(model.init, jax.random.key(0))
    slots = traffic["slots"]
    caches = kv.cache_avals(model, slots, traffic["max_len"], jnp.float32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(p, s, c, tokens, feed):
        return _with_tokens(*kv._slot_step(
            model, p, s, jnp.where(feed[1] >= 0, feed[1], tokens), c,
            feed[0]))

    def prefill(p, s, c, tokens, toks, slot, t0):
        logits, token, c, rep = _with_tokens(
            *kv._prefill(model, p, s, toks, c, slot, t0))
        return logits, tokens.at[slot].set(token, mode="drop"), c, rep

    texts = {"step": jax.jit(step, donate_argnums=(2,)).lower(
        params, state, caches, i32(slots), i32(2, slots)).as_text()}
    for rows in (1, 2):
        texts[f"prefill{rows}"] = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, state, caches, i32(slots), i32(rows, 16), i32(rows),
            i32(rows)).as_text()
    return texts


def main(argv) -> int:
    out, names = argv[0], argv[1:]
    os.makedirs(out, exist_ok=True)
    bench = harness.load_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"))
    for w in bench["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = harness.Cell(w["name"])
        if "slots" not in cell.traffic:
            continue
        for program, text in lowered(cell).items():
            with open(os.path.join(out, f"{w['name']}.{program}.txt"),
                      "w") as f:
                f.write(text)
            print(w["name"], program, len(text))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
