#!/usr/bin/env python3
"""How often does the program choose other experts than the plain reference,
and what does that do to the logits?

Routing is discrete: where a chosen expert and one left out (or the last
kept group and the first left out) score nearly alike, the rounding of the
activations that reach the router decides, and a bfloat16 program and a
float32 reference part ways for that token.  This runs one configuration of
the benchmark with routed experts at its real sizes, on the device jax
finds, over seeded tokens: the program's full forward (its own dtype
policy), the reference in float32, in each control precision and with
bfloat16 operands, and prints one JSON line with

* ``router_noise``: for each expert layer, the standard deviation of the
  difference between a side's router logits and the float32 reference's
  (each token's mean difference taken out: only differences of logits
  decide), over all positions and over those where no earlier layer's held
  choice differs;
* ``flips``: for each expert layer, the share of tokens whose set of chosen
  experts differs from the float32 reference's, and ``held_flips`` the share
  whose chosen *held* experts differ (only those change this share's output);
* ``gap``: over all positions, the gap by which the float32 reference's
  logit of the greedy token lies under its best (the number the benchmark's
  ``logit_gap`` takes the maximum of), as max, quantiles and counts, for all
  positions and for those where no layer's held choice flipped;
* ``decided_by``: for each width in ``--margins``, the share of positions
  whose choice among the held experts the reference decides by that width in
  every layer (its ``held_choice_decided``, what the configuration's
  ``logits_fn`` compares at), and over them each side's held flips and gaps.

    python3 tools/routing_check.py --workload dsv2.decode --seed 7 \\
        --rows 2 --length 1024
"""

import argparse
import json
import os
import sys

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def program_choices(model, params, state, toks):
    """The program's log-probabilities and, for each ``GatedMoE`` layer, the
    experts it chose: the model's own modules applied one after another,
    with the router asked again on the input its layer saw."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.parallel.expert import GatedMoE
    x, chosen, router = toks, [], []
    for m, p, s in zip(model.modules, params, state):
        ffn = m.modules[1].modules[0].modules[0].modules[1] \
            if hasattr(m, "modules") and len(m.modules) == 2 else None
        if isinstance(ffn, GatedMoE):
            attn_res, mlp_res = m.modules
            mid, _ = attn_res.apply(p[0], s[0], x)
            norm = mlp_res.modules[0].modules[0].modules[0]
            pn = p[1][0][0]
            xn, _ = norm.apply(pn[0], {}, mid)
            flat = xn.reshape(-1, xn.shape[-1])
            _w, idx = ffn.route(pn[1], flat)
            hot = jnp.zeros((idx.shape[0], ffn.num_experts), bool)
            chosen.append(hot.at[jnp.arange(idx.shape[0])[:, None],
                                 idx].set(True).reshape(
                xn.shape[:-1] + (ffn.num_experts,)))
            router.append(jnp.matmul(
                flat.astype(jnp.float32), pn[1]["gate"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST).reshape(
                    chosen[-1].shape))
        x, _ = m.apply(p, s, x)
    # [B, layers, T, routed] each
    return x, jnp.stack(chosen, axis=1), jnp.stack(router, axis=1)


def gap_stats(gaps, keep=None) -> dict:
    g = gaps if keep is None else gaps[keep]
    if g.size == 0:
        return {"n": 0}
    return {"n": int(g.size), "max": float(g.max()),
            "p99": float(np.quantile(g, 0.99)),
            "p90": float(np.quantile(g, 0.9)),
            "median": float(np.median(g)),
            "over_0.1": int((g > 0.1).sum()), "over_0.3": int((g > 0.3).sum()),
            "over_1": int((g > 1.0).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--length", type=int, default=1024)
    ap.add_argument("--margins", default="0.02,0.05,0.07,0.1,0.2")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark import harness
    cell = harness.Cell(args.workload)
    cm, cfg = cell.cfg_mod, dict(cell.cfg)
    if args.rehearse:
        cfg.update(cell.cfg.get("rehearse", {}))
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    key = jax.random.key(args.seed)
    params, state = harness.program_weights(cm, cfg, model, key)
    toks = jnp.asarray(np.random.default_rng(args.seed).integers(
        0, cfg["vocab_size"], (args.rows, args.length)).astype(np.int32))
    logp, mine, mine_router = (np.asarray(a) for a in jax.jit(
        lambda p, s, t: program_choices(model, p, s, t))(params, state, toks))
    logp = logp.astype(np.float32)
    del params
    p0 = jax.jit(lambda k: cm.init_params(cfg, k))(key)
    from benchmark.reference import deepseek_v2_share4 as ref
    widths = tuple(float(e) for e in args.margins.split(","))

    def run(prec):
        out, seen = jax.jit(lambda p, t: ref.logits(
            cfg, p, t, prec, widths=widths))(p0, toks)
        return np.asarray(out), {k: np.asarray(v) for k, v in seen.items()}

    want, theirs = run("f32")
    first, count = ref.sizes(cfg)["held"]
    best = want.max(-1)
    gap_of = lambda scores: best - np.take_along_axis(
        want, scores.argmax(-1)[..., None], -1)[..., 0]
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "positions": int(best.size), "router_noise": {}, "flips": {},
           "held_flips": {}, "gap": {}, "decided_by": {},
           "logp_diff_max": float(np.abs(
               logp - np.asarray(jax.nn.log_softmax(want))).max())}
    sides = {"program": (logp, mine, mine_router)}
    for prec in cfg["control_precisions"] + ["bf16"]:
        scores, seen = run(prec)
        sides[prec] = (scores, seen["chosen"], seen["router"])
    for name, (scores, c, router) in sides.items():
        f = (c != theirs["chosen"]).any(-1)               # [B, layers, T]
        fh = (c != theirs["chosen"])[..., first:first + count].any(-1)
        out["flips"][name] = [round(float(x), 5)
                              for x in f.mean(axis=(0, 2))]
        out["held_flips"][name] = [round(float(x), 5)
                                   for x in fh.mean(axis=(0, 2))]
        d = router - theirs["router"]
        d = d - d.mean(-1, keepdims=True)
        # a layer's input is the reference's own up to rounding only where
        # no earlier layer's held choice differs at that position
        clean = np.cumsum(fh, axis=1) - fh == 0
        out["router_noise"][name] = [
            {"all": round(float(d[:, i].std()), 5),
             "no_earlier_held_flip": round(float(
                 d[:, i][clean[:, i]].std()), 5)}
            for i in range(d.shape[1])]
        g = gap_of(scores)
        out["gap"][name] = {"all": gap_stats(g),
                            "no_held_flip": gap_stats(g, ~fh.any(1)),
                            "some_held_flip": gap_stats(g, fh.any(1))}
        for n, w in enumerate(widths):
            clear = theirs["decided"][:, :, n].all(axis=1)      # [B, T]
            row = out["decided_by"].setdefault(str(w), {
                "decided_share": round(float(clear.mean()), 4)})
            row[name] = {"held_flipped_positions":
                         int((fh.any(1) & clear).sum()),
                         "gap": gap_stats(g, clear)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
