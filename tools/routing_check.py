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
  ``logits_fn`` compares at), and over them each side's held flips and gaps;
* with ``--forced`` (a configuration with ``routed_logits_fn``) ``forced``:
  each side put where a served run is: its own choices forced into the
  float32 reference, its greedy tokens' gap read there, and the largest
  share of a layer's positions whose held choice is not the reference's own
  given the choices before (the cell's ``logit_gap`` and
  ``routing_disagree``); and, on the first seed, the same share for the
  program's router with a fault planted in it (``planted``: the selection
  bias left out; the fifth and the seventh best for the fifth and sixth).

    python3 tools/routing_check.py --workload dsv2.decode --seed 7 \\
        --rows 2 --length 1024      (or --workload nemo3.decode --forced)
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
    experts it chose: the model's own modules applied one after another
    (containers walked, every other module through its own ``apply``), with
    the router asked again on the input its layer saw.  Knows no model."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.containers import ConcatTable, Sequential
    from bigdl_tpu.parallel.expert import GatedMoE
    chosen, router = [], []

    def walk(m, p, s, x):
        if isinstance(m, Sequential):
            for mm, pp, ss in zip(m.modules, p, s):
                x = walk(mm, pp, ss, x)
            return x
        if isinstance(m, ConcatTable):
            return [walk(mm, pp, ss, x)
                    for mm, pp, ss in zip(m.modules, p, s)]
        if isinstance(m, GatedMoE):
            flat = x.reshape(-1, x.shape[-1])
            _w, idx = m.route(p, flat)
            hot = jnp.zeros((idx.shape[0], m.num_experts), bool)
            chosen.append(hot.at[jnp.arange(idx.shape[0])[:, None],
                                 idx].set(True).reshape(
                x.shape[:-1] + (m.num_experts,)))
            router.append(jnp.matmul(
                flat.astype(jnp.float32), p["gate"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST).reshape(
                    chosen[-1].shape))
        return m.apply(p, s, x)[0]

    x = walk(model, params, state, toks)
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
    ap.add_argument("--seeds", default=None,
                    help="several seeds, one line each, in one process")
    ap.add_argument("--forced", action="store_true")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--length", type=int, default=1024)
    ap.add_argument("--margins", default="0.02,0.05,0.07,0.1,0.2")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.Cell(args.workload)
    cm, cfg = cell.cfg_mod, dict(cell.cfg)
    if args.rehearse:
        cfg.update(cell.cfg.get("rehearse", {}))
    cm.set_policy(cfg)
    model = cm.build_model(cfg)
    seeds = [int(x) for x in args.seeds.split(",")] if args.seeds \
        else [args.seed]
    for n, seed in enumerate(seeds):
        args.seed = seed
        one_seed(args, cm, cfg, model, planted=args.forced and n == 0)
    return 0


def one_seed(args, cm, cfg, model, planted: bool) -> None:
    import jax
    import jax.numpy as jnp
    from benchmark import harness
    key = jax.random.key(args.seed)
    params, state = harness.program_weights(cm, cfg, model, key)
    toks = jnp.asarray(np.random.default_rng(args.seed).integers(
        0, cfg["vocab_size"], (args.rows, args.length)).astype(np.int32))
    logp, mine, mine_router = (np.asarray(a) for a in jax.jit(
        lambda p, s, t: program_choices(model, p, s, t))(params, state, toks))
    logp = logp.astype(np.float32)
    del params
    p0 = jax.jit(lambda k: cm.init_params(cfg, k))(key)
    ref = cm.ref            # the configuration's own plain reference
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
    for prec in cfg["control_precisions"] + ([] if args.forced else ["bf16"]):
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
    if args.forced:
        k = ref.sizes(cfg)["k"]
        forced_fn = jax.jit(cm.routed_logits_fn(cfg, "f32"))

        def forced(hot):
            idx = np.argsort(~hot, axis=-1, kind="stable")[..., :k]
            got, _made, dis = forced_fn(p0, toks, jnp.asarray(
                idx.astype(np.int32)))
            return np.asarray(got), np.asarray(dis)

        out["forced"] = {}
        for name, (scores, c, _router) in sides.items():
            got, dis = forced(c)
            g = got.max(-1) - np.take_along_axis(
                got, scores.argmax(-1)[..., None], -1)[..., 0]
            out["forced"][name] = {
                "gap": gap_stats(g), "disagree_max": float(dis.max()),
                "disagree_by_layer": [round(float(x), 5)
                                      for x in dis.max(axis=0)]}
        if planted:
            # the program's own router logits, a fault planted in the rule
            bias = np.stack([np.asarray(p["select_bias"], np.float32)
                             for _norm, p in p0[1:-2] if "gate" in p])
            score = 1 / (1 + np.exp(-mine_router))
            rank = lambda key: np.argsort(-key, axis=-1, kind="stable")

            def hot(idx):
                chosen = np.zeros(score.shape, bool)
                np.put_along_axis(chosen, idx, True, -1)
                return chosen

            fair = rank(score + bias[None, :, None, :])
            faults = {
                "bias_left_out": hot(rank(score)[..., :k]),
                "seventh_for_sixth": hot(np.concatenate(
                    [fair[..., :k - 1], fair[..., k:k + 1]], -1))}
            out["planted"] = {
                name: {"disagree_max": float(dis.max()),
                       "disagree_smallest_by_layer": [
                           round(float(x), 5) for x in dis.min(axis=0)]}
                for name, c in faults.items() for dis in [forced(c)[1]]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
