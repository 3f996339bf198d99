"""BatchNorm stat-computation experiment for the ResNet-50 MFU push.

The round-3 chip run of 2026-07-31 (record removed in PR 22; batch 256
bf16): fwd-eval hit 0.61 MFU and eval-mode grad 0.45, but training-mode BN
batch-stats machinery cost ~27ms of the 108ms step, capping train MFU at
~0.34 vs the 0.45 target (BASELINE.md).  This tool times stat-computation
variants through the whole resnet50 grad so the winner can be promoted into
nn/normalization.py with evidence.  Run ON A REAL TPU, one variant per
process and no parent that has touched jax (most variants have never been
measured):

    python -m bigdl_tpu.tools.bn_experiment [baseline dtype_arg]

Variants:
  baseline   — astype(f32) then two fused reductions (current nn code)
  dtype_arg  — jnp.mean(..., dtype=f32) accumulation without the explicit
               upcast (tests whether XLA materializes the f32 copy)
  custom_vjp — hand-written fused BN backward (2 read passes + 1 write:
               the canonical dx = scale*(dy - mean(dy) - xhat*mean(dy*xhat))
               formula) instead of autodiff through the stat graph
  remat_conv — baseline BN + selective rematerialization: save only conv
               outputs + BN stats across fwd/bwd, recompute all elementwise
               (BN normalize, ReLU, adds) in the backward pass — trades
               cheap recompute FLOPs for HBM writes of BN/ReLU activations
  vjp_remat  — custom_vjp and remat_conv combined
  pallas     — the fully fused Pallas kernel (ops/batchnorm.bn_train):
               2 reads + 1 write per direction, stats resident in VMEM
  stat<k>    — ghost-batch statistics from the first k rows only
               (BIGDL_TPU_BN_STAT_ROWS=k), e.g. stat64
  conv_epilogue — nn.fuse_conv_bn model rewrite: BN stats accumulated in
               the producing 1x1 conv's matmul epilogue (ops/convbn.py),
               deleting the separate stat read; non-1x1 convs' BNs run
               the baseline path
  <any>_remat — the above combined with the conv_out remat policy
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import get_int

# BIGDL_TPU_BN_BATCH overrides (the round-3 "MFU falls as batch grows"
# anomaly — 256:0.333, 512:0.317, 1024:0.273 — needs per-variant batch
# sweeps to localize; the step is identical, only stats vary)
BATCH = get_int("BN_BATCH", 256)


_PRISTINE_APPLY = None  # BatchNormalization.apply before any variant patch


def _variant_apply(kind):
    import os

    for var in ("BIGDL_TPU_BN_FUSED_VJP", "BIGDL_TPU_BN_IMPL",
                "BIGDL_TPU_BN_STAT_ROWS"):
        os.environ.pop(var, None)
    if kind == "custom_vjp":
        # the library implementation behind BIGDL_TPU_BN_FUSED_VJP
        # (nn/normalization._fused_bn_train) — benchmark THAT, not a copy
        os.environ["BIGDL_TPU_BN_FUSED_VJP"] = "1"
        return _PRISTINE_APPLY
    if kind == "pallas":
        # the Pallas BN kernels (ops/batchnorm).  Single device routes to
        # the fused two-phase kernel; multi-device routes through the
        # shard_map+psum sync path IF a data-only Engine mesh exists and
        # the batch divides over it — otherwise the library would silently
        # benchmark the baseline under this label, so fail loud.
        import jax

        if jax.device_count() > 1:
            from ..utils.engine import Engine

            if Engine._mesh is None:
                Engine.init()  # data-only mesh over all visible devices
            mesh = Engine.mesh()
            from ..nn.normalization import BatchNormalization as _BN

            if not _BN.shardmap_route_engages(mesh, BATCH):
                raise RuntimeError(
                    f"pallas BN variant needs a data-only mesh dividing "
                    f"batch {BATCH} (mesh: {dict(mesh.shape)}): the "
                    "library would fall back to the baseline path and "
                    "mislabel the measurement")
        os.environ["BIGDL_TPU_BN_IMPL"] = "pallas"
        return _PRISTINE_APPLY
    if kind == "conv_epilogue":
        # model-level rewrite (bench_variant applies nn.fuse_conv_bn before
        # build); the BN class itself stays pristine.  ConvBN only engages
        # its fused kernel single-device — fail loud rather than silently
        # benchmark the unfused fallback under this label.
        import jax

        if jax.device_count() != 1 or jax.default_backend() != "tpu":
            raise RuntimeError(
                f"conv_epilogue needs exactly 1 TPU device (have "
                f"{jax.device_count()} x {jax.default_backend()}): ConvBN would "
                "fall back to the unfused path and mislabel the "
                "measurement")
        return _PRISTINE_APPLY
    if kind.startswith("stat") and kind[len("stat"):].isdigit():
        # ghost-batch statistics from the first k rows (BN_STAT_ROWS)
        os.environ["BIGDL_TPU_BN_STAT_ROWS"] = kind[len("stat"):]
        return _PRISTINE_APPLY
    if kind not in ("baseline", "dtype_arg"):
        # unknown names must not silently benchmark the baseline under a
        # wrong label — mislabeled numbers would enter the record
        raise ValueError(f"unknown BN variant: {kind!r}")

    def apply(self, params, state, x, *, training=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if training:
            if kind == "baseline":
                xf = x.astype(jnp.float32)
                mean = jnp.mean(xf, axis=axes)
                var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
            else:  # dtype_arg
                mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
                var = (jnp.mean(jnp.square(x.astype(jnp.float32)),
                                axis=axes) - jnp.square(mean))
            m = self.momentum
            n = 1
            for ax in axes:
                n *= x.shape[ax]
            unbiased = var * (n / max(n - 1, 1))
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        if self.affine:
            scale = params["weight"] * inv
            shift = params["bias"] - mean * scale
        else:
            scale, shift = inv, -mean * inv
        y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
        return y, new_state

    return apply


def bench_variant(kind: str) -> None:
    global _PRISTINE_APPLY
    from ..common import DTypePolicy, set_policy
    from ..nn import CrossEntropyCriterion
    from ..nn.normalization import BatchNormalization
    from ..utils.flops import jaxpr_flops
    from ..utils.timing import measure_step_seconds

    if _PRISTINE_APPLY is None:
        _PRISTINE_APPLY = BatchNormalization.apply
    # conv outputs are checkpoint_name-tagged by nn/conv itself, so the
    # remat variants only need the jax.checkpoint policy below
    remat = kind.endswith("_remat") or kind in ("remat_conv", "vjp_remat")
    base = {"remat_conv": "baseline", "vjp_remat": "custom_vjp"}.get(kind)
    if base is None:
        base = kind[:-len("_remat")] if kind.endswith("_remat") else kind
    BatchNormalization.apply = _variant_apply(base)
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    from ..models.resnet import ResNet
    model = ResNet(50, class_num=1000, dataset="imagenet")
    if base == "conv_epilogue":
        from ..nn import fuse_conv_bn
        fuse_conv_bn(model)  # before build: the rewrite re-nests params
    model.build(jax.random.key(0))
    crit = CrossEntropyCriterion()
    x = jnp.zeros((BATCH, 224, 224, 3), jnp.float32)
    y = jnp.ones((BATCH,), jnp.int32)

    def loss(p):
        out, _ = model.apply(p, model.state, x, training=True,
                             rng=jax.random.key(2))
        return crit.forward(out, y)

    if remat:
        loss = jax.checkpoint(
            loss, policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out"))

    def g(p):
        gr = jax.grad(loss)(p)
        return sum(jnp.sum(l.astype(jnp.float32))
                   for l in jax.tree.leaves(gr))

    flops = jaxpr_flops(jax.make_jaxpr(g)(model.params))
    compiled = jax.jit(g).lower(model.params).compile()
    compiled(model.params)
    dt, _ = measure_step_seconds(lambda: compiled(model.params))
    # seconds a step and the step's counted operations: the reader divides
    # by the chip's peak (benchmark/peaks.json), this tool states no share
    print(f"bn[{kind:9s}] dt={dt * 1e3:8.2f}ms flops={flops:.4e}",
          flush=True)


def main(argv=None):
    for kind in (argv or sys.argv[1:]) or ["baseline", "dtype_arg",
                                           "custom_vjp", "remat_conv",
                                           "vjp_remat", "pallas",
                                           "pallas_remat", "stat64",
                                           "stat64_remat", "conv_epilogue",
                                           "conv_epilogue_remat"]:
        try:
            bench_variant(kind)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"bn[{kind}] FAILED {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
