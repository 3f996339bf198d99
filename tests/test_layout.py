"""MeshLayout (named data/fsdp/tp axes), role-based sharding assignment,
FSDP/TP training + serving, donated train-step buffers, and multi-axis
elastic re-formation — on the 8-virtual-CPU-device mesh (conftest.py),
the simulate-a-cluster-in-one-process strategy the reference uses
(DistriOptimizerSpec.scala:33-41)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.common import set_seed
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import Optimizer, SGD, Trigger
from bigdl_tpu.parallel import (LayoutSharding, MeshLayout, MeshReformError,
                                UnannotatedParameterError, assign_shardings,
                                assign_specs)
from bigdl_tpu.utils import memstats
from bigdl_tpu.utils.engine import Engine

# the simulated multi-device host mesh: conftest forces 8 virtual CPU
# devices; skip (rather than fail) where that did not take hold
multidev = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4 / conftest force_cpu)")


def _mlp(bias=False):
    """All dims divide 4; bias-free variant makes shard-fraction
    arithmetic exact."""
    return nn.Sequential(
        nn.Linear(64, 256, with_bias=bias), nn.ReLU(),
        nn.Linear(256, 256, with_bias=bias), nn.ReLU(),
        nn.Linear(256, 8, with_bias=bias))


def _dataset(n, batch, in_dim=64, classes=8, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(0.0, 1.0, size=(n, in_dim)).astype(np.float32)
    ys = rng.integers(0, classes, size=n)
    return DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(batch, drop_last=True))


def _train(model, ds, strategy, steps, lr=0.05, momentum=0.9):
    losses = []

    class Cap:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = (Optimizer(model, ds, nn.CrossEntropyCriterion(),
                     strategy=strategy)
           .set_optim_method(SGD(learning_rate=lr, momentum=momentum))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(Cap()))
    opt.optimize()
    return losses, opt


class TestMeshLayout:
    def test_sizes_and_parse(self):
        lay = MeshLayout.parse("2,2,1")
        assert lay.sizes == (2, 2, 1) and lay.size == 4
        assert MeshLayout.parse("1x2x2").tp == 2
        with pytest.raises(ValueError):
            MeshLayout.parse("2,2")
        with pytest.raises(ValueError):
            MeshLayout(0, 1, 1)

    @multidev
    def test_build_mesh_and_of_mesh(self):
        lay = MeshLayout(2, 2, 1)
        mesh = lay.build_mesh()
        assert tuple(mesh.axis_names) == ("data", "fsdp", "tp")
        assert MeshLayout.of_mesh(mesh) == lay
        # legacy 1-D mesh is not a layout mesh
        from jax.sharding import Mesh
        legacy = Mesh(np.array(jax.devices()[:2]), ("data",))
        assert MeshLayout.of_mesh(legacy) is None

    def test_role_table_specs(self):
        lay = MeshLayout(1, 2, 2)
        # column-parallel (out, in): tp on out, fsdp on in
        assert lay.spec_for("kernel_out", (256, 64), min_size=0) == \
            P("tp", "fsdp")
        # in-major (in, out): tp on out, fsdp on in
        assert lay.spec_for("kernel_in", (64, 256), min_size=0) == \
            P("fsdp", "tp")
        # HWIO conv: tp on cout, fsdp on cin
        assert lay.spec_for("conv_kernel", (3, 3, 64, 128), min_size=0) == \
            P(None, None, "fsdp", "tp")
        # embedding rows over fsdp x tp together
        assert lay.spec_for("embedding_row", (64, 32), min_size=0) == \
            P(("fsdp", "tp"), None)
        # small per-feature roles replicate
        assert lay.spec_for("bias", (256,), min_size=0) == P(None)
        assert lay.spec_for("norm_scale", (256,), min_size=0) == P(None)
        with pytest.raises(KeyError):
            lay.spec_for("no_such_role", (4,))

    def test_divisibility_degrades_per_axis(self):
        lay = MeshLayout(1, 4, 2)
        # out=6 not divisible by tp=2? 6 % 2 == 0 -> keep; in=5 % 4 != 0
        # -> fsdp falls back to the other (out) axis? out already used by
        # tp -> replicate along fsdp
        assert lay.spec_for("kernel_out", (6, 5), min_size=0) == \
            P("tp", None)
        # nothing divides -> fully replicated
        assert lay.spec_for("kernel_out", (7, 5), min_size=0) == P(None, None)
        # embedding vocab not divisible by fsdp*tp=8 but by fsdp=4
        assert lay.spec_for("embedding_row", (12, 3), min_size=0) == \
            P("fsdp", None)

    def test_min_size_keeps_small_leaves_replicated(self):
        lay = MeshLayout(1, 2, 1)
        assert lay.spec_for("kernel_out", (8, 8), min_size=1024) == \
            P(None, None)
        assert lay.spec_for("kernel_out", (64, 64), min_size=1024) == \
            P(None, "fsdp")

    def test_single_device_layout_replicates_everything(self):
        lay = MeshLayout(1, 1, 1)
        for role in ("kernel_out", "kernel_in", "conv_kernel",
                     "embedding_row", "bias"):
            spec = lay.spec_for(role, (64, 64), min_size=0)
            assert all(s is None for s in spec)


class TestAssigner:
    @multidev
    def test_roles_resolved_through_containers(self):
        model = _mlp(bias=True)
        model.build(jax.random.key(0))
        lay = MeshLayout(2, 2, 1)
        specs = assign_specs(model, model.params, lay, min_size=0)
        flat = {jax.tree_util.keystr(kp): s for kp, s in
                jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]}
        assert flat["[0]['weight']"] == P(None, "fsdp")  # tp=1: no split
        assert flat["[0]['bias']"] == P(None)
        assert flat["[2]['weight']"] == P(None, "fsdp")

    @multidev
    def test_unannotated_leaf_fails_loudly(self):
        class Mystery(nn.Module):
            def _init(self, rng):
                return {"blob": jnp.zeros((16, 16))}

            def _apply(self, params, x):
                return x

        model = nn.Sequential(nn.Linear(8, 8), Mystery())
        model.build(jax.random.key(0))
        mesh = MeshLayout(2, 2, 1).build_mesh()
        with pytest.raises(UnannotatedParameterError, match="Mystery.*blob"):
            assign_shardings(model, model.params, mesh, min_size=0)

    @multidev
    def test_wildcard_role(self):
        class Annotated(nn.Module):
            PARAM_ROLES = {"*": "elementwise"}

            def _init(self, rng):
                return {"a": jnp.zeros((8,)), "b": jnp.zeros((8, 8))}

            def _apply(self, params, x):
                return x

        m = Annotated()
        m.build(jax.random.key(0))
        mesh = MeshLayout(2, 2, 1).build_mesh()
        sh = assign_shardings(m, m.params, mesh, min_size=0)
        assert all(s.spec in (P(), P(None), P(None, None))
                   for s in jax.tree.leaves(sh))

    @multidev
    def test_legacy_mesh_replicates(self):
        from jax.sharding import Mesh
        model = _mlp()
        model.build(jax.random.key(0))
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        sh = assign_shardings(model, model.params, mesh)
        assert all(tuple(s.spec) == () for s in jax.tree.leaves(sh))


@multidev
class TestFSDPTraining:
    def test_fsdp4_shard_bytes_per_device(self):
        """(a) addressable shard bytes per device == total/N under
        FSDP=4 (bias-free model where every leaf divides)."""
        set_seed(3)
        model = _mlp(bias=False)
        ds = _dataset(64, 16)
        MeshLayout(1, 4, 1).install(jax.devices()[:4])
        _, opt = _train(model, ds, LayoutSharding(model, min_size=0), 2)
        total = memstats.tree_total_bytes(model.params)
        per_dev = memstats.tree_device_bytes(model.params)
        assert per_dev * 4 == total
        # slots (momentum) inherit the param shardings leaf-for-leaf
        slots = opt._final_opt_state
        slot_total = memstats.tree_total_bytes(slots)
        slot_dev = memstats.tree_device_bytes(slots)
        # acceptance: params+slots per device <= 30% of replicated bytes
        assert (per_dev + slot_dev) <= 0.30 * (total + slot_total)

    def test_fsdp_loss_parity_vs_data_parallel(self):
        """(b) loss sequence matches pure DP within the documented
        reassociation tolerance (docs/parallelism.md)."""
        set_seed(3)
        dp_model = _mlp(bias=True)
        MeshLayout(4, 1, 1).install(jax.devices()[:4])
        dp_losses, _ = _train(dp_model, _dataset(80, 16),
                              LayoutSharding(dp_model, min_size=0), 5)
        Engine.reset()
        set_seed(3)
        fs_model = _mlp(bias=True)
        MeshLayout(2, 2, 1).install(jax.devices()[:4])
        fs_losses, _ = _train(fs_model, _dataset(80, 16),
                              LayoutSharding(fs_model, min_size=0), 5)
        assert len(dp_losses) == len(fs_losses) == 5
        np.testing.assert_allclose(fs_losses, dp_losses, atol=2e-3)

    def test_wide_embedding_model_shards_and_trains(self):
        """(c) a wide-embedding model shards its table over fsdp x tp
        and trains on a (1,2,2) layout."""
        set_seed(5)
        model = nn.Sequential(
            nn.LookupTable(64, 32),
            nn.Mean(1),                      # (B, T, E) -> (B, E)
            nn.Linear(32, 64, with_bias=True), nn.ReLU(),
            nn.Linear(64, 8, with_bias=True))
        rng = np.random.default_rng(1)
        seqs = rng.integers(0, 64, size=(64, 12)).astype(np.int32)
        ys = rng.integers(0, 8, size=64)
        ds = DataSet.array(
            [Sample(s, np.int32(y)) for s, y in zip(seqs, ys)]).transform(
            SampleToMiniBatch(16, drop_last=True))
        MeshLayout(1, 2, 2).install(jax.devices()[:4])
        losses, _ = _train(model, ds, LayoutSharding(model, min_size=0), 4)
        assert len(losses) == 4 and all(np.isfinite(losses))
        # the table landed in fsdp x tp row shards: 1/4 per device
        table = model.params[0]["weight"]
        assert table.sharding.spec == P(("fsdp", "tp"), None)
        assert memstats.tree_device_bytes({"w": table}) * 4 == \
            memstats.tree_total_bytes({"w": table})

    def test_tp_wide_linear_trains_and_serves_bucket_ladder(self):
        """A tp=2 wide-Linear model trains, then answers through the
        serve bucket ladder with outputs matching bulk Predictor."""
        from bigdl_tpu.serve import InferenceServer

        set_seed(11)
        model = _mlp(bias=True)
        ds = _dataset(64, 16)
        MeshLayout(1, 2, 2).install(jax.devices()[:4])
        strategy = LayoutSharding(model, min_size=0)
        losses, _ = _train(model, ds, strategy, 3)
        assert all(np.isfinite(losses))
        # wide kernels split over tp
        w0 = model.params[0]["weight"]
        assert "tp" in tuple(w0.sharding.spec)
        rng = np.random.default_rng(2)
        xs = rng.normal(size=(6, 64)).astype(np.float32)
        from bigdl_tpu.optim.optimizer import Predictor
        bulk = Predictor(model, batch_size=8, strategy=strategy).predict(
            [Sample(x, np.int32(0)) for x in xs])
        server = InferenceServer(model, max_batch=4, replicas=1,
                                 strategy=strategy, example=xs[0])
        try:
            server.start()  # warms every ladder bucket before traffic
            outs = [server.submit(x).result(timeout=60) for x in xs]
        finally:
            server.stop()
        np.testing.assert_allclose(np.stack(outs), bulk, atol=1e-5,
                                   rtol=1e-5)


@multidev
class TestDonation:
    def _lenet_losses(self, steps=5, batch=16):
        from bigdl_tpu.models.lenet import LeNet5

        set_seed(7)
        rng = np.random.default_rng(0)
        n = batch * steps
        xs = rng.normal(0.0, 0.1, size=(n, 28, 28, 1)).astype(np.float32)
        ys = rng.integers(0, 10, size=n)
        model = LeNet5(10)
        ds = DataSet.array(
            [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
            SampleToMiniBatch(batch, drop_last=True))
        losses, _ = _train(model, ds, None, steps, lr=0.01)
        return losses, [np.asarray(p) for p in jax.tree.leaves(model.params)]

    def test_no_donate_knob_bit_identical(self, monkeypatch):
        """Donated and undonated 5-step LeNet runs are bit-identical:
        donation changes buffer lifetime, never values."""
        monkeypatch.delenv("BIGDL_TPU_NO_DONATE", raising=False)
        l0, p0 = self._lenet_losses()
        monkeypatch.setenv("BIGDL_TPU_NO_DONATE", "1")
        l1, p1 = self._lenet_losses()
        assert l0 == l1 and len(l0) >= 5
        assert all(np.array_equal(a, b) for a, b in zip(p0, p1))

    def _built_step(self, monkeypatch, no_donate):
        if no_donate:
            monkeypatch.setenv("BIGDL_TPU_NO_DONATE", "1")
        else:
            monkeypatch.delenv("BIGDL_TPU_NO_DONATE", raising=False)
        set_seed(9)
        model = _mlp(bias=True)
        model.build(jax.random.key(0))
        opt = Optimizer(model, dataset=None,
                        criterion=nn.CrossEntropyCriterion(),
                        end_trigger=Trigger.max_iteration(1))
        opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
        mesh = Engine.mesh()
        step, param_sh, data_sh = opt._build_step(mesh)
        params = jax.device_put(model.params, param_sh)
        opt_state = jax.device_put(opt.optim_method.init_state(params),
                                   opt._opt_sh)
        net_state = jax.device_put(
            model.state, jax.sharding.NamedSharding(mesh, P()))
        rngk = jax.random.key(1)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(16, 64)).astype(np.float32))
        y = jnp.asarray(np.zeros((16,), np.int32))
        inp = jax.device_put(x, data_sh)
        tgt = jax.device_put(y, data_sh)
        args = (params, net_state, opt_state, inp, tgt,
                jnp.float32(0.05), rngk)
        return step, args, opt

    def test_donated_buffers_deleted_and_not_reused(self, monkeypatch):
        """The donation contract: after the step, the donated input
        buffers are DELETED (in-place update happened) and nothing in
        the loop touches them again — the classic 'referenced deleted
        buffer' class would raise right here."""
        step, args, opt = self._built_step(monkeypatch, no_donate=False)
        assert opt._step_knobs["donate"] is True
        out = step(*args)
        jax.block_until_ready(out[0])
        params, net_state, opt_state = args[0], args[1], args[2]
        assert all(leaf.is_deleted()
                   for leaf in jax.tree.leaves(params))
        assert all(leaf.is_deleted()
                   for leaf in jax.tree.leaves(opt_state)
                   if hasattr(leaf, "is_deleted"))
        # outputs are fresh, alive, and feed the next step cleanly
        out2 = step(*out[:3], args[3], args[4], jnp.float32(0.05), args[6])
        assert np.isfinite(float(out2[3]))
        # a reuse of the donated buffer is exactly this error:
        with pytest.raises(RuntimeError):
            np.asarray(jax.tree.leaves(params)[0])

    def test_no_donate_keeps_buffers_and_costs_live_bytes(self, monkeypatch):
        """BIGDL_TPU_NO_DONATE=1 keeps the inputs alive — and therefore
        holds TWO params+slots copies after the step, which is the peak
        memory donation removes (measured via the live-buffer sum, the
        CPU fallback of utils/memstats)."""
        step, args, opt = self._built_step(monkeypatch, no_donate=True)
        assert opt._step_knobs["donate"] is False
        before = memstats.live_device_bytes()
        out = step(*args)
        jax.block_until_ready(out[0])
        growth_undonated = memstats.live_device_bytes() - before
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(args[0]))
        del step, args, out, opt

        step, args, opt = self._built_step(monkeypatch, no_donate=False)
        before = memstats.live_device_bytes()
        out = step(*args)
        jax.block_until_ready(out[0])
        growth_donated = memstats.live_device_bytes() - before
        # donated step: old params+slots die, so live growth is smaller
        assert growth_donated < growth_undonated


@multidev
class TestMultiAxisReform:
    def test_shrink_data_axis_keeps_fsdp_tp(self):
        MeshLayout(2, 2, 1).install(jax.devices()[:4])
        model = _mlp()
        model.build(jax.random.key(0))
        strategy = LayoutSharding(model, min_size=0)
        mesh = Engine.mesh()
        params = jax.device_put(model.params,
                                strategy.param_sharding(mesh, model.params))
        # lose half the devices: data 2 -> 1, fsdp x tp intact
        new_mesh = Engine.reform(world=1, rank=0, survivors=[0],
                                 devices=jax.devices()[:2])
        assert dict(zip(new_mesh.axis_names,
                        new_mesh.devices.shape)) == \
            {"data": 1, "fsdp": 2, "tp": 1}
        remapped = strategy.remap(new_mesh, params)
        per_dev = memstats.tree_device_bytes(remapped)
        assert per_dev * 2 == memstats.tree_total_bytes(remapped)

    def test_typed_error_when_block_cannot_survive(self):
        MeshLayout(2, 2, 1).install(jax.devices()[:4])
        with pytest.raises(MeshReformError,
                           match="shard groups intact"):
            Engine.reform(world=1, rank=0, survivors=[0],
                          devices=jax.devices()[:3])
        # fewer devices than the fsdp x tp block itself
        with pytest.raises(MeshReformError):
            Engine.reform(world=1, rank=0, survivors=[0],
                          devices=jax.devices()[:1])

    def test_typed_error_without_data_axis(self):
        from jax.sharding import Mesh
        Engine.set_mesh(Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp")))
        with pytest.raises(MeshReformError, match="no 'data' axis"):
            Engine.reform(world=1, rank=0, survivors=[0],
                          devices=jax.devices()[:2])
