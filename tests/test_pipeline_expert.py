"""Pipeline + expert parallelism as first-class MeshLayout axes (ISSUE 12):
the 5-axis ``data/fsdp/tp/pipe/expert`` layout, the GPipe stage
partitioner + microbatched schedule through the ordinary compiled step,
expert_table-role MoE sharding, the elastic reform rules for the new
axes, and the ring-attention-over-tp seam — on the 8-virtual-CPU-device
mesh (conftest.py), exactly as tools/shard_smoke.py covers fsdp/tp."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.common import set_seed
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import Optimizer, SGD, Trigger
from bigdl_tpu.parallel import (GPipeSequential, LayoutSharding, MeshLayout,
                                MeshReformError, MoEFFN,
                                PipelinePartitionError, bubble_fraction,
                                load_balancing_loss, partition_pipeline,
                                top_k_routing)
from bigdl_tpu.utils import memstats
from bigdl_tpu.utils.engine import Engine

multidev = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (conftest force_cpu)")

LOSS_TOL = 2e-3


def _mlp():
    """Two identical Linear+ReLU blocks and a head — the repeated-block
    body partition_pipeline targets; bias-free so shard-fraction
    arithmetic is exact."""
    return nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))


def _moe_mlp():
    return nn.Sequential(
        nn.Linear(64, 32, with_bias=False), nn.ReLU(),
        MoEFFN(32, 64, num_experts=4, capacity_factor=4.0),
        nn.Linear(32, 8, with_bias=False))


def _dataset(n, batch, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(0.0, 1.0, size=(n, 64)).astype(np.float32)
    ys = rng.integers(0, 8, size=n)
    return DataSet.array(
        [Sample(x, np.int32(y)) for x, y in zip(xs, ys)]).transform(
        SampleToMiniBatch(16, drop_last=True))


def _train(model, ds, strategy, steps, lr=0.05):
    losses = []

    class Cap:
        def add_scalar(self, name, value, step):
            if name == "Loss":
                losses.append(float(value))

    opt = (Optimizer(model, ds, nn.CrossEntropyCriterion(),
                     strategy=strategy)
           .set_optim_method(SGD(learning_rate=lr, momentum=0.9))
           .set_end_when(Trigger.max_iteration(steps))
           .set_log_interval(1)
           .set_train_summary(Cap()))
    opt.optimize()
    return losses, opt


class TestFiveAxisLayout:
    def test_parse_three_and_five(self):
        assert MeshLayout.parse("2,2,1") == MeshLayout(2, 2, 1)
        lay = MeshLayout.parse("1,1,1,2,2")
        assert (lay.pipe, lay.expert) == (2, 2) and lay.size == 4
        with pytest.raises(ValueError):
            MeshLayout.parse("1,1,1,2")  # 4 ints is neither spelling
        with pytest.raises(ValueError):
            MeshLayout(1, 1, 1, 0, 1)

    def test_legacy_mesh_unchanged_at_pipe_expert_one(self):
        """pipe=expert=1 builds the SAME 3-axis mesh as before — the
        AOT-fingerprint/back-compat contract."""
        lay = MeshLayout(2, 1, 1)
        assert lay.axis_names == ("data", "fsdp", "tp")
        assert lay.sizes == (2, 1, 1)

    @multidev
    def test_build_and_of_mesh_roundtrip(self):
        lay = MeshLayout(1, 1, 1, 2, 2)
        mesh = lay.build_mesh()
        assert tuple(mesh.axis_names) == \
            ("data", "fsdp", "tp", "pipe", "expert")
        assert MeshLayout.of_mesh(mesh) == lay
        legacy = MeshLayout(2, 2, 1).build_mesh()
        assert tuple(legacy.axis_names) == ("data", "fsdp", "tp")
        assert MeshLayout.of_mesh(legacy) == MeshLayout(2, 2, 1)

    def test_pipeline_stage_role_spec(self):
        lay = MeshLayout(1, 1, 1, 2, 1)
        assert lay.spec_for("pipeline_stage", (2, 64, 64), min_size=0) == \
            P("pipe", None, None)
        # 1-wide pipe axis or indivisible stack: replicated
        assert lay.spec_for("pipeline_stage", (3, 64, 64), min_size=0) == \
            P(None, None, None)
        assert MeshLayout(1, 1, 1).spec_for(
            "pipeline_stage", (2, 64), min_size=0) == P(None, None)

    def test_expert_table_role_spec(self):
        lay = MeshLayout(1, 1, 1, 1, 2)
        assert lay.spec_for("expert_table", (4, 32, 64), min_size=0) == \
            P("expert", None, None)
        # expert x fsdp compose: experts on 0, fsdp on the largest
        # remaining divisible axis
        both = MeshLayout(1, 2, 1, 1, 2)
        assert both.spec_for("expert_table", (4, 32, 64), min_size=0) == \
            P("expert", None, "fsdp")
        # no expert axis: fsdp fallback alone
        assert MeshLayout(1, 2, 1).spec_for(
            "expert_table", (4, 32, 64), min_size=0) == \
            P(None, None, "fsdp")


class TestPartitioner:
    def test_partition_balanced_with_head(self):
        model = _mlp()
        out = partition_pipeline(model, 2)
        assert [type(m).__name__ for m in out.modules] == \
            ["GPipeSequential", "Linear"]
        assert len(out.modules[0].stages) == 2

    def test_partition_carries_built_params(self):
        set_seed(3)
        model = _mlp()
        model.build(jax.random.key(0))
        w0 = np.asarray(model.params[0]["weight"])
        w1 = np.asarray(model.params[2]["weight"])
        out = partition_pipeline(model, 2)
        stacked = out.params[0]  # [2, ...] stage stack
        leaves = jax.tree.leaves(stacked)
        assert leaves[0].shape[0] == 2
        np.testing.assert_array_equal(np.asarray(leaves[0][0]), w0)
        np.testing.assert_array_equal(np.asarray(leaves[0][1]), w1)

    def test_partition_typed_errors(self):
        # no repeated-block body of the requested width
        bad = nn.Sequential(nn.Linear(8, 16), nn.Linear(16, 8))
        with pytest.raises(PipelinePartitionError,
                           match="structurally identical"):
            partition_pipeline(bad, 2)
        # stateful stages (BatchNorm running stats) refuse loudly
        with pytest.raises(PipelinePartitionError, match="running state"):
            GPipeSequential([nn.BatchNormalization(8),
                             nn.BatchNormalization(8)])
        # non-chain containers refuse loudly
        with pytest.raises(PipelinePartitionError):
            partition_pipeline(nn.ConcatTable(nn.Linear(4, 4),
                                              nn.Linear(4, 4)), 2)

    def test_partition_linear_graph(self):
        from bigdl_tpu.nn.graph import Graph, Input
        inp = Input()
        h = nn.Linear(16, 16, with_bias=False)(inp)
        h = nn.Linear(16, 16, with_bias=False)(h)
        model = Graph(inp, h)
        out = partition_pipeline(model, 2)
        assert isinstance(out.modules[0], GPipeSequential)
        x = np.random.default_rng(0).normal(size=(4, 16)).astype(np.float32)
        out.build(jax.random.key(1))
        y = out.forward(x)
        assert y.shape == (4, 16)

    def test_stage_count_vs_mesh_mismatch_typed(self):
        model = partition_pipeline(_mlp(), 2)
        model.build(jax.random.key(0))
        mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
        with pytest.raises(PipelinePartitionError, match="2 stages"):
            with mesh:
                model.apply(model.params, model.state,
                            jnp.zeros((8, 64), jnp.float32))

    def test_sequential_fallback_matches_plain_model(self):
        """On a mesh without a pipe axis the wrapper runs its stages
        sequentially — bit-identical to the unpartitioned model."""
        set_seed(5)
        model = _mlp()
        model.build(jax.random.key(0))
        x = np.random.default_rng(1).normal(size=(8, 64)).astype(np.float32)
        y_ref = np.asarray(model.forward(x))
        piped = partition_pipeline(model, 2)
        y = np.asarray(piped.forward(x))
        np.testing.assert_array_equal(y, y_ref)


@multidev
class TestPipelineTraining:
    def test_pipe2_parity_fraction_and_bubble_counter(self, tmp_path,
                                                      monkeypatch):
        """Acceptance: a pipe=2 Sequential trains 5 steps with loss
        parity <= 2e-3 vs the (4,1,1) DP run, stage params land 1/2 per
        device, and the traced run emits train.pipe_bubble_fraction."""
        set_seed(7)
        base = _mlp()
        Engine.reset()
        MeshLayout(4, 1, 1).install(jax.devices()[:4])
        base_losses, _ = _train(base, _dataset(160, 16),
                                LayoutSharding(base, min_size=0), 5)

        set_seed(7)
        plain = _mlp()
        plain.build()
        piped = partition_pipeline(plain, 2)
        Engine.reset()
        MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
        monkeypatch.setenv("BIGDL_TPU_TRACE", str(tmp_path))
        pipe_losses, opt = _train(piped, _dataset(160, 16),
                                  LayoutSharding(piped, min_size=0), 5)
        assert len(pipe_losses) == len(base_losses) == 5
        np.testing.assert_allclose(pipe_losses, base_losses, atol=LOSS_TOL)
        # per-device stage-stack bytes: exactly 1/2
        stacked = piped.params[0]
        assert memstats.tree_device_bytes(stacked) * 2 == \
            memstats.tree_total_bytes(stacked)
        # the step self-described its schedule on the compile card
        assert opt._card_extra["pipe_stages"] == 2
        mb = opt._card_extra["pipe_microbatches"]
        assert opt._card_extra["pipe_bubble_fraction"] == pytest.approx(
            bubble_fraction(2, mb), abs=1e-4)
        # the counter reached the trace
        blob = ""
        for name in os.listdir(tmp_path):
            if name.startswith("trace."):
                blob += (tmp_path / name).read_text()
        assert "pipe_bubble_fraction" in blob

    def test_pipe_composes_with_fused_wire_knobs(self, monkeypatch):
        """The promotion claim: the pipelined step runs through the SAME
        _build_step machinery, so the fused update + bucketed wire knobs
        apply unchanged (and donation stays on)."""
        monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1")
        monkeypatch.setenv("BIGDL_TPU_WIRE_BUCKET_MB", "4")
        set_seed(9)
        plain = _mlp()
        plain.build()
        piped = partition_pipeline(plain, 2)
        Engine.reset()
        MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
        losses, opt = _train(piped, _dataset(96, 16),
                             LayoutSharding(piped, min_size=0), 3)
        assert len(losses) == 3 and all(np.isfinite(losses))
        assert opt._step_knobs["fused_update"] is True
        assert opt._step_knobs["donate"] is True
        assert opt._card_extra["fused_buffers"] >= 1

    def test_aot_warm_run_zero_fresh_compiles(self, tmp_path, monkeypatch):
        """Acceptance: with the AOT cache armed, a second training run of
        the same pipelined step deserializes the stored executable — the
        warm run performs ZERO fresh XLA compiles (lowering happens, the
        compile does not — utils/aot.cached_compile).

        The XLA persistent cache is un-latched for the duration (the
        test_serve attribution discipline): an executable
        XLA read back from its disk cache is not stored by the AOT layer,
        so with that cache warm this ledger would show a miss."""
        from jax._src import compilation_cache as _cc

        from bigdl_tpu.utils import aot
        monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", str(tmp_path))
        monkeypatch.setenv("BIGDL_TPU_XLA_CACHE", "0")
        aot.reset()
        prior_xla = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()

        def run():
            set_seed(11)
            plain = _mlp()
            plain.build()
            piped = partition_pipeline(plain, 2)
            Engine.reset()
            MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
            return _train(piped, _dataset(64, 16),
                          LayoutSharding(piped, min_size=0), 2)

        try:
            run()
            s1 = aot.stats()
            assert s1["compiles"] >= 1 and s1["stores"] >= 1
            jax.clear_caches()
            run()
            s2 = aot.stats()
            assert s2["compiles"] == s1["compiles"], \
                "warm pipelined step must not compile again"
            assert s2["misses"] == s1["misses"]
            assert s2["hits"] > s1["hits"]
        finally:
            jax.config.update("jax_compilation_cache_dir", prior_xla)
            _cc.reset_cache()


@multidev
class TestExpertTraining:
    def test_expert2_tables_sharded_trains_and_serves(self):
        """Acceptance: an expert=2 MoEFFN trains with tables sharded
        exactly 1/2 per device (bytes-asserted) and serves through
        _ShardedForward with outputs matching the dense forward."""
        set_seed(7)
        model = _moe_mlp()
        Engine.reset()
        MeshLayout(1, 1, 1, 1, 2).install(jax.devices()[:2])
        strategy = LayoutSharding(model, min_size=0)
        losses, _ = _train(model, _dataset(96, 16), strategy, 3)
        assert len(losses) == 3 and all(np.isfinite(losses))
        tables = {k: model.params[2][k] for k in ("w1", "w2", "b1", "b2")}
        assert model.params[2]["w1"].sharding.spec == \
            P("expert", None, None)
        assert memstats.tree_device_bytes(tables) * 2 == \
            memstats.tree_total_bytes(tables)
        # serve: the sharded forward answers like the dense math
        from bigdl_tpu.optim.optimizer import Predictor
        xs = np.random.default_rng(2).normal(size=(6, 64)).astype(np.float32)
        served = Predictor(model, batch_size=8, strategy=strategy).predict(
            [Sample(x, np.int32(0)) for x in xs])
        model.evaluate()
        host_params = jax.tree.map(np.asarray, model.params)
        ref, _ = model.apply(host_params, model.state, jnp.asarray(xs))
        np.testing.assert_allclose(np.asarray(served), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_expert2_loss_parity_vs_dense(self):
        set_seed(7)
        dense = _moe_mlp()
        Engine.reset()
        MeshLayout(1, 1, 1).install(jax.devices()[:1])
        dense_losses, _ = _train(dense, _dataset(96, 16),
                                 LayoutSharding(dense, min_size=0), 3)
        set_seed(7)
        ep = _moe_mlp()
        Engine.reset()
        MeshLayout(1, 1, 1, 1, 2).install(jax.devices()[:2])
        ep_losses, _ = _train(ep, _dataset(96, 16),
                              LayoutSharding(ep, min_size=0), 3)
        np.testing.assert_allclose(ep_losses, dense_losses, atol=LOSS_TOL)


class TestMoEFixes:
    def test_capacity_overflow_deterministic(self):
        """Dropped tokens are stable across runs: the routing is a pure
        function of the logits, so two evaluations (and a jitted one)
        agree bitwise even under heavy overflow."""
        logits = jax.random.normal(jax.random.key(2), (64, 4))
        a = top_k_routing(logits, capacity=3, k=2)
        b = top_k_routing(logits, capacity=3, k=2)
        j = jax.jit(lambda l: top_k_routing(l, capacity=3, k=2))(logits)
        for x, y, z in zip(a, b, j):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
        # overflow really happened (otherwise this tests nothing)
        assert float(jnp.sum(a[1])) < 128.0

    def test_load_balancing_loss_values(self):
        """Known values: perfectly balanced uniform routing scores
        exactly 1.0; full collapse onto one expert scores E."""
        T, E = 32, 4
        probs = jnp.full((T, E), 1.0 / E)
        assign = jnp.tile(jnp.eye(E), (T // E, 1))
        assert float(load_balancing_loss(probs, assign)) == \
            pytest.approx(1.0, abs=1e-6)
        collapsed_p = jnp.zeros((T, E)).at[:, 0].set(1.0)
        collapsed_a = jnp.zeros((T, E)).at[:, 0].set(1.0)
        assert float(load_balancing_loss(collapsed_p, collapsed_a)) == \
            pytest.approx(float(E), abs=1e-6)

    @multidev
    def test_legacy_mesh_degrades_silently(self):
        """MoEFFN(expert_axis='expert') on a legacy data-only mesh:
        replicated tables, no all-to-all, NO warning — the documented
        graceful degrade (was: assumed the axis exists)."""
        MoEFFN._warned_no_mesh = False
        set_seed(3)
        model = _moe_mlp()
        Engine.reset()
        Engine.init(mesh_shape={"data": 2}, devices=jax.devices()[:2])
        losses, _ = _train(model, _dataset(64, 16), None, 2)
        assert all(np.isfinite(losses))
        assert MoEFFN._warned_no_mesh is False

    @multidev
    def test_expert_parallel_ffn_degrades_on_1wide_mesh(self):
        """expert_parallel_ffn on a mesh without the axis (or a 1-wide
        one) falls back to the dense math instead of crashing."""
        from bigdl_tpu.parallel import expert_parallel_ffn
        m = MoEFFN(16, 32, num_experts=4, capacity_factor=8.0,
                   expert_axis=None).build(jax.random.key(0)).evaluate()
        x = jax.random.normal(jax.random.key(4), (32, 16))
        y_dense = m.forward(x)
        legacy = Mesh(np.array(jax.devices()[:2]), ("data",))
        y = expert_parallel_ffn(legacy, m.params, x, k=1,
                                capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=2e-4, atol=2e-5)
        one_wide = Mesh(np.array(jax.devices()[:1]).reshape(1), ("expert",))
        y1 = expert_parallel_ffn(one_wide, m.params, x, k=1,
                                 capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y_dense),
                                   rtol=2e-4, atol=2e-5)


@multidev
class TestReformNewAxes:
    def test_shrink_keeps_pipe_expert_block(self):
        """Engine.reform shrinks 'data' and keeps fsdp x tp x pipe x
        expert intact; LayoutSharding.remap re-derives the shards."""
        set_seed(13)
        plain = _mlp()
        plain.build()
        piped = partition_pipeline(plain, 2)
        Engine.reset()
        MeshLayout(2, 1, 1, 2, 1).install(jax.devices()[:4])
        strategy = LayoutSharding(piped, min_size=0)
        mesh = Engine.mesh()
        params = jax.device_put(piped.params,
                                strategy.param_sharding(mesh, piped.params))
        new_mesh = Engine.reform(world=1, rank=0, survivors=[0],
                                 devices=jax.devices()[:2])
        assert dict(zip(new_mesh.axis_names, new_mesh.devices.shape)) == \
            {"data": 1, "fsdp": 1, "tp": 1, "pipe": 2, "expert": 1}
        remapped = strategy.remap(new_mesh, params)
        stacked = remapped[0]
        assert memstats.tree_device_bytes(stacked) * 2 == \
            memstats.tree_total_bytes(stacked)

    def test_typed_error_when_block_cannot_survive(self):
        Engine.reset()
        MeshLayout(2, 1, 1, 1, 2).install(jax.devices()[:4])
        with pytest.raises(MeshReformError, match="shard groups intact"):
            Engine.reform(world=1, rank=0, survivors=[0],
                          devices=jax.devices()[:3])


@multidev
class TestRingAttnSeam:
    def test_ring_over_tp_parity(self, monkeypatch):
        """BIGDL_TPU_RING_ATTN=1 on a tp>1 mesh routes the attention
        core through the ring (seq sharded over 'tp'), matching the
        dense flash path."""
        x = jax.random.normal(jax.random.key(20), (2, 16, 32))
        mha = nn.MultiHeadAttention(32, 4, causal=True).build(
            jax.random.key(21))
        monkeypatch.delenv("BIGDL_TPU_RING_ATTN", raising=False)
        y_ref, _ = mha.apply(mha.params, mha.state, x)
        Engine.reset()
        mesh = MeshLayout(1, 1, 2).install(jax.devices()[:2])
        monkeypatch.setenv("BIGDL_TPU_RING_ATTN", "1")
        with mesh:
            y_ring, _ = mha.apply(mha.params, mha.state, x)
        np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)

    def test_seam_inert_when_indivisible_or_ungated(self, monkeypatch):
        x = jax.random.normal(jax.random.key(22), (2, 15, 32))  # 15 % 2
        mha = nn.MultiHeadAttention(32, 4, causal=True).build(
            jax.random.key(23))
        y_ref, _ = mha.apply(mha.params, mha.state, x)
        Engine.reset()
        mesh = MeshLayout(1, 1, 2).install(jax.devices()[:2])
        monkeypatch.setenv("BIGDL_TPU_RING_ATTN", "1")
        with mesh:
            y, _ = mha.apply(mha.params, mha.state, x)  # T=15: flash path
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-6)


def _mlp4():
    """Four identical blocks + a head: splits into 2 GPipe stages or 4
    interleaved 1F1B slices of the same params."""
    return nn.Sequential(
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 64, with_bias=False), nn.ReLU(),
        nn.Linear(64, 8, with_bias=False))


def _pipe_step_temp_bytes(num_stages, batch=256):
    """XLA temp (peak scratch) budget of the real compiled train step
    under the CURRENT schedule env knobs (memstats proxy for peak live
    activations — never executed)."""
    jax.clear_caches()
    Engine.reset()
    mesh = MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
    model = _mlp4()
    model.build(jax.random.key(0))
    model = partition_pipeline(model, num_stages)
    from bigdl_tpu.optim import Optimizer as _Opt
    opt = _Opt(model, dataset=None, criterion=nn.CrossEntropyCriterion(),
               end_trigger=Trigger.max_iteration(1),
               strategy=LayoutSharding(model, min_size=0))
    opt.set_optim_method(SGD(learning_rate=0.05))
    step, param_sh, data_sh = opt._build_step(mesh)
    rng = np.random.default_rng(0)
    inp = jax.device_put(
        jnp.asarray(rng.normal(size=(batch, 64)), jnp.float32), data_sh)
    tgt = jax.device_put(
        jnp.asarray(rng.integers(0, 8, size=batch), jnp.int32), data_sh)
    params = jax.device_put(model.params, param_sh)
    opt_state = jax.device_put(opt.optim_method.init_state(model.params),
                               opt._opt_sh)
    args = (params, model.state, opt_state, inp, tgt, jnp.float32(0.05),
            jax.random.key(1))
    ma = memstats.compiled_memory_analysis(step.lower(*args).compile())
    return (ma or {}).get("temp_bytes")


@multidev
class TestOneFOneB:
    """The 1F1B schedule + interleaved virtual stages (ISSUE 13
    tentpole): parity vs GPipe at the pinned tolerance, the bubble and
    activation-memory claims, remat/AOT composition, and the
    microbatch-clamp surfacing."""

    def _run(self, num_stages, steps=5, monkeypatch=None, **env):
        for k, val in env.items():
            monkeypatch.setenv(k, str(val))
        set_seed(13)
        model = _mlp4()
        model.build()
        piped = partition_pipeline(model, num_stages)
        Engine.reset()
        MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
        return _train(piped, _dataset(16 * steps * 2, 16),
                      LayoutSharding(piped, min_size=0), steps)

    def test_1f1b_v1_loss_parity_vs_gpipe(self, monkeypatch):
        """pipe=2, equal m=8: 1F1B's explicitly staged backward must
        reproduce GPipe's losses within the pinned reassociation
        tolerance (docs/parallelism.md 'Choosing a schedule')."""
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        g_losses, _ = self._run(2, monkeypatch=monkeypatch)
        f_losses, fopt = self._run(
            2, monkeypatch=monkeypatch, BIGDL_TPU_PIPE_SCHEDULE="1f1b")
        assert len(f_losses) == len(g_losses) == 5
        np.testing.assert_allclose(f_losses, g_losses, atol=LOSS_TOL)
        assert fopt._card_extra["pipe_schedule"] == "1f1b"
        assert fopt._card_extra["pipe_virtual_stages"] == 1
        # v=1 1F1B keeps GPipe's bubble — the win is memory
        assert fopt._card_extra["pipe_bubble_fraction"] == pytest.approx(
            bubble_fraction(2, 8), abs=1e-4)

    def test_1f1b_interleaved_parity_and_lower_bubble(self, monkeypatch):
        """pipe=2 with v=2 (4 interleaved slices): losses still match,
        and the card reports the strictly lower interleaved bubble
        (1/17 vs GPipe's 1/9 at m=8) — the acceptance geometry."""
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        g_losses, gopt = self._run(2, monkeypatch=monkeypatch)
        f_losses, fopt = self._run(
            4, monkeypatch=monkeypatch, BIGDL_TPU_PIPE_SCHEDULE="1f1b",
            BIGDL_TPU_PIPE_VIRTUAL_STAGES="2")
        np.testing.assert_allclose(f_losses, g_losses, atol=LOSS_TOL)
        g_bubble = gopt._card_extra["pipe_bubble_fraction"]
        f_bubble = fopt._card_extra["pipe_bubble_fraction"]
        assert g_bubble == pytest.approx(1 / 9, abs=1e-4)
        assert f_bubble == pytest.approx(1 / 17, abs=1e-4)
        assert f_bubble < g_bubble
        assert fopt._step_knobs["pipe_schedule"] == "1f1b"
        assert fopt._step_knobs["pipe_virtual_stages"] == 2
        # per-device stage stack still 1/2 of the logical params
        stacked = next(p for c, p in zip(fopt.model.modules,
                                         fopt.model.params)
                       if isinstance(c, GPipeSequential))
        assert memstats.tree_device_bytes(stacked) * 2 == \
            memstats.tree_total_bytes(stacked)

    def test_1f1b_bubble_counter_from_actual_schedule(self, tmp_path,
                                                      monkeypatch):
        """The traced run emits the TABLE's bubble (1/17), not the
        gpipe closed form — the counter reads the realized schedule."""
        import json as _json
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        monkeypatch.setenv("BIGDL_TPU_TRACE", str(tmp_path))
        self._run(4, steps=2, monkeypatch=monkeypatch,
                  BIGDL_TPU_PIPE_SCHEDULE="1f1b",
                  BIGDL_TPU_PIPE_VIRTUAL_STAGES="2")
        vals = []
        for name in os.listdir(tmp_path):
            if not name.startswith("trace."):
                continue
            blob = _json.loads((tmp_path / name).read_text())
            for ev in blob.get("traceEvents", []):
                if ev.get("ph") == "C" and ev.get("name") == "train":
                    v = ev.get("args", {}).get("pipe_bubble_fraction")
                    if v is not None:
                        vals.append(float(v))
        assert vals, "no pipe_bubble_fraction samples in the trace"
        assert all(v == pytest.approx(1 / 17, abs=1e-4) for v in vals)

    def test_activation_memory_bound(self, monkeypatch):
        """The memory claim, twice: the schedule table's analytic
        in-flight count is m-independent and below GPipe's keep-all,
        and XLA's own temp budget for the compiled 1F1B step is <= the
        GPipe step's at an activation-dominated batch."""
        from bigdl_tpu.parallel import build_schedule
        tbl = build_schedule("1f1b", 2, 8, 2)
        assert tbl.peak_inflight == 5 < 16  # GPipe keeps m*v
        assert build_schedule("1f1b", 2, 16, 2).peak_inflight == 5
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        g_temp = _pipe_step_temp_bytes(2)
        monkeypatch.setenv("BIGDL_TPU_PIPE_SCHEDULE", "1f1b")
        f1_temp = _pipe_step_temp_bytes(2)
        monkeypatch.setenv("BIGDL_TPU_PIPE_VIRTUAL_STAGES", "2")
        f2_temp = _pipe_step_temp_bytes(4)
        if g_temp is None:
            pytest.skip("backend exposes no memory_analysis")
        assert f1_temp <= g_temp
        assert f2_temp <= g_temp

    def test_remat_composes_with_1f1b(self, monkeypatch):
        """remat=True (stage-level jax.checkpoint on the forward
        schedule) must compose with the 1F1B backward — parity held;
        the 1F1B backward already recomputes (full-remat by design)."""
        monkeypatch.setenv("BIGDL_TPU_PIPE_SCHEDULE", "1f1b")
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        set_seed(13)
        model = _mlp4()
        model.build()
        piped = partition_pipeline(model, 2, remat=True)
        Engine.reset()
        MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
        r_losses, _ = _train(piped, _dataset(160, 16),
                             LayoutSharding(piped, min_size=0), 5)
        p_losses, _ = self._run(2, monkeypatch=monkeypatch)
        assert len(r_losses) == 5 and all(np.isfinite(r_losses))
        np.testing.assert_allclose(r_losses, p_losses, atol=LOSS_TOL)

    def test_microbatch_clamp_logged_and_surfaced(self, monkeypatch,
                                                  caplog):
        """The silent-clamp satellite: a knob that does not divide the
        local batch is clamped, logged ONCE (requested -> effective),
        and the effective count lands in step_knobs + the compile card
        so bench records agree with reality."""
        import logging as _logging
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "7")
        with caplog.at_level(_logging.WARNING, logger="bigdl_tpu"):
            _, opt = self._run(2, steps=3, monkeypatch=monkeypatch,
                               BIGDL_TPU_PIPE_SCHEDULE="1f1b")
        clamp_logs = [r for r in caplog.records
                      if "clamped to 4 microbatches" in r.getMessage()]
        assert len(clamp_logs) == 1  # once, not per trace/step
        # local batch 16: 7 -> 4 (largest feasible <= the knob)
        assert opt._step_knobs["pipe_microbatches"] == 4
        assert opt._card_extra["pipe_microbatches"] == 4
        assert opt._card_extra["pipe_bubble_fraction"] == pytest.approx(
            bubble_fraction(2, 4, "1f1b", 1), abs=1e-4)

    def test_aot_warm_run_zero_fresh_compiles_1f1b(self, tmp_path,
                                                   monkeypatch):
        """The AOT cache composes with the new schedule knobs (the
        fingerprint carries pipe_schedule/pipe_virtual_stages): a warm
        run of the 1F1B step performs zero fresh XLA compiles."""
        from jax._src import compilation_cache as _cc

        from bigdl_tpu.utils import aot
        monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", str(tmp_path))
        monkeypatch.setenv("BIGDL_TPU_XLA_CACHE", "0")
        monkeypatch.setenv("BIGDL_TPU_PIPE_SCHEDULE", "1f1b")
        monkeypatch.setenv("BIGDL_TPU_PIPE_VIRTUAL_STAGES", "2")
        monkeypatch.setenv("BIGDL_TPU_PIPE_MICROBATCHES", "8")
        aot.reset()
        prior_xla = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()

        def run():
            set_seed(11)
            model = _mlp4()
            model.build()
            piped = partition_pipeline(model, 4)
            Engine.reset()
            MeshLayout(1, 1, 1, 2, 1).install(jax.devices()[:2])
            return _train(piped, _dataset(64, 16),
                          LayoutSharding(piped, min_size=0), 2)

        try:
            run()
            s1 = aot.stats()
            assert s1["compiles"] >= 1 and s1["stores"] >= 1
            jax.clear_caches()
            run()
            s2 = aot.stats()
            assert s2["compiles"] == s1["compiles"], \
                "warm 1F1B step must not compile again"
            assert s2["misses"] == s1["misses"]
            assert s2["hits"] > s1["hits"]
        finally:
            jax.config.update("jax_compilation_cache_dir", prior_xla)
            _cc.reset_cache()
