"""Sequence parallelism (ring/Ulysses attention), pipeline parallelism, and
the flash-attention op — on the 8-virtual-CPU-device mesh (conftest.py),
mirroring the reference's simulate-a-cluster-in-one-process test strategy
(DistriOptimizerSpec.scala:33-41)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from bigdl_tpu.ops.attention import flash_attention, mha_reference
from bigdl_tpu.parallel import (ring_attention, ulysses_attention,
                                pipeline_apply, stack_stage_params)


def _qkv(B=2, H=4, T=32, D=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


class TestFlashAttention:
    def test_matches_reference_noncausal(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, use_pallas=True, interpret=True,
                              block_q=16, block_k=16)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_reference_causal(self):
        q, k, v = _qkv(seed=1)
        out = flash_attention(q, k, v, causal=True, use_pallas=True,
                              interpret=True, block_q=16, block_k=16)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_grad_through_pallas_path(self):
        """Training differentiates through flash_attention: the pallas
        forward must carry a VJP (pallas_call itself has no autodiff rule
        — without the custom_vjp this raises on real TPUs) and its
        gradients must match differentiating the dense reference."""
        q, k, v = _qkv(seed=3)

        def f_pallas(q, k, v):
            out = flash_attention(q, k, v, causal=True, use_pallas=True,
                                  interpret=True, block_q=16, block_k=16)
            return jnp.sum(out * out)

        def f_ref(q, k, v):
            out = mha_reference(q, k, v, causal=True)
            return jnp.sum(out * out)

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_grad_pallas_ragged_blocks_and_noncausal(self):
        """Backward-kernel edge cases: Tq not a multiple of block_q, and
        the non-causal mask — both must match dense-reference gradients."""
        r = np.random.default_rng(9)
        q = jnp.asarray(r.normal(size=(2, 2, 21, 8)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, 2, 21, 8)), jnp.float32)
        v = jnp.asarray(r.normal(size=(2, 2, 21, 8)), jnp.float32)
        for causal in (False, True):
            def f_pallas(q, k, v):
                out = flash_attention(q, k, v, causal=causal,
                                      use_pallas=True, interpret=True,
                                      block_q=8, block_k=8)
                return jnp.sum(jnp.sin(out))

            def f_ref(q, k, v):
                return jnp.sum(jnp.sin(mha_reference(q, k, v,
                                                     causal=causal)))

            gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gp, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=3e-5, rtol=3e-5,
                                           err_msg=f"causal={causal}")

    def test_fallback_path(self):
        q, k, v = _qkv(seed=2)
        out = flash_attention(q, k, v)  # auto: jnp path on CPU
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    # -- bfloat16 operands: the MXU's own path -------------------------
    #
    # With bfloat16 q, k, v the kernel multiplies bfloat16 operands into
    # float32 and rounds the probabilities to bfloat16 for p @ v, as
    # mha_reference does.  Both compute the same float32 scores (bfloat16
    # products are exact in float32).  They differ in where p is rounded
    # (the kernel rounds exp(s - running max), the reference the normalised
    # p): each rounding is at most 2^-8 relative (bfloat16 keeps 8 bits,
    # so its unit roundoff is 2^-8), so each side's sum over keys is off by
    # at most 2^-8 max|v|, and each side's result is rounded once more to
    # bfloat16, at most 2^-8 max|v| again: four roundings, 2^-6 max|v|,
    # bound the difference.

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T,blocks", [(32, (16, 16)), (40, (16, 16)),
                                          (40, (None, None))])
    def test_bf16_matches_reference(self, causal, T, blocks):
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=2, H=2, T=T, D=16, seed=4))
        out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                              interpret=True, block_q=blocks[0],
                              block_k=blocks[1])
        assert out.dtype == jnp.bfloat16
        ref = mha_reference(q, k, v, causal=causal)
        tol = 2.0 ** -6 * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=0)

    def test_bf16_scores_are_the_float32_cast_scores(self):
        """With one key block and v = identity columns the output IS p:
        bfloat16 operands must give the probabilities that the float32
        cast gave, to float32's own rounding (the first cast bought
        nothing)."""
        T = D = 16
        q, k, _ = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=1, H=2, T=T, D=D, seed=5))
        eye = jnp.broadcast_to(jnp.eye(T, D, dtype=jnp.float32),
                               (1, 2, T, D))
        kw = dict(use_pallas=True, interpret=True, block_q=16, block_k=16)
        lo = flash_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                             eye, **kw)
        hi = flash_attention(q, k, eye.astype(jnp.bfloat16), **kw)
        # hi's p and result are rounded to bfloat16: 2^-8 each, of p <= 1
        np.testing.assert_allclose(np.asarray(hi, np.float32),
                                   np.asarray(lo), atol=2.0 ** -7, rtol=0)

    @pytest.mark.parametrize("causal", [False, True])
    def test_bf16_grad_through_pallas_path(self, causal):
        """The custom VJP with bfloat16 inputs: the backward kernels
        (`flash_bwd_dkv`, `flash_bwd_dq`) rebuild p from the bfloat16 q, k
        and the forward's float32 row statistics, and send p and dS to the
        MXU rounded once to bfloat16, as the forward rounds p (2^-8
        relative each; the float32 sums and the one rounding of each result
        add as much again).  Against the float32 gradient of exact
        attention on the same inputs the gap is held norm-wise at 2^-6 of
        the gradient's norm.  `jax.grad(mha_reference)` on bfloat16 inputs
        rounds p, dP and each result to bfloat16 on the way (2^-8 each, and
        `dP - rowsum(dP p)` cancels: taken as at most four times), so
        against it the gap is held norm-wise at 4 x 4 x 2^-8 = 2^-4; a
        missing term or a wrong mask reads of order 1."""
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=2, H=2, T=21, D=8, seed=6))
        # weights that bfloat16 holds exactly: the output's cotangent is
        # cast to the output's dtype on its way into the backward
        w = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)

        def f_pallas(q, k, v):
            out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                                  interpret=True, block_q=8, block_k=8)
            return jnp.sum(out.astype(jnp.float32) * w)

        def f_ref(q, k, v):
            out = mha_reference(q, k, v, causal=causal)
            return jnp.sum(out.astype(jnp.float32) * w)

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        g16 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g32 = jax.grad(f_ref, argnums=(0, 1, 2))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
        for a, b16, b32 in zip(gp, g16, g32):
            assert a.dtype == jnp.bfloat16
            a = np.asarray(a, np.float32)
            b32 = np.asarray(b32)
            assert (np.linalg.norm(a - b32)
                    <= 2.0 ** -6 * np.linalg.norm(b32))
            b16 = np.asarray(b16, np.float32)
            assert (np.linalg.norm(a - b16)
                    <= 2.0 ** -4 * np.linalg.norm(b16))

    # -- the backward kernels against jax.grad(mha_reference), float32 ---

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("Tq,Tk,blocks", [
        (32, 32, (16, 16)),
        (21, 21, (8, 8)),          # ragged: padded rows and masked keys
        (24, 40, (16, 16)),        # Tq != Tk; causal: a key block no query sees
        (40, 24, (16, 16)),
        (40, 40, (8, 16)),         # the two kernels clamp their skipped blocks
        (40, 40, (16, 8)),         # by another ratio each
        (40, 40, (None, None)),    # the rule's blocks: one padded tile
    ])
    def test_grad_kernels_match_reference(self, causal, Tq, Tk, blocks):
        r = np.random.default_rng(11)
        q = jnp.asarray(r.normal(size=(2, 2, Tq, 8)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, 2, Tk, 8)), jnp.float32)
        v = jnp.asarray(r.normal(size=(2, 2, Tk, 8)), jnp.float32)

        def f_pallas(q, k, v):
            out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                                  interpret=True, block_q=blocks[0],
                                  block_k=blocks[1])
            return jnp.sum(jnp.sin(out))

        def f_ref(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("blocks", [(16, 16), (8, 16), (16, 8)])
    def test_grad_skips_blocks_no_query_sees(self, blocks):
        """Causal with more keys than queries: the key blocks wholly above
        the diagonal hold NaN.  A skipped block is not read into any sum,
        so the output and every gradient the mask allows are those of the
        keys that can be seen, and the unseen keys' gradients are zero."""
        Tq, Tk = 16, 48
        q, k, v = _qkv(B=1, H=2, T=Tk, D=8, seed=12)
        q = q[:, :, :Tq]
        poison = jnp.arange(Tk)[:, None] >= Tq
        kn, vn = (jnp.where(poison, jnp.nan, x) for x in (k, v))

        def f(attend, q, k, v):
            return jnp.sum(jnp.sin(attend(q, k, v)))

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=True, use_pallas=True,
                                   interpret=True, block_q=blocks[0],
                                   block_k=blocks[1])

        def seen(q, k, v):
            return mha_reference(q, k, v, causal=True)

        out = kernel(q, kn, vn)
        assert np.isfinite(np.asarray(out)).all()
        gp = jax.grad(functools.partial(f, kernel), argnums=(0, 1, 2))(
            q, kn, vn)
        gr = jax.grad(functools.partial(f, seen), argnums=(0, 1, 2))(
            q, k[:, :, :Tq], v[:, :, :Tq])
        np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                                   atol=3e-5, rtol=3e-5)
        for a, b in zip(gp[1:], gr[1:]):
            np.testing.assert_allclose(np.asarray(a[:, :, :Tq]),
                                       np.asarray(b), atol=3e-5, rtol=3e-5)
            assert not np.asarray(a[:, :, Tq:]).any()

    @pytest.mark.parametrize("rule", [
        {}, {"grad": True}, {"backward": True}],
        ids=["forward", "forward-lse", "backward"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("Tk", [1, 17, 1024, 4096, 131072])
    @pytest.mark.parametrize("Tq", [1, 17, 1024, 4096, 131072])
    def test_block_rule(self, Tq, Tk, dtype, rule):
        """Blocks chosen from the shape are whole tiles (so Mosaic takes
        them), pad a length by under one tile a block, never exceed the
        sweep's cap, and are reckoned to fit VMEM.  Under differentiation
        the rows' statistics are lane-dense, so a query block that is a
        part of the length is whole 128-lane tiles."""
        from bigdl_tpu.ops import attention as att

        rows = 32 // jnp.dtype(dtype).itemsize
        for D in (64, 128, 256):
            block_q, block_k = att._choose_blocks(Tq, Tk, D, dtype, **rule)
            q_tile = 128 if rule and block_q < Tq else rows
            assert block_q % q_tile == 0 and block_k % 128 == 0
            assert 0 < block_q <= att._BLOCK_CAP
            assert 0 < block_k <= att._BLOCK_CAP
            for T, block, tile in ((Tq, block_q, q_tile),
                                   (Tk, block_k, 128)):
                n_blocks = -(-T // block)         # as _flash_pallas pads
                assert n_blocks * block - T < n_blocks * tile
            assert att._vmem_bytes(block_q, block_k, D, dtype,
                                   rule.get("backward", False)) \
                <= att._VMEM_BUDGET

    @pytest.mark.parametrize("backward", [False, True])
    def test_block_rule_shrinks_to_the_budget(self, backward):
        """A head size nobody swept still gets blocks that fit (the
        backward holds more tiles a step and its query block is at least
        128 rows: a quarter of the head size brings it to small blocks)."""
        from bigdl_tpu.ops import attention as att

        for D, dtype in ((2048, jnp.float32), (8192, jnp.bfloat16)):
            D //= 4 if backward else 1
            bq, bk = att._choose_blocks(4096, 4096, D, dtype,
                                        backward=backward)
            assert bq * bk < att._BLOCK_CAP ** 2
            assert att._vmem_bytes(bq, bk, D, dtype, backward) \
                <= att._VMEM_BUDGET

    def test_grad_with_default_blocks(self):
        """With the blocks left to the rule (one padded tile each way
        here) the gradients match those of explicit blocks."""
        q, k, v = _qkv(B=1, H=2, T=40, D=8, seed=8)

        def f(q, k, v, **kw):
            out = flash_attention(q, k, v, causal=True, use_pallas=True,
                                  interpret=True, **kw)
            return jnp.sum(jnp.sin(out))

        g0 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g1 = jax.grad(lambda *a: f(*a, block_q=16, block_k=16),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        n = 8
        mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
        q, k, v = _qkv(B=2, H=2, T=4 * n, D=8, seed=3)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal,
                             batch_axis=None)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_2d_mesh_data_and_seq(self):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("data", "seq"))
        q, k, v = _qkv(B=4, H=2, T=16, D=8, seed=4)
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grad_flows(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        q, k, v = _qkv(B=1, H=2, T=8, D=8, seed=5)

        def loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=True,
                                          batch_axis=None) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


class TestUlyssesAttention:
    def test_matches_full_attention(self):
        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
        q, k, v = _qkv(B=2, H=4, T=4 * n, D=8, seed=6)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=True,
                                batch_axis=None)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_rejects_indivisible_heads(self):
        mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
        q, k, v = _qkv(B=1, H=4, T=16, D=8)
        with pytest.raises(ValueError):
            ulysses_attention(q, k, v, mesh=mesh)


class TestPipeline:
    def _stages(self, n, F=16, seed=7):
        keys = jax.random.split(jax.random.key(seed), n)
        return [{"w": jax.random.normal(k, (F, F)) * 0.1,
                 "b": jnp.zeros((F,))} for k in keys]

    @staticmethod
    def _stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def test_forward_matches_sequential(self):
        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("pipe",))
        stages = self._stages(n)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.key(8), (8, 16))
        y = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                           num_microbatches=4, batch_axis=None)
        y_ref = x
        for p in stages:
            y_ref = self._stage_fn(p, y_ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)

    def test_grad_matches_sequential(self):
        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("pipe",))
        stages = self._stages(n, seed=9)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.key(10), (8, 16))

        def loss(sp):
            y = pipeline_apply(self._stage_fn, sp, x, mesh=mesh,
                               num_microbatches=4, batch_axis=None)
            return jnp.mean(y ** 2)

        def loss_ref(stages_list):
            y = x
            for p in stages_list:
                y = self._stage_fn(p, y)
            return jnp.mean(y ** 2)

        g = jax.jit(jax.grad(loss))(stacked)
        g_ref = jax.grad(loss_ref)(stages)
        g_ref_stacked = stack_stage_params(g_ref)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
            g, g_ref_stacked)

    def test_remat_same_result(self):
        n = 2
        mesh = Mesh(np.array(jax.devices()[:n]), ("pipe",))
        stages = self._stages(n, seed=11)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.key(12), (4, 16))
        y1 = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                            num_microbatches=2, batch_axis=None, remat=True)
        y2 = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                            num_microbatches=2, batch_axis=None, remat=False)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)

    def test_data_parallel_times_pipeline(self):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("data", "pipe"))
        stages = self._stages(4, seed=13)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.key(14), (8, 16))
        y = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                           num_microbatches=2)
        y_ref = x
        for p in stages:
            y_ref = self._stage_fn(p, y_ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)


class TestDryrunExtras:
    def test_run(self):
        from bigdl_tpu.parallel import dryrun_extras
        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        dryrun_extras.run(mesh)


class TestMultiHeadAttention:
    def test_forward_shapes_and_seq_parallel_parity(self):
        from bigdl_tpu.nn import MultiHeadAttention
        from bigdl_tpu.utils.engine import Engine
        x = jax.random.normal(jax.random.key(20), (2, 16, 32))
        mha = MultiHeadAttention(32, 4, causal=True).build(jax.random.key(21))
        y, _ = mha.apply(mha.params, mha.state, x)
        assert y.shape == (2, 16, 32)

        Engine.init(mesh_shape={"seq": 4}, devices=jax.devices()[:4])
        sp = MultiHeadAttention(32, 4, causal=True, seq_parallel=True)
        sp.params, sp.state = mha.params, mha.state
        with Engine.mesh():
            y2, _ = sp.apply(sp.params, sp.state, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                                   atol=2e-5, rtol=2e-5)


class TestFlashAttentionPadding:
    def test_non_divisible_lengths(self):
        # T=40 with block 16 exercises the pad+mask path
        q, k, v = _qkv(B=2, H=2, T=40, D=16, seed=30)
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                                  interpret=True, block_q=16, block_k=16)
            ref = mha_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_cross_attention_lengths(self):
        ks = jax.random.split(jax.random.key(31), 3)
        q = jax.random.normal(ks[0], (1, 2, 24, 8), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 40, 8), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 40, 8), jnp.float32)
        out = flash_attention(q, k, v, use_pallas=True, interpret=True,
                              block_q=16, block_k=16)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestRingChunkedInner:
    def test_ring_with_chunked_inner(self, monkeypatch):
        # force tiny chunks so the scan path in _block_attn is exercised
        import importlib
        ra = importlib.import_module("bigdl_tpu.parallel.ring_attention")
        monkeypatch.setattr(ra, "_CHUNK", 4)
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        q, k, v = _qkv(B=1, H=2, T=32, D=8, seed=32)
        out = ra.ring_attention(q, k, v, mesh=mesh, causal=True,
                                batch_axis=None)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestOptStateSharding:
    """ShardedDataParallel must shard same-shaped optimizer slots like the
    params (ZeRO — the TPU-native form of the reference's per-node 1/N slice
    update, DistriOptimizer.scala:265-280)."""

    def test_momentum_inherits_param_sharding(self):
        from bigdl_tpu.parallel.sharding import ShardedDataParallel
        from bigdl_tpu.optim import SGD
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        params = {"w": jnp.zeros((1024, 64)), "b": jnp.zeros((64,))}
        strat = ShardedDataParallel(min_size=1024)
        p_sh = strat.param_sharding(mesh, params)
        opt = SGD(learning_rate=0.1, momentum=0.9)
        opt_state = opt.init_state(params)
        os_sh = strat.opt_state_sharding(mesh, opt_state, params, p_sh)
        placed = jax.device_put(opt_state, os_sh)
        flat = jax.tree_util.tree_flatten_with_path(placed)[0]
        mom_w = [l for kp, l in flat if l.ndim == 2]
        assert mom_w, "expected a 2-D momentum slot"
        for leaf in mom_w:
            assert len(leaf.sharding.device_set) == 8  # sharded, not replicated
            assert "data" in jax.tree.leaves(
                [ax for ax in leaf.sharding.spec if ax])

    def test_scalars_replicate(self):
        from bigdl_tpu.parallel.sharding import ShardedDataParallel
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        params = {"w": jnp.zeros((1024, 64))}
        strat = ShardedDataParallel(min_size=1024)
        p_sh = strat.param_sharding(mesh, params)
        state = {"t": jnp.zeros(()), "m": {"w": jnp.zeros((1024, 64))}}
        sh = strat.opt_state_sharding(mesh, state, params, p_sh)
        assert sh["t"].spec == jax.sharding.PartitionSpec()

    def test_ambiguous_shapes_replicate(self):
        """Two same-shaped params with different shardings: their optimizer
        slots must not be guessed by shape (row- vs column-parallel TP)."""
        from bigdl_tpu.parallel.sharding import ShardingStrategy
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        params = {"a": jnp.zeros((64, 64)), "b": jnp.zeros((64, 64))}
        p_sh = {"a": NamedSharding(mesh, P("data", None)),
                "b": NamedSharding(mesh, P(None, "data"))}
        # state that is NOT structurally identical to params (extra leaf)
        state = {"slot_a": jnp.zeros((64, 64)), "t": jnp.zeros(())}
        sh = ShardingStrategy().opt_state_sharding(mesh, state, params, p_sh)
        assert sh["slot_a"].spec == P()  # ambiguous -> replicated
        # structurally-matching subtree still inherits exactly
        state2 = {"m": {"a": jnp.zeros((64, 64)), "b": jnp.zeros((64, 64))},
                  "t": jnp.zeros(())}
        sh2 = ShardingStrategy().opt_state_sharding(mesh, state2, params, p_sh)
        assert sh2["m"]["a"].spec == P("data", None)
        assert sh2["m"]["b"].spec == P(None, "data")


class TestExpertParallel:
    """EP: capacity-routed MoE (parallel/expert.py) — dense GSPMD module vs
    explicit shard_map all-to-all implementation."""

    def _model(self, E=8, D=16, H=32, k=1, cf=4.0, axis=None):
        from bigdl_tpu.parallel import MoEFFN
        return MoEFFN(D, H, E, k=k, capacity_factor=cf,
                      expert_axis=axis).build(jax.random.key(0))

    def test_dense_routing_matches_manual(self):
        """With ample capacity and k=1, MoE output == gate-prob-weighted
        output of each token's argmax expert."""
        m = self._model().evaluate()  # eval: no router jitter
        x = jax.random.normal(jax.random.key(1), (32, 16))
        y = m.forward(x)
        p = m.params
        logits = x @ p["gate"]
        probs = jax.nn.softmax(logits, axis=-1)
        idx = jnp.argmax(logits, axis=-1)
        h = jnp.maximum(jnp.einsum("td,edh->teh", x, p["w1"])
                        + p["b1"][None], 0.0)
        out_e = jnp.einsum("teh,ehd->ted", h, p["w2"]) + p["b2"][None]
        expect = (jnp.take_along_axis(
            out_e, idx[:, None, None].repeat(16, -1), 1)[:, 0]
            * jnp.take_along_axis(probs, idx[:, None], 1))
        np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def test_top2_and_capacity_drop(self):
        """k=2 routes each token to two experts; capacity 1 forces drops —
        dispatch mask never exceeds capacity."""
        from bigdl_tpu.parallel import top_k_routing
        logits = jax.random.normal(jax.random.key(2), (16, 4))
        combine, dispatch, probs, assign = top_k_routing(logits,
                                                         capacity=2, k=2)
        # pre-capacity assignment counts every router choice, dropped or not
        assert float(jnp.sum(assign)) == 32.0  # 16 tokens x k=2
        # per-token: at most 2 slots
        assert float(jnp.max(jnp.sum(dispatch, axis=(1, 2)))) <= 2.0
        # per-expert: never more tokens than capacity
        assert float(jnp.max(jnp.sum(dispatch, axis=(0, 2)))) <= 2.0
        # slot uniqueness: one token per (expert, slot)
        assert float(jnp.max(jnp.sum(dispatch, axis=0))) <= 1.0

    def test_gate_gradient_flows(self):
        m = self._model()
        x = jax.random.normal(jax.random.key(3), (32, 16))

        def loss(params):
            y = m.apply(params, m.state, x, training=True)[0]
            return jnp.sum(jnp.square(y))

        g = jax.grad(loss)(m.params)
        assert float(jnp.sum(jnp.abs(g["gate"]))) > 0.0
        assert float(jnp.sum(jnp.abs(g["w1"]))) > 0.0

    def test_shard_map_matches_dense(self):
        """expert_parallel_ffn (explicit all_to_all over the expert axis)
        must match the dense MoEFFN math when nothing overflows."""
        from bigdl_tpu.parallel import expert_parallel_ffn
        mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
        m = self._model(E=8, cf=8.0).evaluate()  # eval: no router jitter
        x = jax.random.normal(jax.random.key(4), (64, 16))
        y_dense = m.forward(x)
        y_ep = expert_parallel_ffn(mesh, m.params, x, k=1,
                                   capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                                   rtol=2e-4, atol=2e-5)

    def test_aux_loss_balanced_vs_collapsed(self):
        from bigdl_tpu.parallel import top_k_routing, load_balancing_loss
        T, E = 64, 4
        balanced = jnp.tile(jnp.eye(E) * 10.0, (T // E, 1))
        collapsed = jnp.zeros((T, E)).at[:, 0].set(10.0)
        _, _, p1, a1 = top_k_routing(balanced, capacity=T, k=1)
        _, _, p2, a2 = top_k_routing(collapsed, capacity=T, k=1)
        assert float(load_balancing_loss(p1, a1)) < \
            float(load_balancing_loss(p2, a2))
        # aux pressure must NOT saturate under capacity overflow: with a
        # tiny capacity the collapsed router keeps the same (pre-drop) loss
        _, _, p3, a3 = top_k_routing(collapsed, capacity=2, k=1)
        np.testing.assert_allclose(float(load_balancing_loss(p3, a3)),
                                   float(load_balancing_loss(p2, a2)),
                                   rtol=1e-6)
        # k > num_experts is a hard error, not silent expert-0 double-dispatch
        with pytest.raises(ValueError):
            top_k_routing(balanced, capacity=4, k=5)

    def test_moe_lm_trains_on_data_x_expert_mesh(self):
        """GSPMD EP end-to-end: the MoE TransformerLM trains through the
        Optimizer's compiled step on a {"data": 2, "expert": 4} mesh with
        expert_axis sharding constraints active (MoEFFN._constrain) —
        proving EP composes with data-parallel training, not just the
        shard_map parity path."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.common import set_seed
        from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
        from bigdl_tpu.models import TransformerLM
        from bigdl_tpu.optim import Adam, Optimizer, Trigger
        from bigdl_tpu.utils.engine import Engine

        Engine.reset()
        Engine.init(mesh_shape={"data": 2, "expert": 4})
        set_seed(3)
        vocab, t = 12, 8
        seqs = [[(s + i) % vocab for i in range(t + 1)]
                for s in range(vocab)] * 8
        samples = [Sample(np.asarray(s[:-1], np.int32),
                          np.asarray(s[1:], np.int32)) for s in seqs]
        ds = DataSet.array(samples).transform(
            SampleToMiniBatch(32, drop_last=True))
        model = TransformerLM(vocab_size=vocab, max_len=t, d_model=32,
                              num_heads=4, num_layers=2, num_experts=4,
                              expert_axis="expert")
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                           size_average=True)
        from bigdl_tpu.optim import Loss, Trigger as Trg
        opt = (Optimizer(model, ds, crit)
               .set_optim_method(Adam(3e-3))
               .set_end_when(Trigger.max_epoch(5))
               .set_validation(Trg.every_epoch(), ds, [Loss(crit)]))
        from bigdl_tpu.parallel import MoEFFN
        MoEFFN._warned_no_mesh = False
        opt.optimize()
        # the expert-axis constraint must have BOUND (the step is traced
        # under the mesh context) — a silent replicated-experts fallback
        # would set the warning latch
        assert MoEFFN._warned_no_mesh is False
        loss = opt.optim_method.hyper["loss"]
        assert np.isfinite(loss) and loss < 2.4  # descending from ln(12)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_attention_path_follows_the_backend(monkeypatch, backend):
    """use_pallas=None lets the backend decide: the Pallas kernel on a
    TPU, `mha_reference` elsewhere; an explicit use_pallas= wins, and both
    paths agree.  The backend is faked here; on a chip chip_smoke.py looks
    for `tpu_custom_call` in the compiled LM step."""
    import numpy as np
    import jax

    from bigdl_tpu.ops import attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 16, 8))
    o_jnp = attention.flash_attention(q, k, v, causal=True, use_pallas=False)
    o_pl = attention.flash_attention(q, k, v, causal=True, use_pallas=True,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_pl),
                               rtol=1e-4, atol=1e-4)

    taken = []
    for name in ("mha_reference", "_flash_diff"):
        def spy(*a, _name=name, _real=getattr(attention, name), **kw):
            taken.append(_name)
            return _real(*a, **kw)
        monkeypatch.setattr(attention, name, spy)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    out = attention.flash_attention(q, k, v, causal=True, interpret=True)
    want, same = {"cpu": ("mha_reference", o_jnp),
                  "tpu": ("_flash_diff", o_pl)}[backend]
    assert taken == [want]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(same))
