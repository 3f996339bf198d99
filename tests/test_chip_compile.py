"""The main path's Pallas kernels, and the batch norm `resnet50.train` runs
(XLA's own fusions), compiled by the TPU's compiler for a described v5e at
the real widths — no chip, nothing runs.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: a slice off the tiling, too much fast memory, a kernel that cannot
be partitioned.  These compiles can, in about two seconds each.  A compile
that passes is not a chip run; chip_smoke.py is.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and under pytest-xdist every worker
imports this file but only one runs it.  JAX's persistent compile cache is
off around the compiles (an entry written for a described device cannot be
read back without one).  `ops/attention.flash_attention` asks
`jax.default_backend()` and would take the jnp reference on this CPU host,
so the tests steer it (`use_pallas=True`) themselves; of `ops/grouped.py`
they take the kernel's own entry (`_pallas`).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    return compiled.as_text()


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ------------------------------------------------------- flash attention

_ATTN_SHAPES = [
    pytest.param((8, 8, 2048, 64), jnp.bfloat16, id="8x8x2048x64-bf16"),
    pytest.param((4, 8, 512, 64), jnp.float32, id="4x8x512x64-f32"),
    pytest.param((2, 16, 4096, 128), jnp.bfloat16, id="2x16x4096x128-bf16"),
    # the shape `gpt2m.train` runs (benchmark/configs/gpt2_medium.json)
    pytest.param((8, 16, 1024, 64), jnp.bfloat16, id="8x16x1024x64-bf16"),
    # a length the block rule pads to whole tiles
    pytest.param((2, 4, 17, 64), jnp.bfloat16, id="2x4x17x64-bf16"),
]


@pytest.mark.parametrize("shape,dtype", _ATTN_SHAPES)
def test_flash_attention_forward_and_grad_compile(one_chip, shape, dtype):
    from bigdl_tpu.ops.attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, use_pallas=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    q = _aval(shape, dtype, one_chip)
    assert "tpu_custom_call" in _compile(fwd, q, q, q)
    _assert_backward_is_the_kernels(
        _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, q, q))


def _assert_backward_is_the_kernels(text):
    """The gradient's program calls the forward kernel and the backward's
    (`flash_bwd_dkv`, `flash_bwd_dq`) by name and holds no loop: no scan
    stands in for a kernel."""
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any("flash_fwd" in name for name in calls), calls
    assert any("flash_bwd" in name for name in calls), calls
    assert " while(" not in text


def test_flash_attention_inside_shard_map_compiles(topo):
    """parallel/ring_attention.ulysses_attention calls the kernel inside a
    shard_map body: the outputs of the forward's and of the backward's
    pallas_calls must say which mesh axes they vary over."""
    import functools

    import bigdl_tpu.ops.attention as att
    from bigdl_tpu.parallel.ring_attention import ulysses_attention

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "seq"))
    sh = NamedSharding(mesh, P("data", None, "seq", None))
    q = _aval((2, 8, 4096, 64), jnp.bfloat16, sh)
    real = att.flash_attention
    att.flash_attention = functools.partial(real, use_pallas=True)
    try:
        def attn(q, k, v):
            return ulysses_attention(q, k, v, mesh=mesh, causal=True)

        def loss(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        text = _compile(attn, q, q, q)
        assert "tpu_custom_call" in text and "all-to-all" in text
        _assert_backward_is_the_kernels(
            _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, q, q))
    finally:
        att.flash_attention = real


# ------------------------------------------------------------ batch norm

def _compile_train_forward_and_grad(module, x, one_chip):
    """What `resnet50.train`'s step holds of `module`: its training forward
    and the gradients to its parameters and its input, XLA's own fusions
    (the cell runs no kernel here).  Returns the two compiled texts."""
    params, state = jax.tree.map(
        lambda a: _aval(a.shape, a.dtype, one_chip),
        jax.eval_shape(module.init, jax.random.key(0)))

    def fwd(params, state, x):
        return module.apply(params, state, x, training=True)

    def loss(params, state, x):
        return fwd(params, state, x)[0].astype(jnp.float32).sum()

    texts = (_compile(fwd, params, state, x),
             _compile(jax.grad(loss, argnums=(0, 2)), params, state, x))
    for text in texts:
        assert "tpu_custom_call" not in text
    return texts


def _results(text):
    """The result types of a compiled module, from its first line's
    `entry_computation_layout={(...)->...}`."""
    return text.split("entry_computation_layout={", 1)[1].split(
        "\n", 1)[0].split("->", 1)[1]


_BN_SHAPES = [
    pytest.param((256, 56, 56, 64), id="256x56x56x64"),
    pytest.param((256, 112, 112, 64), id="256x112x112x64"),
    pytest.param((256, 7, 7, 2048), id="256x7x7x2048"),
]


@pytest.mark.parametrize("shape", _BN_SHAPES)
def test_bn_train_forward_and_grad_compile(one_chip, shape):
    """ResNet-50's BN shapes at batch 256, bf16 activations: the statistics
    are float32 reductions, the output is bf16."""
    import bigdl_tpu.nn as nn

    c = shape[-1]
    fwd, grad = _compile_train_forward_and_grad(
        nn.SpatialBatchNormalization(c),
        _aval(shape, jnp.bfloat16, one_chip), one_chip)
    assert f"f32[{c}]" in fwd and "reduce(" in fwd
    dims = ",".join(map(str, shape))
    assert f"bf16[{dims}]" in _results(fwd)
    assert f"bf16[{dims}]" in _results(grad)


# ------------------------------------------- 1x1 convolution + batch norm

_CONVBN_SHAPES = [   # (rows, c_in, c_out) with rows = 256 images x H x W
    pytest.param((56, 64, 256), id="802816x64x256"),
    pytest.param((7, 2048, 512), id="12544x2048x512"),
    pytest.param((14, 1024, 256), id="50176x1024x256"),
    pytest.param((56, 64, 64), id="802816x64x64"),
]


@pytest.mark.parametrize("hkn", _CONVBN_SHAPES)
def test_conv_bn_forward_and_grad_compile(one_chip, hkn):
    """ResNet-50's 1x1 convolutions with the batch norm behind each, as the
    model builds the pair, bf16 compute over float32 parameters."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.common import DTypePolicy, get_policy, set_policy

    hw, k, n = hkn
    pair = (nn.Sequential()
            .add(nn.SpatialConvolution(k, n, 1, 1))
            .add(nn.SpatialBatchNormalization(n)))
    prev = get_policy()
    set_policy(DTypePolicy(compute_dtype=jnp.bfloat16))
    try:
        fwd, grad = _compile_train_forward_and_grad(
            pair, _aval((256, hw, hw, k), jnp.bfloat16, one_chip), one_chip)
    finally:
        set_policy(prev)
    assert "convolution(" in fwd and "convolution(" in grad
    assert f"bf16[256,{hw},{hw},{n}]" in _results(fwd)
    assert f"f32[1,1,{k},{n}]" in _results(grad)


# ------------------------------------------------ the cells' decode steps

def _compile_decode_step(workload, one_chip):
    """A decode cell's step at its real sizes, donated state and all, as
    `DecodeEngine` builds it; returns (compiled, cfg, bytes of the state)."""
    from bigdl_tpu.common import get_policy, set_policy
    from bigdl_tpu.models import decode as kv
    from bigdl_tpu.serve.decode import _with_tokens
    from benchmark import harness
    cell = harness.Cell(workload)
    cm, cfg, tr = cell.cfg_mod, cell.cfg, cell.traffic
    prior = get_policy()
    try:
        cm.set_policy(cfg)
        model = cm.build_model(cfg)
        on = lambda t: jax.tree.map(
            lambda a: _aval(a.shape, a.dtype, one_chip), t)
        params, state = on(jax.eval_shape(model.init, jax.random.key(0)))
        slots, length = tr["slots"], tr["max_len"]
        caches = on(kv.cache_avals(model, slots, length, jnp.bfloat16))
        # the tokens the call before left on the device, and the host's
        # positions over the tokens it chose itself (ISSUE 40)
        compiled = jax.jit(
            lambda p, s, c, tokens, feed: _with_tokens(*kv._slot_step(
                model, p, s, jnp.where(feed[1] >= 0, feed[1], tokens), c,
                feed[0])),
            donate_argnums=(2,)).lower(
                params, state, caches, _aval((slots,), jnp.int32, one_chip),
                _aval((2, slots), jnp.int32, one_chip)).compile()
    finally:
        set_policy(prior)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for c in caches for a in c.values())
    return compiled, cfg, cache_bytes


def test_latent_decode_step_compiles_at_the_cells_sizes(one_chip):
    """`dsv2.decode`'s step at its real sizes (64 slots, a 4,608-long latent
    cache, 40 held experts a layer): it fits one chip beside its 9.3 GB of
    weights, the grouped expert product is the compiler's own ragged dot,
    and each layer's cache is written in place (a scatter under the
    donation), not copied."""
    compiled, cfg, cache_bytes = _compile_decode_step("dsv2.decode", one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("ragged-dot") >= 4 * 3          # 4 layers x 3 products
    assert mem.alias_size_in_bytes >= cache_bytes     # donated, in place
    assert mem.temp_size_in_bytes < 1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    assert text.count(" scatter(") >= 2 * cfg["num_hidden_layers"]


def test_kv_decode_step_compiles_at_the_cells_sizes(one_chip):
    """`gpt2m.decode`'s step at its real sizes (32 slots, 24 layers of
    `[32, 512, 1024]` bfloat16 keys and values): each leaf is written by one
    scatter in place under the donation, and nothing of the write is a loop
    over slots or a pass over a leaf (ISSUE 30: the vmapped
    `dynamic_update_slice` compiled to 48 `while` loops of 32 trips)."""
    compiled, cfg, cache_bytes = _compile_decode_step("gpt2m.decode",
                                                      one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert cache_bytes == 2 * cfg["n_layer"] * 32 * 512 * cfg["n_embd"] * 2
    assert mem.alias_size_in_bytes >= cache_bytes     # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert text.count(" scatter(") == 2 * cfg["n_layer"]
    assert " while(" not in text
    assert "dynamic-update-slice(" not in text
    # the greedy tokens leave the program beside the logits (ISSUE 33), and
    # the argmax reads the bfloat16 values that leave it and nothing else:
    # fused into the log-softmax it compares them before they are rounded
    # and chose another of the tied entries in 31 rows of 32 on the chip
    entry = _entry(text)
    root = [ln for ln in entry if ln.startswith("ROOT ")][0]
    assert "bf16[32,50257]" in root and "s32[32]" in root
    argmax = [ln.split(" fusion(") for ln in entry if " fusion(" in ln
              and ln.split(" = ")[1].startswith("(bf16[32]{")
              and "s32[32]" in ln.split(" fusion(")[0]]
    assert len(argmax) == 1, argmax
    assert len(argmax[0][1].split(")")[0].split(", ")) == 1, argmax


# ------------------------------- the hybrid cell: grouped matmul, state update

def _entry(text):
    """The entry computation's instructions, metadata cut off."""
    import re
    return [re.sub(r", (metadata|backend_config)=\{.*", "", ln).strip()
            for ln in text[text.index("ENTRY"):].splitlines()]


@pytest.mark.parametrize("rows", [768, 6144, 6])
@pytest.mark.parametrize("transposed,k,n", [(True, 2688, 1856),
                                            (False, 1856, 2688)])
def test_grouped_matmul_compiles_at_the_cells_shapes(one_chip, rows,
                                                     transposed, k, n):
    """`nemo3.decode`'s expert products (64 held experts, hidden 2,688,
    expert width 1,856: neither a multiple of 256) for a step's 768 rows, a
    1,024-token prompt's 6,144 and the 6 of a prompt's last position: the
    Pallas kernel fits its fast memory, and reads the table
    `bf16[64,1856,2688]` as the device keeps it, with no copy of it."""
    from bigdl_tpu.ops.grouped import _pallas
    text = _compile(
        lambda x, w, g: _pallas(x, w, g, transposed),
        _aval((rows, k), jnp.bfloat16, one_chip),
        _aval((64, 1856, 2688), jnp.bfloat16, one_chip),
        _aval((64,), jnp.int32, one_chip))
    entry = _entry(text)
    assert sum("tpu_custom_call" in ln for ln in entry) == 1
    assert not any(" copy(" in ln and "bf16[64," in ln for ln in entry)
    assert any("bf16[64,1856,2688]{2,1,0" in ln and "parameter(" in ln
               for ln in entry)


def test_hybrid_decode_step_compiles_at_the_cells_sizes(one_chip,
                                                        monkeypatch):
    """`nemo3.decode`'s step at its real sizes (128 slots, 6 Mamba layers of
    `f32[128,32,64,128]` recurrent state, 2 attention layers of
    `[128, 1536, 128]` keys and values, 64 held experts a layer): it fits
    one chip beside its 8.9 GB of weights; every leaf is updated in place
    under the donation; each recurrent state crosses HBM once in and once
    out (one fusion a layer whose result holds the leaf: no Pallas call is
    needed for it, PERF.md PR 32); the expert products are the grouped
    matmul kernel, two a layer, and no expert table is copied."""
    import bigdl_tpu.parallel.expert as ep
    from bigdl_tpu.ops.grouped import _pallas
    # on a TPU these shapes take the kernel; here the test says so
    monkeypatch.setattr(ep, "grouped_matmul",
                        lambda x, w, sizes, transposed=False:
                        _pallas(x, w, sizes, transposed))
    compiled, cfg, cache_bytes = _compile_decode_step("nemo3.decode",
                                                      one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    fixed = 6 * 128 * (32 * 64 * 128 * 4 + 3 * 3072 * 2)
    assert cache_bytes == fixed + 2 * 2 * 128 * 1536 * 128 * 2
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    entry = _entry(text)
    leaf = "f32[128,32,64,128]"
    made = [ln for ln in entry if " = " in ln and not any(
        op in ln for op in ("parameter(", "get-tuple-element(", " tuple("))
        and leaf in ln.split(" = ")[1].split(" fusion(")[0]]
    assert len(made) == 6 and all(" fusion(" in ln for ln in made)
    assert not any(" copy(" in ln and leaf in ln for ln in entry)
    assert sum("tpu_custom_call" in ln for ln in entry) == 2 * 6
    assert not any(" copy(" in ln and "bf16[64," in ln for ln in entry)
    assert text.count(" scatter(") >= 2 * 2             # keys and values


def _compile_decode_prefill(workload, rows, bucket, one_chip):
    """A decode cell's prefill for a group of `rows` prompts of `bucket`
    positions at its real sizes, as `DecodeEngine._prefill_exe` builds it;
    returns (compiled, bytes of the state)."""
    from bigdl_tpu.common import get_policy, set_policy
    from bigdl_tpu.models import decode as kv
    from bigdl_tpu.serve.decode import _with_tokens
    from benchmark import harness
    cell = harness.Cell(workload)
    cm, cfg, tr = cell.cfg_mod, cell.cfg, cell.traffic
    prior = get_policy()
    try:
        cm.set_policy(cfg)
        model = cm.build_model(cfg)
        on = lambda t: jax.tree.map(
            lambda a: _aval(a.shape, a.dtype, one_chip), t)
        params, state = on(jax.eval_shape(model.init, jax.random.key(0)))
        slots, length = tr["slots"], tr["max_len"]
        caches = on(kv.cache_avals(model, slots, length, jnp.bfloat16))

        def prefill(p, s, c, tokens, toks, slot, t0):
            logits, token, c, report = _with_tokens(*kv._prefill(
                model, p, s, toks, c, slot, t0))
            return logits, tokens.at[slot].set(token, mode="drop"), c, report

        compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, state, caches, _aval((slots,), jnp.int32, one_chip),
            _aval((rows, bucket), jnp.int32, one_chip),
            _aval((rows,), jnp.int32, one_chip),
            _aval((rows,), jnp.int32, one_chip)).compile()
    finally:
        set_policy(prior)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for c in caches for a in c.values())
    return compiled, cache_bytes


def test_hybrid_group_prefill_compiles_at_the_cells_sizes(one_chip,
                                                          monkeypatch):
    """`nemo3.decode`'s prefill for a group of four 256-position prompts
    (ISSUE 42: the widest group the engine reckons for that bucket): it
    fits one chip beside the weights with no more temporaries than the
    longest single prompt's; every leaf is written in place under the
    donation, the group's rows of a leaf with a length by a loop of four
    windows and of a recurrent state by one scatter, not by a copy of the
    leaf; the expert products are the grouped matmul kernel over the
    group's 6,144 rows, and no expert table is copied."""
    import bigdl_tpu.parallel.expert as ep
    from bigdl_tpu.ops.grouped import _pallas
    monkeypatch.setattr(ep, "grouped_matmul",
                        lambda x, w, sizes, transposed=False:
                        _pallas(x, w, sizes, transposed))
    compiled, cache_bytes = _compile_decode_prefill("nemo3.decode", 4, 256,
                                                    one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    entry = _entry(text)
    # (the convolutions' 2.4 MB leaves are laid out anew around their
    # scatter, twice a layer: 28 MB a call)
    for leaf in ("f32[128,32,64,128]", "bf16[128,1536,128]"):
        assert not any(" copy(" in ln and leaf in ln for ln in entry), leaf
    assert not any(" copy(" in ln and "bf16[64," in ln for ln in entry)
    assert sum("tpu_custom_call" in ln for ln in entry) == 2 * 6
    # four rows of logits leave the program, and four tokens enter the
    # slots' vector
    root = [ln for ln in entry if ln.startswith("ROOT ")][0]
    assert "bf16[4,65536]" in root and "s32[128]" in root


def test_delta_rule_decode_step_compiles_at_the_cells_sizes(one_chip):
    """`qwen3n.decode`'s step at its real sizes (192 slots, 9 linear layers
    of `f32[192,8,128,128]` matrix state, 3 full layers of `[192, 1536,
    256]` keys and values, 128 held experts a layer of `[2048, 512]` and
    `[512, 2048]` matrices): it fits one chip beside its 10.27 GB of
    weights; every leaf is updated in place under the donation; each matrix
    state is written by one fusion a layer (what it holds of `k` and `q` is
    read in a pass before it: PERF.md PR 41); the expert products stay the
    compiler's own ragged dot (both widths are multiples of 256), which the
    TPU compiler turns into its own kernel calls, three a layer, and no
    expert table is copied."""
    compiled, cfg, cache_bytes = _compile_decode_step("qwen3n.decode",
                                                      one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    fixed = 9 * 192 * (8 * 128 * 128 * 4 + 3 * 2048 * 2)
    assert cache_bytes == fixed + 3 * 2 * 192 * 1536 * 256 * 2
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    entry = _entry(text)
    leaf = "f32[192,8,128,128]"
    made = [ln for ln in entry if " = " in ln and " fusion(" in ln
            and leaf in ln.split(" = ")[1].split(" fusion(")[0]]
    assert len(made) == 9, made
    assert not any(" copy(" in ln and "bf16[128," in ln for ln in entry)
    assert sum("ragged-dot" in ln and "tpu_custom_call" in ln
               for ln in text.splitlines()) >= 3 * 12


@pytest.mark.parametrize("rows", [2560, 10240])
@pytest.mark.parametrize("k,n", [(2048, 512), (512, 2048)])
def test_grouped_matmul_compiles_at_the_small_groups_shapes(one_chip, rows,
                                                            k, n):
    """`qwen3n.decode`'s expert products under a prompt's rows (128 held
    experts of 2 MB matrices, 10 choices a token of a 256- or 1,024-token
    bucket): the Pallas kernel fits its fast memory at these tiles and
    reads the table as the device keeps it."""
    from bigdl_tpu.ops.grouped import _pallas
    text = _compile(
        lambda x, w, g: _pallas(x, w, g, False),
        _aval((rows, k), jnp.bfloat16, one_chip),
        _aval((128, k, n), jnp.bfloat16, one_chip),
        _aval((128,), jnp.int32, one_chip))
    entry = _entry(text)
    assert sum("tpu_custom_call" in ln for ln in entry) == 1
    assert not any(" copy(" in ln and "bf16[128," in ln for ln in entry)


# -------------------- the whole small hybrid: selective scan, state update

@pytest.mark.parametrize("rows,positions", [(4, 256), (8, 128), (1, 64)])
def test_selective_scan_compiles_at_the_cells_shapes(one_chip, rows,
                                                     positions):
    """`jamba2.decode`'s prefill scan (5,120 channels, a state of 16 a
    channel) for a group of four 256-position prompts, eight of 128 and a
    lone one of 64, as the Pallas kernel a TPU takes (`ops/ssm._pallas`;
    here the backend is the CPU's, so the test takes the kernel's own
    entry): one custom call named `selective_scan` and no loop beside it,
    and the state is laid out `[rows, 16, 5120]` and for no other
    position."""
    import re
    from bigdl_tpu.ops.ssm import _pallas
    f32 = lambda *s: _aval(s, jnp.float32, one_chip)
    text = _compile(_pallas, f32(rows, positions, 5120),
                    f32(rows, positions, 5120), f32(rows, positions, 16),
                    f32(rows, positions, 16), f32(16, 5120), f32(5120))
    entry = _entry(text)
    calls = [ln for ln in entry if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "selective_scan" in calls[0]
    assert f"f32[{rows},16,5120]" in calls[0]
    assert " while(" not in text
    assert set(re.findall(r"f32\[([\d,]+),16,5120\]", text)) == {str(rows)}


def test_selective_decode_step_compiles_at_the_cells_sizes(one_chip):
    """`jamba2.decode`'s step at its real sizes (384 slots, 26 Mamba layers
    of `f32[384,16,5120]` selective state, 2 attention layers of `[384,
    1024, 128]` keys and values, the table `[65536, 2560]` once): it fits
    one chip beside its 6.06 GB of weights; every leaf is updated in place
    under the donation; each selective state crosses HBM once in and once
    out (one fusion a layer whose result holds the leaf beside the layer's
    `y`: no Pallas call is needed for it, PERF.md PR 44), and no state is
    copied."""
    compiled, cfg, cache_bytes = _compile_decode_step("jamba2.decode",
                                                      one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    fixed = 26 * 384 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert cache_bytes == fixed + 2 * 2 * 384 * 1024 * 128 * 2
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    assert mem.argument_size_in_bytes == pytest.approx(
        2 * 3_029_337_472 + cache_bytes, rel=1e-3)     # the table once
    entry = _entry(text)
    leaf = "f32[384,16,5120]"
    made = [ln for ln in entry if " = " in ln and " fusion(" in ln
            and leaf in ln.split(" = ")[1].split(" fusion(")[0]]
    assert len(made) == 26, made
    assert not any(" copy(" in ln and leaf in ln for ln in entry)
    assert " while(" not in text
    root = [ln for ln in entry if ln.startswith("ROOT ")][0]
    assert "bf16[384,65536]" in root and "s32[384]" in root


def test_selective_prefill_compiles_at_the_cells_sizes(one_chip,
                                                       monkeypatch):
    """`jamba2.decode`'s prefill of one 256-bucket prompt at its real sizes
    with the prompt's scan as a TPU takes it (the Pallas kernel; the test
    says so, as the hybrid's tests do for the grouped matmul): one custom
    call named `selective_scan` a Mamba layer and no loop anywhere, the
    state laid out `f32[1,16,5120]` and for no other position, each layer's
    last state written into the donated `f32[384,16,5120]` leaf in place,
    beside 10 GB of weights and state with under 0.5 GB of its own."""
    import re
    import bigdl_tpu.nn.mamba as mamba
    from bigdl_tpu.ops.ssm import _pallas
    monkeypatch.setattr(mamba, "selective_scan", _pallas)
    compiled, cache_bytes = _compile_decode_prefill("jamba2.decode", 1, 256,
                                                    one_chip)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    entry = _entry(text)
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes > 10e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    # the compiler fuses a layer's kernel with the write of its last state
    # into the leaf's row: one fusion of the entry a layer, which keeps the
    # kernel's name (what a trace's event is called) around one custom call
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 26, len(calls)
    assert all("selective_scan" in ln.split(" = ")[0]
               and "f32[1,16,5120]" in ln for ln in calls)
    named = [ln for ln in entry if ln.startswith("%selective_scan")]
    assert len(named) == 26 and all("kind=kCustom" in ln for ln in named)
    assert " while(" not in text
    assert set(re.findall(r"f32\[([\d,]+),16,5120\]", text)) == {"1", "384"}
    assert not any(" copy(" in ln and "f32[384,16,5120]" in ln
                   for ln in entry)


def test_window_decode_step_compiles_at_the_cells_sizes(one_chip,
                                                        monkeypatch):
    """`mellum2.decode`'s step at its real sizes (192 slots, 21 window
    layers' rings of `[192, 1024, 128]` and 7 full layers of `[192, 5120,
    128]` bfloat16 keys and values, 16 held experts a layer of `[2304,
    896]`): it fits one chip beside its 6.08 GB of weights; every leaf is
    written in place under the donation and none is copied or widened to
    float32 whole on its way to the scores; the expert products are the
    Pallas grouped matmul, three a layer (896 is no multiple of 256; the
    rule asks the backend, so the test says so, as the hybrid's does)."""
    import bigdl_tpu.parallel.expert as ep
    from bigdl_tpu.ops.grouped import _pallas
    monkeypatch.setattr(ep, "grouped_matmul",
                        lambda x, w, sizes, transposed=False:
                        _pallas(x, w, sizes, transposed))
    compiled, cfg, cache_bytes = _compile_decode_step("mellum2.decode",
                                                      one_chip)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert cache_bytes == 192 * (11_010_048 + 3_584 * 5120)
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes == pytest.approx(
        2 * 3_040_674_048 + cache_bytes, rel=1e-3)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    entry = _entry(text)
    for leaf in ("[192,1024,128]", "[192,5120,128]"):
        assert not any(" copy(" in ln and "bf16" + leaf in ln
                       for ln in entry), leaf
        assert "f32" + leaf not in text
    assert sum("tpu_custom_call" in ln for ln in entry) == 3 * 28
    assert text.count(" scatter(") >= 2 * 28
    root = [ln for ln in entry if ln.startswith("ROOT ")][0]
    assert "bf16[192,24576]" in root and "s32[192]" in root
