"""ops/grouped.py: rows sorted by group times each group's own matrix.  The
compiler's ragged dot and the Pallas grouped matmul (in interpret mode here;
tests/test_chip_compile.py compiles it for the chip at the cell's shapes)
against a loop over the groups, for a table stored ``[g, k, n]`` and one
stored as rows ``[g, n, k]``, group sizes that leave rows over, empty groups,
and a row count that is no multiple of the row tile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.grouped import _pallas, _tiles, grouped_matmul


def _case(m, k, n, g, seed, transposed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(r.standard_normal((g, n, k) if transposed else (g, k, n)),
                    jnp.float32)
    cuts = np.sort(r.integers(0, m - m // 5, g - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [m - m // 5]]))
    sizes[r.integers(0, g)] += 0            # some groups are empty by chance
    return x, w, jnp.asarray(sizes, jnp.int32)


def _loop(x, w, sizes, transposed):
    out, at = np.zeros((x.shape[0], w.shape[1 if transposed else 2]),
                       np.float32), 0
    for e, s in enumerate(np.asarray(sizes)):
        we = np.asarray(w[e]).T if transposed else np.asarray(w[e])
        out[at:at + s] = np.asarray(x[at:at + s]) @ we
        at += s
    return out, at


@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("m,k,n,g", [(40, 128, 256, 5), (300, 256, 640, 7),
                                     (6, 128, 128, 4)])
def test_both_paths_against_a_loop_over_the_groups(m, k, n, g, transposed):
    x, w, sizes = _case(m, k, n, g, m + n, transposed)
    want, real = _loop(x, w, sizes, transposed)
    with jax.default_matmul_precision("highest"):
        plain = grouped_matmul(x, w, sizes, transposed=transposed)
        kernel = _pallas(x, w, sizes, transposed, interpret=True)
    assert plain.shape == kernel.shape == want.shape
    assert plain.dtype == kernel.dtype == jnp.float32
    # rows past the groups' runs hold nothing that may be read
    np.testing.assert_allclose(plain[:real], want[:real], atol=2e-3)
    np.testing.assert_allclose(kernel[:real], want[:real], atol=2e-3)


def test_the_shape_and_the_backend_decide():
    """Here (no TPU) every shape takes the compiler's ragged dot; the tiles
    the kernel would take are chosen from the shape alone."""
    x, w, sizes = _case(24, 128, 256, 3, 1, False)
    text = str(jax.make_jaxpr(lambda *a: grouped_matmul(*a))(x, w, sizes))
    assert "ragged_dot" in text and "pallas_call" not in text
    # the cell's shapes: the whole contraction, up to 512 columns
    assert _tiles(768, 2688, 1856) == (128, 2688, 512)      # up, a step
    assert _tiles(768, 1856, 2688) == (128, 1856, 384)      # down: 7 x 384
    assert _tiles(6144, 2688, 1856) == (256, 2688, 512)     # a prompt


@pytest.mark.parametrize("m,k,n,kernel", [
    (1920, 2048, 512, False),     # qwen3n.decode's step: few rows
    (2560, 2048, 512, True),      # its shortest prompt's rows: the kernel
    (10240, 512, 2048, True),
    (4096, 5120, 1536, False),    # dsv2.decode's matrices are not small
    (768, 2688, 1856, True)])     # nemo3.decode's tile badly: as before
def test_many_small_groups_under_a_prompts_rows_take_the_kernel(
        monkeypatch, m, k, n, kernel):
    """On a TPU (said here by the test) the shape alone decides: a matrix
    of at most 4 MiB under more than 2,048 rows goes to the Pallas grouped
    matmul (PERF.md, PR 41), every other well-tiling shape to the ragged
    dot."""
    import bigdl_tpu.ops.grouped as g
    took = []
    monkeypatch.setattr(g.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(g, "_pallas", lambda *a: took.append("kernel"))
    monkeypatch.setattr(g.lax, "ragged_dot",
                        lambda *a, **kw: took.append("ragged"))
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, n, k) if k == 2688 else (4, k, n),
                             jnp.bfloat16)
    g.grouped_matmul(x, w, None, transposed=k == 2688)
    assert took == ["kernel" if kernel else "ragged"]
