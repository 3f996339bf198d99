"""Gradient-parity goldens against pytorch (CPU) as an independent oracle.

tests/test_torch_golden.py checks FORWARD numerics; training correctness
rests on the backward pass, which the reference validates layer-by-layer
through its Torch7-golden specs' accGradParameters/updateGradInput
comparisons (SURVEY.md §4, test/.../torch/ — e.g. SpatialConvolutionSpec
drives both gradInput and gradWeight through `th`).  Here the same idea:
push an identical random cotangent through our jax.grad and through
torch.autograd and compare input/weight/bias gradients elementwise.

Layout notes as in test_torch_golden.py: ours NHWC/HWIO, torch NCHW/OIHW;
every test permutes explicitly.  All grads are wrt a scalar loss
sum(out * cot) with a fixed nonuniform cotangent so reductions/broadcasts
are exercised with per-element weights, not an all-ones dy.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import bigdl_tpu.nn as nn

torch = pytest.importorskip("torch")


def rng():
    return jax.random.key(0)


def _np(shape, seed=0, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape) * scale
            ).astype(np.float32)


def _t(a, requires_grad=False):
    t = torch.tensor(np.asarray(a))
    if requires_grad:
        t.requires_grad_(True)
    return t


def _our_grads(m, x, cot, training=True):
    """d loss / d (params, x) for loss = sum(apply(x) * cot)."""

    def loss(params, xx):
        out, _ = m.apply(params, m.state, xx, training=training,
                         rng=jax.random.key(1))
        return jnp.sum(out * cot)

    gp, gx = jax.grad(loss, (0, 1))(m.params, jnp.asarray(x))
    return jax.tree.map(np.asarray, gp), np.asarray(gx)


def test_conv2d_grads_match_torch():
    m = nn.SpatialConvolution(3, 8, 5, 3, 2, 1, 2, 1).build(rng())
    x = _np((2, 9, 11, 3), 1)
    cot = _np((2, 9, 6, 8), 2)          # NHWC cotangent (h=9/1 pad1k3; w=6)
    gp, gx = _our_grads(m, x, jnp.asarray(cot))

    conv = torch.nn.Conv2d(3, 8, kernel_size=(3, 5), stride=(1, 2),
                           padding=(1, 2))
    with torch.no_grad():
        conv.weight.copy_(_t(np.asarray(m.params["weight"]).transpose(3, 2, 0, 1)))
        conv.bias.copy_(_t(np.asarray(m.params["bias"])))
    xt = _t(x.transpose(0, 3, 1, 2), requires_grad=True)
    (conv(xt) * _t(cot.transpose(0, 3, 1, 2))).sum().backward()

    np.testing.assert_allclose(gx.transpose(0, 3, 1, 2), xt.grad.numpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gp["weight"].transpose(3, 2, 0, 1),
                               conv.weight.grad.numpy(), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(gp["bias"], conv.bias.grad.numpy(),
                               rtol=1e-4, atol=1e-4)


def test_linear_grads_match_torch():
    m = nn.Linear(7, 4).build(rng())
    x = _np((5, 7), 3)
    cot = _np((5, 4), 4)
    gp, gx = _our_grads(m, x, jnp.asarray(cot))

    lin = torch.nn.Linear(7, 4)
    with torch.no_grad():
        # ours (out, in) == torch (out, in) — reference nn/Linear.scala layout
        lin.weight.copy_(_t(np.asarray(m.params["weight"])))
        lin.bias.copy_(_t(np.asarray(m.params["bias"])))
    xt = _t(x, requires_grad=True)
    (lin(xt) * _t(cot)).sum().backward()

    np.testing.assert_allclose(gx, xt.grad.numpy(), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gp["weight"], lin.weight.grad.numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gp["bias"], lin.bias.grad.numpy(),
                               rtol=1e-5, atol=1e-5)


def test_batchnorm_train_mode_grads_match_torch():
    """Backward through the BATCH statistics, the program `resnet50.train`
    runs; torch differentiates through mean/var the same way."""
    m = nn.SpatialBatchNormalization(6, eps=1e-5, momentum=0.1).build(rng())
    bn = torch.nn.BatchNorm2d(6, eps=1e-5, momentum=0.1)
    with torch.no_grad():
        bn.weight.copy_(_t(np.asarray(m.params["weight"])))
        bn.bias.copy_(_t(np.asarray(m.params["bias"])))
    x = _np((4, 5, 5, 6), 5)
    cot = _np((4, 5, 5, 6), 6)
    gp, gx = _our_grads(m, x, jnp.asarray(cot), training=True)

    bn.train()
    xt = _t(x.transpose(0, 3, 1, 2), requires_grad=True)
    (bn(xt) * _t(cot.transpose(0, 3, 1, 2))).sum().backward()

    np.testing.assert_allclose(gx.transpose(0, 3, 1, 2), xt.grad.numpy(),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(gp["weight"], bn.weight.grad.numpy(),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(gp["bias"], bn.bias.grad.numpy(),
                               rtol=1e-3, atol=1e-4)
    # running-stat EMA (torch-lineage unbiased-var convention) must match
    _, new_state = m.apply(m.params, m.state, jnp.asarray(x), training=True)
    np.testing.assert_allclose(np.asarray(new_state["running_mean"]),
                               bn.running_mean.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state["running_var"]),
                               bn.running_var.numpy(), rtol=1e-4, atol=1e-5)


def test_maxpool_grad_matches_torch():
    """Routing of the cotangent to argmax positions (the reduce_window /
    select-and-scatter pair vs torch's MaxPool2d backward)."""
    m = nn.SpatialMaxPooling(2, 2, 2, 2).build(rng())
    x = _np((3, 8, 8, 4), 7)
    cot = _np((3, 4, 4, 4), 8)

    def loss(xx):
        out, _ = m.apply(m.params, m.state, xx, training=True, rng=None)
        return jnp.sum(out * jnp.asarray(cot))

    gx = np.asarray(jax.grad(loss)(jnp.asarray(x)))

    xt = _t(x.transpose(0, 3, 1, 2), requires_grad=True)
    (torch.nn.MaxPool2d(2, 2)(xt) * _t(cot.transpose(0, 3, 1, 2))
     ).sum().backward()
    np.testing.assert_allclose(gx.transpose(0, 3, 1, 2), xt.grad.numpy(),
                               rtol=1e-5, atol=1e-6)


def test_logsoftmax_nll_grad_matches_torch():
    """The classification loss path every zoo model trains through."""
    logits = _np((6, 9), 9)
    tgt = np.array([0, 3, 8, 1, 1, 5])
    crit = nn.ClassNLLCriterion()
    lsm = nn.LogSoftMax().build(rng())

    def loss(z):
        out, _ = lsm.apply(lsm.params, lsm.state, z, training=True, rng=None)
        return crit.loss(out, jnp.asarray(tgt))

    gz = np.asarray(jax.grad(loss)(jnp.asarray(logits)))

    zt = _t(logits, requires_grad=True)
    torch.nn.NLLLoss()(torch.nn.LogSoftmax(dim=-1)(zt),
                       torch.tensor(tgt)).backward()
    np.testing.assert_allclose(gz, zt.grad.numpy(), rtol=1e-5, atol=1e-6)


def test_lstm_sequence_grads_match_torch():
    """Backprop-through-time through our lax.scan vs torch's unrolled cell
    loop: kernel/bias grads accumulated over all timesteps."""
    H, I, T, B = 7, 5, 4, 3
    m = nn.Recurrent(nn.LSTM(I, H)).build(rng())
    kernel = np.asarray(m.params[0]["kernel"])
    bias = np.asarray(m.params[0]["bias"])
    x = _np((B, T, I), 10)
    cot = _np((B, T, H), 11)

    def loss(params, xx):
        out, _ = m.apply(params, m.state, xx, training=True,
                         rng=jax.random.key(1))
        return jnp.sum(out * jnp.asarray(cot))

    gp, gx = jax.grad(loss, (0, 1))(m.params, jnp.asarray(x))
    gk, gb = np.asarray(gp[0]["kernel"]), np.asarray(gp[0]["bias"])
    gx = np.asarray(gx)

    cell = torch.nn.LSTMCell(I, H)
    with torch.no_grad():
        cell.weight_ih.copy_(_t(kernel[:I].T))
        cell.weight_hh.copy_(_t(kernel[I:].T))
        cell.bias_ih.copy_(_t(bias))
        cell.bias_hh.copy_(torch.zeros(4 * H))
    xt = _t(x, requires_grad=True)
    h = torch.zeros(B, H)
    c = torch.zeros(B, H)
    total = torch.zeros(())
    for t in range(T):
        h, c = cell(xt[:, t], (h, c))
        total = total + (h * _t(cot[:, t])).sum()
    total.backward()

    np.testing.assert_allclose(gx, xt.grad.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gk[:I], cell.weight_ih.grad.numpy().T,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gk[I:], cell.weight_hh.grad.numpy().T,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gb, cell.bias_ih.grad.numpy(),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- forwards
# layers the round-2 forward suite did not cover against torch


def test_bilinear_matches_torch():
    m = nn.Bilinear(4, 5, 3).build(rng())
    x1, x2 = _np((6, 4), 12), _np((6, 5), 13)
    y = np.asarray(m.forward([jnp.asarray(x1), jnp.asarray(x2)]))
    bl = torch.nn.Bilinear(4, 5, 3)
    with torch.no_grad():
        bl.weight.copy_(_t(np.asarray(m.params["weight"])))
        bl.bias.copy_(_t(np.asarray(m.params["bias"])))
        ref = bl(_t(x1), _t(x2)).numpy()
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_temporal_convolution_matches_torch_conv1d():
    m = nn.TemporalConvolution(5, 8, 3, 2).build(rng())
    x = _np((2, 12, 5), 14)             # (batch, time, features)
    y = np.asarray(m.forward(jnp.asarray(x)))
    conv = torch.nn.Conv1d(5, 8, 3, stride=2)
    with torch.no_grad():
        # ours (k, in, out) -> torch (out, in, k)
        conv.weight.copy_(_t(np.asarray(m.params["weight"]).transpose(2, 1, 0)))
        conv.bias.copy_(_t(np.asarray(m.params["bias"])))
        ref = conv(_t(x.transpose(0, 2, 1))).numpy()  # (B, out, T')
    np.testing.assert_allclose(y.transpose(0, 2, 1), ref,
                               rtol=1e-4, atol=1e-5)


def test_prelu_matches_torch():
    for n, torch_n in ((0, 1), (5, 5)):
        m = nn.PReLU(n).build(rng())
        x = _np((3, 4, 4, 5), 15)
        y = np.asarray(m.forward(jnp.asarray(x)))
        pr = torch.nn.PReLU(torch_n, init=0.25)
        with torch.no_grad():
            ref = pr(_t(x.transpose(0, 3, 1, 2))).numpy()
        np.testing.assert_allclose(y.transpose(0, 3, 1, 2), ref,
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------- criterions
# margin/embedding family vs the torch losses of the same Torch lineage


def test_cosine_embedding_matches_torch():
    c = nn.CosineEmbeddingCriterion(margin=0.2)
    x1, x2 = _np((5, 6), 16), _np((5, 6), 17)
    y = np.array([1, -1, 1, -1, -1], np.float32)
    ours = float(c.loss([jnp.asarray(x1), jnp.asarray(x2)], jnp.asarray(y)))
    ref = float(torch.nn.CosineEmbeddingLoss(margin=0.2)(
        _t(x1), _t(x2), _t(y)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_hinge_embedding_matches_torch():
    c = nn.HingeEmbeddingCriterion(margin=1.5)
    x = _np((8,), 18)
    y = np.array([1, -1, 1, -1, 1, -1, -1, 1], np.float32)
    ours = float(c.loss(jnp.asarray(x), jnp.asarray(y)))
    ref = float(torch.nn.HingeEmbeddingLoss(margin=1.5)(_t(x), _t(y)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_margin_ranking_matches_torch():
    c = nn.MarginRankingCriterion(margin=0.3)
    x1, x2 = _np((7,), 19), _np((7,), 20)
    y = np.array([1, -1, 1, 1, -1, -1, 1], np.float32)
    ours = float(c.loss([jnp.asarray(x1), jnp.asarray(x2)], jnp.asarray(y)))
    ref = float(torch.nn.MarginRankingLoss(margin=0.3)(_t(x1), _t(x2), _t(y)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


@pytest.mark.parametrize("p", [1, 2])
def test_multi_margin_matches_torch(p):
    c = nn.MultiMarginCriterion(p=p, margin=1.0)
    x = _np((6, 9), 21)
    t = np.array([0, 4, 8, 2, 2, 7])
    ours = float(c.loss(jnp.asarray(x), jnp.asarray(t)))
    ref = float(torch.nn.MultiMarginLoss(p=p, margin=1.0)(
        _t(x), torch.tensor(t)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_multilabel_soft_margin_matches_torch():
    c = nn.MultiLabelSoftMarginCriterion()
    x = _np((4, 6), 22)
    t = (np.random.default_rng(23).random((4, 6)) > 0.5).astype(np.float32)
    ours = float(c.loss(jnp.asarray(x), jnp.asarray(t)))
    ref = float(torch.nn.MultiLabelSoftMarginLoss()(_t(x), _t(t)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_soft_margin_matches_torch():
    c = nn.SoftMarginCriterion()
    x = _np((3, 5), 24)
    y = np.sign(_np((3, 5), 25)).astype(np.float32)
    ours = float(c.loss(jnp.asarray(x), jnp.asarray(y)))
    ref = float(torch.nn.SoftMarginLoss()(_t(x), _t(y)))
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_criterion_grads_match_torch():
    """Backward parity for the two losses the zoo trains with."""
    logits = _np((5, 7), 26)
    tgt = np.array([2, 0, 6, 3, 1])

    ce = nn.CrossEntropyCriterion()
    g = np.asarray(jax.grad(
        lambda z: ce.loss(z, jnp.asarray(tgt)))(jnp.asarray(logits)))
    zt = _t(logits, requires_grad=True)
    torch.nn.CrossEntropyLoss()(zt, torch.tensor(tgt)).backward()
    np.testing.assert_allclose(g, zt.grad.numpy(), rtol=1e-5, atol=1e-6)

    mse = nn.MSECriterion()
    x, y = _np((4, 6), 27), _np((4, 6), 28)
    g = np.asarray(jax.grad(
        lambda z: mse.loss(z, jnp.asarray(y)))(jnp.asarray(x)))
    xt = _t(x, requires_grad=True)
    torch.nn.MSELoss()(xt, _t(y)).backward()
    np.testing.assert_allclose(g, xt.grad.numpy(), rtol=1e-5, atol=1e-6)


def test_gru_sequence_grads_match_torch_autograd():
    """BPTT through our fused-gate GRU scan vs torch AUTOGRAD over the same
    equations.  torch.nn.GRUCell is a different GRU variant (reset gate
    applied AFTER the hidden matmul, r*(W_hn h); ours — the original GRU and
    the reference nn/GRU.scala — applies it BEFORE, W_cand (r*h)), so the
    cells are not weight-mappable.  The forward golden already pins our
    equations against a numpy loop; here torch's tape differentiates the
    identical unrolled math, independently checking the lax.scan VJP."""
    H, I, T, B = 6, 4, 3, 2
    m = nn.Recurrent(nn.GRU(I, H)).build(rng())
    p = m.params[0]
    gk = np.asarray(p["gate_kernel"])   # (I+H, 2H) -> (r, u)
    gb = np.asarray(p["gate_bias"])
    ck = np.asarray(p["cand_kernel"])   # (I+H, H)
    cb = np.asarray(p["cand_bias"])
    x = _np((B, T, I), 30)
    cot = _np((B, T, H), 31)

    gp, gx = _our_grads(m, x, jnp.asarray(cot))

    gk_t = _t(gk, requires_grad=True)
    gb_t = _t(gb, requires_grad=True)
    ck_t = _t(ck, requires_grad=True)
    cb_t = _t(cb, requires_grad=True)
    xt = _t(x, requires_grad=True)
    h = torch.zeros(B, H)
    total = torch.zeros(())
    for t in range(T):
        zin = torch.cat([xt[:, t], h], dim=-1)
        gates = torch.sigmoid(zin @ gk_t + gb_t)
        r, u = gates[:, :H], gates[:, H:]
        cin = torch.cat([xt[:, t], r * h], dim=-1)
        cand = torch.tanh(cin @ ck_t + cb_t)
        h = (1.0 - u) * h + u * cand
        total = total + (h * _t(cot[:, t])).sum()
    total.backward()

    np.testing.assert_allclose(gx, xt.grad.numpy(), rtol=1e-4, atol=1e-5)
    for ours, theirs in ((gp[0]["gate_kernel"], gk_t), (gp[0]["gate_bias"], gb_t),
                         (gp[0]["cand_kernel"], ck_t), (gp[0]["cand_bias"], cb_t)):
        np.testing.assert_allclose(np.asarray(ours), theirs.grad.numpy(),
                                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------- transformer family
# the long-context flagship's building blocks vs the torch oracle


def test_layernorm_grads_match_torch():
    m = nn.LayerNorm(6).build(rng())
    x = _np((4, 5, 6), 40)
    cot = _np((4, 5, 6), 41)
    gp, gx = _our_grads(m, x, jnp.asarray(cot), training=False)

    ln = torch.nn.LayerNorm(6, eps=1e-5)
    with torch.no_grad():
        ln.weight.copy_(_t(np.asarray(m.params["weight"])))
        ln.bias.copy_(_t(np.asarray(m.params["bias"])))
    xt = _t(x, requires_grad=True)
    (ln(xt) * _t(cot)).sum().backward()

    np.testing.assert_allclose(gx, xt.grad.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gp["weight"], ln.weight.grad.numpy(),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gp["bias"], ln.bias.grad.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_gelu_matches_torch_tanh_approximation():
    """jax.nn.gelu defaults to the tanh approximation — torch's
    GELU(approximate='tanh'), not the exact erf form."""
    m = nn.GELU().build(rng())
    x = _np((7, 9), 42, scale=2.0)
    y = np.asarray(m.forward(jnp.asarray(x)))
    ref = torch.nn.GELU(approximate="tanh")(_t(x)).numpy()
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)

    def loss(z):
        out, _ = m.apply(m.params, m.state, z, training=False, rng=None)
        return jnp.sum(out ** 2)

    gx = np.asarray(jax.grad(loss)(jnp.asarray(x)))
    xt = _t(x, requires_grad=True)
    (torch.nn.GELU(approximate="tanh")(xt) ** 2).sum().backward()
    np.testing.assert_allclose(gx, xt.grad.numpy(), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_multihead_attention_matches_torch(causal):
    """Ours: y = proj(x) with (in, out) weights; torch packs QKV row-major
    (3E, E) applied as x @ W^T — map W_q = wq.T etc.  Forward AND input
    grads must agree (softmax/scale/mask conventions)."""
    E, H, B, T = 8, 2, 2, 5
    m = nn.MultiHeadAttention(E, H, causal=causal).build(rng())
    x = _np((B, T, E), 43)
    cot = _np((B, T, E), 44)

    gp, gx = _our_grads(m, x, jnp.asarray(cot), training=False)
    y = np.asarray(m.apply(m.params, m.state, jnp.asarray(x),
                           training=False, rng=None)[0])

    mha = torch.nn.MultiheadAttention(E, H, batch_first=True, bias=True)
    p = {k: np.asarray(v) for k, v in m.params.items()}
    with torch.no_grad():
        mha.in_proj_weight.copy_(_t(np.concatenate(
            [p["wq"].T, p["wk"].T, p["wv"].T], axis=0)))
        mha.in_proj_bias.copy_(_t(np.concatenate(
            [p["bq"], p["bk"], p["bv"]])))
        mha.out_proj.weight.copy_(_t(p["wo"].T))
        mha.out_proj.bias.copy_(_t(p["bo"]))
    xt = _t(x, requires_grad=True)
    mask = (torch.triu(torch.ones(T, T), diagonal=1).bool()
            if causal else None)
    ref, _ = mha(xt, xt, xt, attn_mask=mask, need_weights=False)
    np.testing.assert_allclose(y, ref.detach().numpy(),
                               rtol=1e-4, atol=1e-5)
    (ref * _t(cot)).sum().backward()
    np.testing.assert_allclose(np.asarray(gx), xt.grad.numpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gp["wo"]).T,
                               mha.out_proj.weight.grad.numpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gp["bo"]),
                               mha.out_proj.bias.grad.numpy(),
                               rtol=1e-4, atol=1e-4)
    # QKV parameter grads: torch packs them (3E, E) row-major as x @ W^T
    ipw = mha.in_proj_weight.grad.numpy()
    ipb = mha.in_proj_bias.grad.numpy()
    E = 8
    for i, (wk_, bk_) in enumerate((("wq", "bq"), ("wk", "bk"),
                                    ("wv", "bv"))):
        np.testing.assert_allclose(np.asarray(gp[wk_]).T,
                                   ipw[i * E:(i + 1) * E],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gp[bk_]),
                                   ipb[i * E:(i + 1) * E],
                                   rtol=1e-4, atol=1e-4)
