"""Device selection says what it found and never makes a device up: the
Pallas routes' interpret flag is never true on a TPU."""

import pytest


@pytest.mark.parametrize("where", ["bn", "convbn"])
def test_interpret_is_never_true_on_a_tpu(monkeypatch, where):
    """BN_IMPL=pallas_interpret is for CPU tests; on platform `tpu` the
    place that computes `interpret` refuses it."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn import fused
    monkeypatch.setenv("BIGDL_TPU_BN_IMPL", "pallas_interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(AssertionError, match="pallas_interpret on a TPU"):
        if where == "convbn":
            fused._engagement(True, 8)
        else:
            bn = nn.SpatialBatchNormalization(4).build()
            bn._route_pallas(bn.params, bn.state,
                             jnp.zeros((2, 3, 3, 4)), (0, 1, 2),
                             "pallas_interpret")
