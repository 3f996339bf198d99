"""Scaling-evidence tooling (bigdl_tpu/tools/scaling.py): the compiled
distributed train step must contain real XLA collectives, and the HLO
introspection that dryrun_multichip relies on must find them.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import bigdl_tpu.nn as nn
from bigdl_tpu.optim import Optimizer, SGD, Trigger
from bigdl_tpu.tools.scaling import collective_counts
from bigdl_tpu.models.lenet import LeNet5


def test_dp_step_contains_gradient_allreduce():
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    model = LeNet5(10).build(jax.random.key(0))
    opt = Optimizer(model, dataset=None, criterion=nn.ClassNLLCriterion(),
                    end_trigger=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.05))
    step, param_sh, data_sh = opt._build_step(mesh)
    params = jax.device_put(model.params, param_sh)
    opt_state = opt.optim_method.init_state(params)
    inp = jax.device_put(jnp.zeros((16, 28, 28, 1), jnp.float32), data_sh)
    tgt = jax.device_put(jnp.ones((16,), jnp.int32), data_sh)
    compiled = step.lower(params, model.state, opt_state, inp, tgt,
                          jnp.float32(0.05), jax.random.key(1)).compile()
    colls = collective_counts(compiled.as_text())
    assert colls.get("all-reduce", 0) >= 1, colls


def test_collective_counts_parses_hlo_snippets():
    hlo = """
    %all-reduce.1 = f32[100]{0} all-reduce(%p), replica_groups={}
    %all-gather.2 = f32[8,4]{1,0} all-gather(%x), dimensions={0}
    %add.3 = f32[] add(%a, %b)
    """
    counts = collective_counts(hlo)
    assert counts.get("all-reduce") == 1
    assert counts.get("all-gather") == 1
    assert "reduce-scatter" not in counts


def test_collective_counts_tuple_results_tiled_layouts_async_pairs():
    """What a TPU's optimized HLO looks like: gradient all-reduces combined
    into one instruction with a TUPLE result, layouts that carry tiles with
    parentheses of their own, and async start/done pairs (one collective,
    two instructions)."""
    hlo = """
  %all-reduce.1 = f32[100]{0} all-reduce(%p), to_apply=%add, metadata={op_name="jit(f)/psum x(y)"}
  %all-reduce.2 = (f32[100]{0}, bf16[8,8]{1,0:T(8,128)(2,1)}) all-reduce(%p, %q), to_apply=%add
  %ars = (f32[1]{0}, f32[2]{0:T(256)}) all-reduce-start(%p, %q)
  %ard = (f32[1]{0}, f32[2]{0:T(256)}) all-reduce-done(%ars)
  %cc = (f32[8]{0:T(8)S(1)}, u8[16]{0}) custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %t = (f32[], f32[]) tuple(%all-reduce.1, %a)
    """
    assert collective_counts(hlo) == {"all-reduce": 3}
    from bigdl_tpu.utils import hlostats
    hist = hlostats.op_histogram(hlo)
    assert hist["custom-call"] == 1 and hist["tuple"] == 1
    assert hlostats.collective_count(hist) == 3


def test_strategy_collective_signatures():
    """Each parallelism strategy must lower to its expected ICI collectives
    on the virtual mesh (evidence the strategies are real XLA programs, not
    Python-side simulations): DP = one gradient all-reduce; ZeRO adds
    all-gathers of the sharded params/opt-state; engaged TP adds
    activation-path collectives beyond the single gradient all-reduce;
    ring SP = a collective-permute chain; Ulysses SP = all-to-alls."""
    from bigdl_tpu.tools.scaling import strategy_signatures

    sig = strategy_signatures(8)
    # >= 1, not == 1: how many all-reduces the gradients are combined into
    # is XLA's choice
    assert sig["dp8"].get("all-reduce", 0) >= 1, sig["dp8"]
    assert sig["zero8"].get("all-gather", 0) >= 1, sig["zero8"]
    tp = sig["dp4xtp2"]
    assert sum(tp.values()) > 1 and tp.get("all-reduce", 0) >= 1, tp
    assert sig["ring_sp8"].get("collective-permute", 0) >= 1, sig["ring_sp8"]
    assert sig["ulysses_sp8"].get("all-to-all", 0) >= 1, sig["ulysses_sp8"]
