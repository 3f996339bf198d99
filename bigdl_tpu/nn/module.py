"""Module system: the TPU-native re-design of BigDL's AbstractModule.

Reference: `nn/abstractnn/AbstractModule.scala:54` defines a *stateful* Torch-style
module: mutable `output`/`gradInput` caches (:62,67), `forward` = timed
`updateOutput` (:213), `backward` = `updateGradInput` + `accGradParameters` (:231),
`parameters()` exposing weight/gradient tensor pairs, and `getParameters()` (:284)
flattening everything into ONE contiguous weight vector + ONE gradient vector — the
contract BigDL's whole distributed design hangs off.

TPU-native re-design
--------------------
The mutable-module style cannot live inside `jax.jit` (tracing requires pure
functions), so each Module here is two things at once:

1. **A pure functional core** — `init(rng) -> (params, state)` and
   `apply(params, state, input, training, rng) -> (output, new_state)` where
   `params`/`state` are pytrees.  This is what the Optimizer jits/pjits: a whole
   train step (forward + loss + backward + update + psum) compiles to one XLA
   program, where BigDL dispatched each op separately to MKL via JNI
   (tensor/TensorNumeric.scala:195-312).

2. **A thin stateful facade** for API parity and interactive use — `forward`,
   `backward`, `zero_grad_parameters`, `update_parameters`, `parameters`,
   `get_parameters` behave like the reference (backward computes gradInput via
   `jax.vjp` and *accumulates* parameter gradients, matching accGradParameters
   semantics).

`Activity` (Tensor ∨ Table union, nn/abstractnn/Activity.scala) needs no machinery:
any pytree (array, list, dict, Table) is a valid input/output.
"""

from __future__ import annotations

import itertools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import get_policy, next_rng_key

__all__ = ["Module", "Container", "Criterion", "StateLeaf", "prefill_rows",
           "write_prompt_rows"]


class StateLeaf(NamedTuple):
    """One array a layer keeps between decode steps (``Module.decode_state``):
    its shape for ``rows`` sequences of ``length`` positions, and the layout
    role that places it on a mesh (parallel/layout.ROLES).  There are two
    kinds.  A leaf with a ``length_axis`` holds something of every position
    (keys and values, a latent): it grows by a page along that axis, and
    what lies past a row's position is masked, so a prefill may leave its
    pads' rows there.  A leaf whose ``length_axis`` is ``None`` is of fixed
    size a row whatever the length (a recurrent state, a convolution's last
    inputs): nothing masks it, so a prefill writes the row whole, as it is
    after the prompt's last real position, and a growing cache carries it
    over bit for bit.  ``dtype``: the leaf's own where it must not follow
    the cache's (a recurrence summed in float32); ``None`` is the cache's."""
    shape: tuple
    length_axis: Optional[int]
    role: str
    dtype: Any = None


def prefill_rows(x, slot, length):
    """A prefill's ``slot`` and ``length`` as int32 ``[n]``, one of each a
    row of ``x [n, P, ...]`` (a scalar stands for every row)."""
    return tuple(jnp.broadcast_to(jnp.asarray(a, jnp.int32), x.shape[:1])
                 for a in (slot, length))


def write_prompt_rows(cache, slot, new):
    """How a prefill puts a group's rows into its donated state: ``new [n,
    ...]`` into rows ``slot [n]`` of ``cache [S, ...]``, from the start of
    every later axis (a leaf with a length axis takes the P positions
    computed, a leaf of fixed size the whole row): one scatter of n windows
    a leaf.  A row whose slot lies past ``S`` is dropped: it writes
    nothing.  A lone row (n == 1, known at trace time) is its call's one
    request, never a fill-up row, and is written by a dynamic-update-slice:
    the compiler fuses that into the product that computes the window,
    where a scatter of one window stays an operation of its own."""
    new = new.astype(cache.dtype)
    if new.shape[0] == 1:
        return jax.lax.dynamic_update_slice(
            cache, new, (slot[0],) + (0,) * (new.ndim - 1))
    at = (slot,) + tuple(slice(0, d) for d in new.shape[1:])
    return cache.at[at].set(new, mode="drop")


_uid_counter = itertools.count()


#: bumped by every set_scale_w/set_scale_b anywhere — lets cached
#: grad-scale trees (facade) and compiled steps (Optimizer) detect scale
#: changes without parent/child cache-invalidation plumbing
_SCALE_EPOCH = [0]


def scale_epoch() -> int:
    return _SCALE_EPOCH[0]


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


class Module:
    """Base class for all layers (BigDL: AbstractModule, abstractnn/AbstractModule.scala:54)."""

    #: parameter-name -> role string for the mesh-layout assigner
    #: (parallel/layout.py): modules declare WHAT each parameter is
    #: ("kernel_out", "embedding_row", "bias", ...) and the canonical
    #: role table decides how it shards over the data/fsdp/tp mesh.
    #: None (the default) = unannotated — the assigner fails loudly on
    #: such leaves instead of silently replicating them.  "*" is a
    #: wildcard entry covering every remaining name.
    PARAM_ROLES = None

    def __init__(self):
        self.name = f"{type(self).__name__}_{next(_uid_counter)}"
        self.training_mode: bool = True
        # facade state
        self.params = None   # pytree of parameters (None until build())
        self.state = None    # pytree of non-trained state (e.g. BN running stats)
        # accumulated parameter gradients (accGradParameters): zeros the
        # size of the weights, made when first read (the ``grads`` property)
        self._grads = None
        self._grads_due = False
        self.output = None
        self.grad_input = None
        self._last_rng = None
        # per-module gradient scaling (AbstractModule.scala:73 scaleW/scaleB);
        # property-backed so even direct assignment bumps the scale epoch
        self._scale_w: float = 1.0
        self._scale_b: float = 1.0
        # initializer overrides (nn/abstractnn/Initializable.scala:23)
        self.weight_initializer = None
        self.bias_initializer = None

    @property
    def grads(self):
        """The accumulated gradients.  After ``build()`` or ``attach()`` they
        are zeros like the weights; those zeros are made here, on the first
        read, so a model that only serves never holds them."""
        if getattr(self, "_grads", None) is None \
                and getattr(self, "_grads_due", False) \
                and self.params is not None:
            self._grads = _tree_zeros_like(self.params)
            self._grads_due = False
        return getattr(self, "_grads", None)

    @grads.setter
    def grads(self, value):
        self._grads = value
        self._grads_due = False

    # scale_w/scale_b are properties so that DIRECT attribute assignment
    # (m.scale_w = 2.0) also bumps the scale epoch — otherwise a cached
    # grad-scale tree or an already-compiled step would keep applying the
    # stale scale with no error.  set_scale_w/set_scale_b remain the
    # container-propagating API.
    @property
    def scale_w(self) -> float:
        return self._scale_w

    @scale_w.setter
    def scale_w(self, s: float):
        self._scale_w = s
        _SCALE_EPOCH[0] += 1

    @property
    def scale_b(self) -> float:
        return self._scale_b

    @scale_b.setter
    def scale_b(self, s: float):
        self._scale_b = s
        _SCALE_EPOCH[0] += 1

    # ------------------------------------------------------------------
    # pure functional core — override _init / _apply (stateless layers) or
    # init / apply (layers with state or randomness)
    # ------------------------------------------------------------------

    def init(self, rng):
        """Create (params, state) pytrees."""
        return self._init(rng), self._init_state()

    def _init(self, rng):
        return {}

    def _init_state(self):
        return {}

    def apply(self, params, state, input, *, training: bool = False, rng=None):
        """Pure forward. Returns (output, new_state)."""
        return self._apply(params, input), state

    def _apply(self, params, input):
        raise NotImplementedError(
            f"{type(self).__name__} must implement _apply or apply")

    def has_params(self) -> bool:
        return len(jax.tree.leaves(self.init(jax.random.key(0))[0])) > 0

    # -- incremental decoding (models/decode.py, serve/decode.py) ---------
    # A layer whose output at one position depends on earlier positions, or
    # on the position itself, says so here; every other layer is applied to
    # the new position alone by its own ``apply``.

    def decode_state(self, rows: int, length: int):
        """What this layer keeps for ``rows`` sequences of ``length``
        positions: ``{leaf name: StateLeaf}``.  None (the default): nothing,
        and the position does not matter.  An empty dict: no state, but the
        layer needs the position, or which tokens are real
        (``decode_prefill``/``decode_step``); what such a layer returns in
        the cache's place is its report of the call (a small vector that a
        walk sums over the layers, e.g. tokens an expert took) or None."""
        return None

    def decode_prefill(self, params, x, cache, slot, length):
        """A group of n whole prompts from position 0, ``x [n, P, ...]``;
        of row i the first ``length[i]`` positions are real and the rest
        pads, and it enters row ``slot[i]`` of ``cache`` (this layer's
        leaves; ``slot`` and ``length`` int32 ``[n]``, or a scalar for
        every row: ``prefill_rows``).  A row whose slot lies past the
        cache's rows fills the program up and writes nothing
        (``write_prompt_rows``).  Returns (y, cache)."""
        raise NotImplementedError(type(self).__name__)

    def decode_step(self, params, x, cache, pos):
        """One position a row: ``x [rows, 1, ...]`` at positions ``pos
        [rows]``; returns (y, cache) with ``pos`` written.  A row whose
        ``pos`` is negative is idle: it computes position 0, is counted
        nowhere, and what it writes a prefill overwrites."""
        raise NotImplementedError(type(self).__name__)

    def param_roles(self):
        """name -> role map for THIS module's own parameters (see
        PARAM_ROLES; containers are never asked — the layout assigner
        recurses into their children instead, and parameter-free
        modules have no leaves to resolve).  None = unannotated."""
        return self.PARAM_ROLES

    # ------------------------------------------------------------------
    # stateful facade (Torch-style API parity)
    # ------------------------------------------------------------------

    def build(self, rng=None):
        """Materialize parameters (lazy; called automatically on first forward)."""
        if rng is None:
            rng = next_rng_key()
        self.params, self.state = self.init(rng)
        self._grads, self._grads_due = None, True
        return self

    def set_init_method(self, weight_init=None, bias_init=None):
        """BigDL: Initializable.setInitMethod (abstractnn/Initializable.scala:29)."""
        self.weight_initializer = weight_init
        self.bias_initializer = bias_init
        if self.params is not None:
            self.build()
        return self

    def forward(self, input):
        """BigDL: AbstractModule.forward (AbstractModule.scala:213)."""
        if self.params is None:
            self.build()
        rng = next_rng_key()
        self._last_rng = rng
        out, new_state = self.apply(self.params, self.state, input,
                                    training=self.training_mode, rng=rng)
        self.state = new_state
        self.output = out
        return out

    __call__ = forward

    def backward(self, input, grad_output):
        """gradInput + accumulated parameter grads (AbstractModule.scala:231-236)."""
        if self.params is None:
            raise RuntimeError("backward before forward")

        def f(p, x):
            y, _ = self.apply(p, self.state, x, training=self.training_mode,
                              rng=self._last_rng)
            return y

        _, vjp = jax.vjp(f, self.params, input)
        gp, gx = vjp(grad_output)
        gp = self._scale_param_grads(gp)
        self.grads = _tree_add(self.grads, gp)
        self.grad_input = gx
        return gx

    def update_grad_input(self, input, grad_output):
        """BigDL: updateGradInput — gradInput only, no param-grad accumulation."""
        def f(x):
            y, _ = self.apply(self.params, self.state, x,
                              training=self.training_mode, rng=self._last_rng)
            return y
        _, vjp = jax.vjp(f, input)
        (gx,) = vjp(grad_output)
        self.grad_input = gx
        return gx

    def acc_grad_parameters(self, input, grad_output):
        """BigDL: accGradParameters — accumulate dL/dParams only."""
        def f(p):
            y, _ = self.apply(p, self.state, input,
                              training=self.training_mode, rng=self._last_rng)
            return y
        _, vjp = jax.vjp(f, self.params)
        (gp,) = vjp(grad_output)
        self.grads = _tree_add(self.grads, self._scale_param_grads(gp))

    def _scale_param_grads(self, gp):
        """Facade-path scaling: same tree the compiled step uses, so the
        two paths cannot diverge."""
        st = self._grad_scale_tree()
        if st is None:
            return gp
        return jax.tree.map(lambda g, s: g * s, gp, st)

    def _grad_scale_tree(self, params=None):
        """Per-leaf gradient scale factors matching the params tree
        (scaleW/scaleB, AbstractModule.scala:73; the reference applies them
        inside accGradParameters so layer-wise LR scaling reaches the
        DISTRIBUTED update too — DistriOptimizer.scala:729
        isLayerwiseScaled).  Container-level scales reach leaves because
        Container.set_scale_w/b PROPAGATE to children (the reference's
        Container.setScaleW semantics) — set scales through the setters,
        not by attribute assignment.  Returns None when every module's
        scales are 1 so the compiled step skips the multiply entirely."""
        if params is None:
            if self.params is None:
                self.build()
            params = self.params
            # static between set_scale calls — cache per scale epoch so the
            # facade backward's common all-ones case costs one int compare
            cached = getattr(self, "_scale_tree_cache", None)
            if cached is not None and cached[0] == _SCALE_EPOCH[0]:
                return cached[1]
        tree = None
        if not all(m.scale_w == 1.0 and m.scale_b == 1.0
                   for m in self.unique_modules()):
            tree = self._walk_scales(self, params)
        if params is self.params:
            self._scale_tree_cache = (_SCALE_EPOCH[0], tree)
        return tree

    @staticmethod
    def _walk_scales(root, params):
        def walk(mod, p):
            if hasattr(mod, "modules") and isinstance(p, list):
                return [walk(c, cp) for c, cp in zip(mod.modules, p)]

            def f(path, leaf):
                key = path[-1].key if hasattr(path[-1], "key") else ""
                return float(mod.scale_b if key == "bias" else mod.scale_w)

            return jax.tree_util.tree_map_with_path(f, p)

        return walk(root, params)

    # -- parameter access ----------------------------------------------

    def parameters(self):
        """(weights, gradWeights) leaf lists (BigDL: AbstractModule.parameters)."""
        if self.params is None:
            self.build()
        return jax.tree.leaves(self.params), jax.tree.leaves(self.grads)

    def get_parameters(self):
        """ONE flat weight vector + ONE flat gradient vector.

        BigDL contract: AbstractModule.getParameters (AbstractModule.scala:284)
        flattens all parameters into a single contiguous tensor pair; the
        distributed optimizer slices that flat vector across nodes.  JAX arrays
        are immutable so these are copies, not views — the compiled train step
        never uses this path (it maps pytrees directly); it exists for API parity,
        checkpoint compactness, and tests.
        """
        ws, gs = self.parameters()
        if not ws:
            return jnp.zeros((0,)), jnp.zeros((0,))
        return (jnp.concatenate([w.reshape(-1) for w in ws]),
                jnp.concatenate([g.reshape(-1) for g in gs]))

    def set_flat_parameters(self, flat):
        """Inverse of get_parameters()[0]: scatter a flat vector back."""
        leaves, treedef = jax.tree.flatten(self.params)
        out, off = [], 0
        for leaf in leaves:
            n = leaf.size
            out.append(jnp.asarray(flat[off:off + n]).reshape(leaf.shape).astype(leaf.dtype))
            off += n
        self.params = jax.tree.unflatten(treedef, out)
        return self

    def zero_grad_parameters(self):
        if self._grads is not None:   # zeros not made yet are zeros already
            self.grads = _tree_zeros_like(self._grads)

    def update_parameters(self, learning_rate: float):
        """w -= lr * gradW (BigDL: AbstractModule.updateParameters)."""
        self.params = jax.tree.map(
            lambda w, g: w - learning_rate * g, self.params, self.grads)

    def get_parameters_table(self):
        """name -> params dict (BigDL: getParametersTable, used by summaries)."""
        return {self.name: self.params}

    def summary(self, print_fn=print) -> str:
        """Keras/torchsummary-style parameter table (net-new ergonomics vs
        the reference, whose closest analog is the bare __repr__ tree):
        one row per leaf module with its parameter count and dtypes, plus
        totals.  Returns the rendered string (also sent to print_fn)."""
        if self.params is None:
            self.build()
        rows = []

        def count(p):
            leaves = jax.tree.leaves(p)
            return (sum(l.size for l in leaves),
                    ",".join(sorted({str(l.dtype) for l in leaves})) or "-")

        def walk(module, params, depth):
            n, dt = count(params)
            label = "  " * depth + type(module).__name__
            rows.append((label, n, dt))
            # Container AND Graph (which subclasses Module directly) both
            # keep child params list-aligned with .modules — recurse on the
            # structural property so imported Caffe/TF Graphs break down too
            children = getattr(module, "modules", None)
            if children is not None and isinstance(params, list) and \
                    len(children) == len(params):
                for m, p in zip(children, params):
                    walk(m, p, depth + 1)

        walk(self, self.params, 0)
        width = max(len(r[0]) for r in rows) + 2
        total = rows[0][1]  # the root row already counted everything
        body = [f"{lbl:<{width}}{n:>12,}  {dt}" for lbl, n, dt in rows]
        header = f"{'Layer':<{width}}{'Params':>12}  Dtypes"
        rule = "-" * max(len(header), max(len(b) for b in body))
        lines = ([header, rule] + body
                 + [rule, f"{'Total':<{width}}{total:>12,}"])
        text = "\n".join(lines)
        if print_fn is not None:
            print_fn(text)
        return text

    # -- native-format persistence ------------------------------------
    # (reference: Module.save/Module.load, nn/Module.scala:41 over JVM
    # serialization in utils/File.scala; here: pickle of the module with
    # weights detached — the same strip trick ModelBroadcast uses,
    # models/utils/ModelBroadcast.scala:66)

    def save(self, path: str, overwrite: bool = True):
        import numpy as _np

        from ..utils import file_io
        to_np = lambda t: jax.tree.map(_np.asarray, t) if t is not None \
            else None
        detached = (self.params, self.state, self._grads, self._grads_due,
                    self.output, self.grad_input)
        self.params = self.state = self.grads = None
        self.output = self.grad_input = None
        try:
            blob = {"format": "bigdl_tpu-module-v1", "module": self,
                    "params": to_np(detached[0]), "state": to_np(detached[1])}
            file_io.save(blob, path, overwrite=overwrite)
        finally:
            (self.params, self.state, self._grads, self._grads_due,
             self.output, self.grad_input) = detached
        return self

    @staticmethod
    def load(path: str) -> "Module":
        from ..utils import file_io
        blob = file_io.load(path)
        if not (isinstance(blob, dict) and
                blob.get("format") == "bigdl_tpu-module-v1"):
            raise ValueError(f"{path!r} is not a bigdl_tpu module file")
        m = blob["module"]
        m.attach(blob["params"], blob["state"])
        return m

    def attach(self, params, state=None):
        """Install externally-produced params (checkpoint/interop load) into
        the stateful facade, keeping grads consistent with build(): zeros
        like ``params``, made when ``grads`` is first read."""
        self.params = params
        if state is not None:
            self.state = state
        elif self.state is None:
            _, self.state = self.init(jax.random.key(0))
        self._grads, self._grads_due = None, params is not None
        return self

    # -- modes ---------------------------------------------------------

    def training(self):
        self.training_mode = True
        return self

    def evaluate(self, dataset=None, methods=None, batch_size=None):
        """No args: switch to eval mode (Torch semantics).  With a dataset
        and validation methods: bulk mesh-sharded evaluation — the
        reference's `model.evaluate(rdd, vMethods, batchSize)` overload
        (AbstractModule.scala:571 -> Evaluator, SURVEY.md §3.4)."""
        if dataset is None:
            self.training_mode = False
            return self
        if not methods:
            raise ValueError(
                "evaluate(dataset, ...) needs validation methods, e.g. "
                "[Top1Accuracy()] (AbstractModule.evaluate vMethods)")
        from ..optim.optimizer import Evaluator
        self.training_mode = False
        # list coercion + batch-size defaulting live in Evaluator.test so
        # every entry point (this facade, Evaluator, Validator) accepts the
        # same inputs
        return Evaluator(self).test(dataset, methods, batch_size=batch_size)

    def is_training(self) -> bool:
        return self.training_mode

    # -- misc parity helpers ------------------------------------------

    def get_times(self):
        """(module, forward_seconds, backward_seconds) triples for this module
        tree, populated by the most recent utils.profiling.ModuleProfiler run
        (reference: AbstractModule.getTimes, abstractnn/AbstractModule.scala:197
        — always-on there; opt-in here because per-layer timers cannot live
        inside one fused XLA program)."""
        return [(m, *getattr(m, "_profile_times", (0.0, 0.0)))
                for m in self.unique_modules()]

    def reset_times(self):
        """Clear profiling counters (AbstractModule.resetTimes:204)."""
        for m in self.unique_modules():
            if hasattr(m, "_profile_times"):
                del m._profile_times

    def unique_modules(self):
        """Pre-order walk of the module tree, visiting each INSTANCE once —
        shared (weight-tied) submodules appear a single time.  Shared by
        get_times/reset_times and utils.profiling.ModuleProfiler."""
        seen = set()

        def walk(m):
            if id(m) in seen:
                return
            seen.add(id(m))
            yield m
            for c in getattr(m, "modules", []):
                yield from walk(c)

        # note: the inner generator must be consumed, not returned, so the
        # seen-set is shared across recursion
        yield from walk(self)

    # -- facade parity: weight interchange, prediction, interop savers ---
    # (AbstractModule.scala's public surface beyond the training core)

    def update_output(self, input):
        """Alias of forward for reference-API parity (updateOutput is the
        compute half of AbstractModule.forward; this facade never separates
        them because timing lives in get_times' profiler instead)."""
        return self.forward(input)

    def get_scale_w(self) -> float:
        return self.scale_w

    def get_scale_b(self) -> float:
        return self.scale_b

    def inputs(self, *nodes):
        """Graph-building parity (`layer.inputs(node...)`,
        AbstractModule.scala / nn/Graph.scala): identical to calling the
        module on node(s) — returns the ModuleNode wired to `nodes`."""
        from .graph import _node
        return _node(self, list(nodes) if len(nodes) != 1 else nodes[0])

    def clear_state(self):
        """Drop cached activations (AbstractModule.clearState) — slims the
        facade before serialization or cloning; parameters are untouched."""
        self.output = None
        self.grad_input = None
        return self

    def copy_status(self, src: "Module"):
        """Copy cached output/gradInput (+ running state) from `src`
        (AbstractModule.copyStatus)."""
        self.output = src.output
        self.grad_input = src.grad_input
        if src.state is not None:
            self.state = src.state
        return self

    def get_weights_bias(self):
        """Parameter leaves in deterministic tree order
        (AbstractModule.getWeightsBias: Array[Tensor])."""
        if self.params is None:
            self.build()
        return [np.asarray(leaf) for leaf in jax.tree.leaves(self.params)]

    def set_weights_bias(self, arrays):
        """Install leaves produced by get_weights_bias (or any same-shaped
        sequence) back into the parameter tree
        (AbstractModule.setWeightsBias)."""
        if self.params is None:
            self.build()
        leaves, treedef = jax.tree.flatten(self.params)
        if len(arrays) != len(leaves):
            raise ValueError(f"expected {len(leaves)} arrays, "
                             f"got {len(arrays)}")
        new = []
        for i, (a, leaf) in enumerate(zip(arrays, leaves)):
            a = jnp.asarray(a, leaf.dtype)
            if a.shape != leaf.shape:
                # no silent reshape: a same-element-count array in the
                # wrong layout (e.g. a transposed Linear weight from
                # another framework) would install scrambled weights
                raise ValueError(
                    f"set_weights_bias: array {i} has shape {a.shape}, "
                    f"parameter expects {leaf.shape}")
            new.append(a)
        self.attach(jax.tree.unflatten(treedef, new), self.state)
        return self

    def save_weights(self, path: str, overwrite: bool = True):
        """Weights-only snapshot (AbstractModule.saveWeights) — loadable
        into any architecture-identical module via load_weights."""
        from ..utils import file_io
        file_io.save({"format": "bigdl_tpu-weights-v1",
                      "weights": self.get_weights_bias()},
                     path, overwrite=overwrite)
        return self

    def load_weights(self, path: str):
        """(AbstractModule.loadWeights)"""
        from ..utils import file_io
        blob = file_io.load(path)
        if not (isinstance(blob, dict) and
                blob.get("format") == "bigdl_tpu-weights-v1"):
            raise ValueError(f"{path!r} is not a bigdl_tpu weights file")
        return self.set_weights_bias(blob["weights"])

    def load_model_weights(self, src: "Module"):
        """Copy another (architecture-identical) module's weights
        (AbstractModule.loadModelWeights / copyWeights)."""
        if src.params is None:
            src.build()
        # device arrays pass straight through set_weights_bias — no
        # host round trip
        return self.set_weights_bias(jax.tree.leaves(src.params))

    copy_weights = load_model_weights

    def predict(self, dataset, batch_size: int = 128):
        """Bulk inference over a dataset or raw Sample list
        (AbstractModule.predict -> Predictor, SURVEY.md §3.4)."""
        from ..optim.optimizer import Predictor
        self.training_mode = False
        return Predictor(self, batch_size=batch_size).predict(dataset)

    def predict_class(self, dataset, batch_size: int = 128):
        """(AbstractModule.predictClass)"""
        from ..optim.optimizer import Predictor
        self.training_mode = False
        return Predictor(self, batch_size=batch_size).predict_class(dataset)

    def save_caffe(self, prototxt_path: str, model_path: str = None):
        """(AbstractModule.saveCaffe(prototxtPath, modelPath) ->
        CaffePersister).  Two-arg form writes the text net definition to
        `prototxt_path` AND the binary caffemodel to `model_path`; one-arg
        form writes only the binary caffemodel to the given path."""
        from ..interop.caffe import save_caffe
        if self.params is None:
            self.build()
        if model_path is None:
            save_caffe(self, self.params, prototxt_path, state=self.state)
        else:
            save_caffe(self, self.params, model_path, state=self.state,
                       prototxt_path=prototxt_path)
        return self

    def save_tf(self, path: str):
        """(AbstractModule.saveTF -> TensorflowSaver)"""
        from ..interop.tensorflow import save_tf
        if self.params is None:
            self.build()
        save_tf(self, self.params, path, state=self.state)
        return self

    def save_torch(self, path: str):
        """(AbstractModule.saveTorch -> TorchFile)"""
        from ..interop.torchfile import save_torch_module
        if self.params is None:
            self.build()
        save_torch_module(self, self.params, path)
        return self

    def set_name(self, name: str):
        self.name = name
        return self

    def get_name(self) -> str:
        return self.name

    def set_scale_w(self, s: float):
        """Layer-wise weight-gradient scale (AbstractModule.scala:73).
        Propagates to children when this module has any (`self.modules`):
        the reference's Container.setScaleW semantics, and Graph/MapTable
        get the same behavior for free."""
        self.scale_w = s
        for m in getattr(self, "modules", ()):
            m.set_scale_w(s)
        _SCALE_EPOCH[0] += 1
        return self

    def set_scale_b(self, s: float):
        """(AbstractModule.setScaleB; propagation as set_scale_w)."""
        self.scale_b = s
        for m in getattr(self, "modules", ()):
            m.set_scale_b(s)
        _SCALE_EPOCH[0] += 1
        return self

    def clone_module(self) -> "Module":
        """Deep copy (BigDL: cloneModule via serialization, AbstractModule.scala:353)."""
        import copy
        return copy.deepcopy(self)

    def reset(self):
        """Re-randomize parameters (BigDL: AbstractModule.reset)."""
        self.build()
        return self

    def __repr__(self):
        return self.name


class Container(Module):
    """Base for composite modules (BigDL: nn/Container.scala:40).

    Child params/state are list-pytrees in child order.
    """

    def __init__(self, *modules: Module):
        super().__init__()
        self.modules: list = list(modules)

    def add(self, module: Module):
        """BigDL: Container.add (nn/Container.scala:54)."""
        self.modules.append(module)
        return self

    def __len__(self):
        return len(self.modules)

    def __getitem__(self, i):
        return self.modules[i]

    def init(self, rng):
        keys = jax.random.split(rng, max(len(self.modules), 1))
        ps, ss = [], []
        for m, k in zip(self.modules, keys):
            p, s = m.init(k)
            ps.append(p)
            ss.append(s)
        return ps, ss

    def child_params(self, params):
        """The parameters each child is applied with, in child order: its
        own slot of ``params``, except where a container shares one child's
        with another (``TiedSequential``)."""
        return params

    def _split_rng(self, rng):
        if rng is None:
            return [None] * len(self.modules)
        return list(jax.random.split(rng, max(len(self.modules), 1)))

    # facade conveniences: keep children's own facade params in sync is NOT done;
    # the container owns the authoritative (params, state) pytrees.

    def __repr__(self):
        inner = "\n  ".join(repr(m).replace("\n", "\n  ") for m in self.modules)
        return f"{self.name} {{\n  {inner}\n}}"


class Criterion:
    """Loss base (BigDL: nn/abstractnn/AbstractCriterion.scala).

    Pure core: `loss(output, target) -> scalar` (mean-reduced over batch by
    default, matching BigDL's sizeAverage=true convention).  Facade: forward /
    backward mirroring AbstractCriterion.
    """

    def __init__(self):
        self.output = None
        self.grad_input = None

    def loss(self, output, target):
        raise NotImplementedError

    def forward(self, output, target):
        self.output = self.loss(output, target)
        return self.output

    __call__ = forward

    def backward(self, output, target):
        self.grad_input = jax.grad(lambda o: self.loss(o, target))(output)
        return self.grad_input
