"""Composite modules.

Reference: BigDL `nn/Sequential.scala:30` (linear chain), `nn/Concat.scala`
(parallel branches concatenated along a dim), `nn/ConcatTable.scala` (branches
returning a Table), `nn/ParallelTable.scala` (i-th child on i-th input),
`nn/MapTable.scala` (one child mapped over every input), `nn/Identity.scala`,
`nn/Echo.scala`, `nn/Bottle.scala`.

TPU-native notes: containers thread a `training` flag and split the PRNG key per
child; child params/state are list-pytrees, so a whole model is a single pytree that
pjit can shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import Container, Module

__all__ = ["Sequential", "TiedSequential", "Concat", "ConcatTable", "ParallelTable", "MapTable",
           "Identity", "Echo", "Bottle"]


class Sequential(Container):
    """BigDL: nn/Sequential.scala:30 — fold input through children in order."""

    def apply(self, params, state, input, *, training=False, rng=None):
        rngs = self._split_rng(rng)
        new_states = []
        x = input
        for m, p, s, k in zip(self.modules, self.child_params(params), state,
                              rngs):
            x, ns = m.apply(p, s, x, training=training, rng=k)
            new_states.append(ns)
        return x, new_states


class TiedSequential(Sequential):
    """A ``Sequential`` in which a child reads another child's parameters:
    one table for the embedding and the head (``tie_word_embeddings``).

    ``tie(reader, owner)``: child ``reader`` has no parameters of its own
    (an empty dict holds its place in the list, so every walk that pairs
    children with their slots still lines up, and the tree has one leaf for
    the table); whoever applies the children takes their parameters from
    ``child_params``, which hands the reader the owner's.  The two modules
    must agree on the shared dict: a ``Linear(d, V, with_bias=False)`` keeps
    ``weight [V, d]`` as a ``LookupTable(V, d)`` does.  A gradient through
    either use lands on the one leaf."""

    def __init__(self, *modules: Module):
        super().__init__(*modules)
        self._ties = {}         # reader's index -> owner's index

    def tie(self, reader: Module, owner: Module):
        self._ties[self.modules.index(reader)] = self.modules.index(owner)
        return self

    def init(self, rng):
        params, state = super().init(rng)
        for reader in self._ties:
            params[reader] = {}
        return params, state

    def child_params(self, params):
        out = list(params)
        for reader, owner in self._ties.items():
            out[reader] = params[owner]
        return out


class Concat(Container):
    """BigDL: nn/Concat.scala — run children on the same input, concatenate outputs
    along `dimension`.  Reference uses 1-based dims over NCHW; here `dimension` is a
    0-based axis over the canonical NHWC layout (channel axis = -1)."""

    def __init__(self, dimension: int = -1):
        super().__init__()
        self.dimension = dimension

    def apply(self, params, state, input, *, training=False, rng=None):
        rngs = self._split_rng(rng)
        outs, new_states = [], []
        for m, p, s, k in zip(self.modules, params, state, rngs):
            o, ns = m.apply(p, s, input, training=training, rng=k)
            outs.append(o)
            new_states.append(ns)
        return jnp.concatenate(outs, axis=self.dimension), new_states


class ConcatTable(Container):
    """BigDL: nn/ConcatTable.scala — children on same input, outputs as a list."""

    def apply(self, params, state, input, *, training=False, rng=None):
        rngs = self._split_rng(rng)
        outs, new_states = [], []
        for m, p, s, k in zip(self.modules, params, state, rngs):
            o, ns = m.apply(p, s, input, training=training, rng=k)
            outs.append(o)
            new_states.append(ns)
        return outs, new_states


class ParallelTable(Container):
    """BigDL: nn/ParallelTable.scala — i-th child applied to i-th input element."""

    def apply(self, params, state, input, *, training=False, rng=None):
        rngs = self._split_rng(rng)
        outs, new_states = [], []
        for m, p, s, x, k in zip(self.modules, params, state, input, rngs):
            o, ns = m.apply(p, s, x, training=training, rng=k)
            outs.append(o)
            new_states.append(ns)
        return outs, new_states


class MapTable(Container):
    """BigDL: nn/MapTable.scala — ONE shared child mapped over each input element
    (parameters shared across applications)."""

    def __init__(self, module: Module = None):
        super().__init__()
        if module is not None:
            self.add(module)

    def init(self, rng):
        p, s = self.modules[0].init(rng)
        return [p], [s]

    def apply(self, params, state, input, *, training=False, rng=None):
        m, p, s = self.modules[0], params[0], state[0]
        rngs = ([None] * len(input) if rng is None
                else list(jax.random.split(rng, max(len(input), 1))))
        outs = []
        ns = s
        for x, k in zip(input, rngs):
            o, ns = m.apply(p, ns, x, training=training, rng=k)
            outs.append(o)
        return outs, [ns]


class Identity(Module):
    """BigDL: nn/Identity.scala."""

    def _apply(self, params, input):
        return input


class Echo(Module):
    """BigDL: nn/Echo.scala — identity that prints activation shape (debug aid).
    Uses jax.debug.callback so it also works under jit."""

    def _apply(self, params, input):
        jax.debug.print("{name}: shape {shape}", name=self.name,
                        shape=jnp.asarray(jnp.shape(input)))
        return input


class Bottle(Container):
    """BigDL: nn/Bottle.scala — collapse leading dims, apply child, restore.

    `Bottle(module, n_input_dim=2)` flattens an (d1, d2, ..., features) input to
    (d1*d2*..., features), applies the child, and unflattens.
    """

    def __init__(self, module: Module, n_input_dim: int = 2, n_output_dim: int = None):
        super().__init__(module)
        self.n_input_dim = n_input_dim
        self.n_output_dim = n_output_dim or n_input_dim

    def apply(self, params, state, input, *, training=False, rng=None):
        lead = input.shape[:self.n_input_dim]
        rest = input.shape[self.n_input_dim:]
        flat = input.reshape((-1,) + rest)
        out, ns = self.modules[0].apply(params[0], state[0], flat,
                                        training=training, rng=rng)
        out = out.reshape(lead + out.shape[1:])
        return out, [ns]
