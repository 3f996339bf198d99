"""`shard_map` as the supported JAX (0.9.0) spells it, in one place."""

from jax import shard_map

__all__ = ["shard_map"]
