"""`shard_map` as the supported JAX (0.9.0) spells it, in one place.

`shard_map_unchecked` is shard_map with the varying-manual-axes check off:
custom_vjp + psum bodies (the Pallas BN / ConvBN shard_map routes) trip the
checker.
"""

from jax import shard_map


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


__all__ = ["shard_map", "shard_map_unchecked"]
