"""shard_map with this repo's historical ``check=`` keyword (JAX calls it
``check_vma``)."""
from jax import shard_map as _shard_map


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check=True):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check)
