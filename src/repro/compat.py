"""``shard_map`` with the replication check off.

Every caller in this repo wants ``check_vma=False`` (outputs deliberately
mix replicated and sharded specs), so callers pass only
``mesh``/``in_specs``/``out_specs``.
"""

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
