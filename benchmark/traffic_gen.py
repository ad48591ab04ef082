"""The one traffic generator.  A traffic mix is a JSON file of parameters
under benchmark/traffic/; a builder says which fields one sample has; this
draws `pool_batches` whole batches from the seed, on the device, already
sharded along the batch dimension over the mesh, in set-up.  The measured
loop only cycles through them, as upstream Horovod's documented benchmark
does with its synthetic batch: no host input pipeline is in any cell.

Integer fields are uniform over [0, high); float fields uniform over [0, 1).
Lengths are fixed by the traffic file, never drawn: the repository has no
document masks, so there is nothing yet for a drawn length to exercise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def make_pool(fields, global_batch: int, pool_batches: int, seed: int, mesh,
              axis_name: str):
    """[{field name: array of (global_batch, *shape)}] * pool_batches."""
    sharding = NamedSharding(mesh, P(axis_name))

    def draw(key):
        out = {}
        for i, field in enumerate(fields):
            shape = (global_batch, *field["shape"])
            sub = jax.random.fold_in(key, i)
            dtype = jnp.dtype(field["dtype"])
            if jnp.issubdtype(dtype, jnp.integer):
                out[field["name"]] = jax.random.randint(
                    sub, shape, 0, field["high"], dtype)
            else:
                out[field["name"]] = jax.random.uniform(sub, shape, dtype)
        return out

    draw = jax.jit(draw, out_shardings=sharding)
    # Another stream than the weights', which use PRNGKey(seed) itself.
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0x7AFF1C)
    return [draw(jax.random.fold_in(base, i)) for i in range(pool_batches)]
