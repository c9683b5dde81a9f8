"""Spider's ``gaussian`` distribution (see ``spider_gaussian.py``) drawn
on the device, for deployments larger than the host should hold.

For independent coordinates, Spider's rule of drawing a point again
when it falls outside the unit square is exactly a product of normals
truncated to [0, 1]: each coordinate is drawn from N(mean, sd)
truncated there (``jax.random.truncated_normal``, float32).

Point i is a function of (seed, i) alone: the points come in fixed
blocks of ``BLOCK``, block b drawn from ``fold_in(key(seed), b)``, and
each shard draws the blocks its range of positions touches. So one
seed gives bitwise the same points over 1, 2 or 4 shards, and a
point's id is its position.
"""
from __future__ import annotations

BLOCK = 1 << 16


def key(seed: int):
    """The generator's key for any whole-number seed (all 64 bits
    count)."""
    import jax
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def device_points(n: int, seed: int, mesh, mean: float = 0.5,
                  sd: float = 0.1):
    """``n`` float32 points (x, y) for ``seed``, sharded over the mesh's
    ``"data"`` axis, which has to divide ``n``."""
    shards = int(mesh.shape["data"])
    if n % shards:
        raise ValueError(f"{n} points do not split over {shards} shards")
    return _draw(mesh, n // shards, float(mean), float(sd))(key(seed))


def _draw(mesh, m: int, mean: float, sd: float):
    """The jitted draw of ``m`` points a shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    lo, hi = (0.0 - mean) / sd, (1.0 - mean) / sd
    nb = -(-m // BLOCK) + 1        # blocks a shard's range can touch

    def block(k, b):
        z = jax.random.truncated_normal(jax.random.fold_in(k, b), lo, hi,
                                        (2, BLOCK), jnp.float32)
        v = jnp.clip(mean + sd * z, 0.0, 1.0)
        return v[0], v[1]

    def shard(k):
        start = jax.lax.axis_index("data") * m
        b0 = start // BLOCK
        xs, ys = jax.lax.map(lambda b: block(k, b), b0 + jnp.arange(nb))
        off = start - b0 * BLOCK
        return tuple(jax.lax.dynamic_slice_in_dim(a.reshape(-1), off, m)
                     for a in (xs, ys))

    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(),
                                 out_specs=(P("data"), P("data"))))
