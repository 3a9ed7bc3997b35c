"""Base vectors and query pool of a cell, made on the device from the seed.

The law is the Laplacian-cluster law of the program's synthetic data
(``repro.data.ann_synthetic``): ``num_clusters`` centres uniform in
[0.25, 0.75] per coordinate, each row a centre plus Laplace noise of scale
``cluster_spread`` (as a fraction of the universe U), clipped to [0, 1],
scaled by U and rounded to the nearest even integer (the paper's Sect. 3.2
normalisation).  Queries are rows of the data plus Laplace noise of scale
``perturb_frac * U``, rounded and clipped the same way, so every query has
true neighbours at controlled L1 radii.  It is a copy of that law and not an
import, so that the yardstick does not move when the program does; it runs
in float32 on the device, in row blocks, instead of float64 on the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["seed_keys", "make_data", "make_queries"]


def seed_keys(seed: int) -> dict:
    """Independent keys for data, queries and hash functions, from ``seed``.

    ``jax.random.key`` keeps only the low 32 bits of a seed, so the high
    bits are folded in: seeds 0 and 2**40 make different data.
    """
    seed = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    data, queries, hashes = jax.random.split(key, 3)
    return {"data": data, "queries": queries, "hashes": hashes}


def _row_blocks(n: int) -> int:
    """Blocks the rows are made in: bounds the float32 temporaries."""
    for b in (16, 8, 4, 2):
        if n % b == 0:
            return b
    return 1


@partial(jax.jit, static_argnames=("n", "dim", "universe", "num_clusters",
                                   "cluster_spread"))
def make_data(key, *, n: int, dim: int, universe: int, num_clusters: int,
              cluster_spread: float) -> jax.Array:
    """(n, dim) int32 rows, nonnegative even, <= universe."""
    k_centres, k_rows = jax.random.split(key)
    centres = jax.random.uniform(k_centres, (num_clusters, dim), jnp.float32,
                                 0.25, 0.75)
    blocks = _row_blocks(n)
    rows = n // blocks
    keys = jax.random.split(k_rows, blocks)

    def block(b, out):
        k_a, k_n = jax.random.split(keys[b])
        assign = jax.random.randint(k_a, (rows,), 0, num_clusters)
        noise = jax.random.laplace(k_n, (rows, dim), jnp.float32)
        x = jnp.clip(centres[assign] + cluster_spread * noise, 0.0, 1.0)
        even = jnp.clip(2.0 * jnp.round(x * universe / 2.0), 0, universe)
        return jax.lax.dynamic_update_slice_in_dim(
            out, even.astype(jnp.int32), b * rows, 0)

    # filled in place block by block: temporaries stay one block's size
    return jax.lax.fori_loop(0, blocks, block,
                             jnp.zeros((n, dim), jnp.int32))


@partial(jax.jit, static_argnames=("count", "universe", "perturb_frac"))
def make_queries(key, data: jax.Array, *, count: int, universe: int,
                 perturb_frac: float) -> jax.Array:
    """(count, dim) int32 queries near rows of ``data``."""
    k_rows, k_noise = jax.random.split(key)
    n, dim = data.shape
    base = data[jax.random.randint(k_rows, (count,), 0, n)]
    noise = jax.random.laplace(k_noise, (count, dim), jnp.float32)
    x = base.astype(jnp.float32) + perturb_frac * universe * noise
    even = 2.0 * jnp.round(x / 2.0)
    return jnp.clip(even, 0, universe).astype(jnp.int32)
