"""Two-way top-k merge: the fold of per-segment and per-shard result lists.

Used by the segmented index's merge of per-segment top-k lists and by the
distributed query path's ring and tree merges (DESIGN.md Sect. 4): each
side holds an ascending per-query top-k, and one lexicographic (dist, id)
sort of the two concatenated lists keeps the k smallest.  Ids are a
total-order tie-break, which makes the merge deterministic (two correct
implementations agree bit for bit even on tied distances); since distances
dominate the key, distance outputs are those of a dist-only compare.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["topk_merge_xla"]


@jax.jit
def topk_merge_xla(da: jax.Array, ia: jax.Array, db: jax.Array,
                   ib: jax.Array):
    """Merge ascending (Q, k) lists into the k lex-(dist, id) smallest."""
    k = da.shape[-1]
    d, i = jax.lax.sort((jnp.concatenate([da, db], axis=-1),
                         jnp.concatenate([ia, ib], axis=-1)),
                        dimension=-1, num_keys=2)
    return d[:, :k], i[:, :k]
