"""Pure-jnp oracles for every kernel (the ``ref.py`` contract).

Each function is the semantic ground truth the kernels are tested against
(tests/test_kernels.py, test_fused_probe.py and test_fused_rerank.py sweep
shapes and dtypes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["l1_distance", "l1_distance_rows", "rw_hash", "topk_merge",
           "fused_rerank", "fused_probe"]

_BIG = (2 ** 31 - 1) // 2  # == iinfo(int32).max // 2, pipeline.BIG_DIST


def l1_distance(queries: jax.Array, points: jax.Array) -> jax.Array:
    """(Q, m), (N, m) -> (Q, N) pairwise L1 distances.

    Integer inputs accumulate in int32 (exact); float in float32.
    """
    acc = jnp.int32 if jnp.issubdtype(queries.dtype, jnp.integer) else jnp.float32
    diff = queries[:, None, :].astype(acc) - points[None, :, :].astype(acc)
    return jnp.abs(diff).sum(axis=-1)


def l1_distance_rows(queries: jax.Array, rows: jax.Array) -> jax.Array:
    """(Q, m), (Q, C, m) -> (Q, C) per-query candidate L1 distances."""
    acc = jnp.int32 if jnp.issubdtype(queries.dtype, jnp.integer) else jnp.float32
    diff = rows.astype(acc) - queries[:, None, :].astype(acc)
    return jnp.abs(diff).sum(axis=-1)


def rw_hash(pairs: jax.Array, points: jax.Array) -> jax.Array:
    """Random-walk raw hash via thermometer inner product.

    pairs  : (F, m, U2) int8 paired walk steps
    points : (n, m) int32 nonnegative even coordinates (<= 2*U2)
    returns: (n, F) int32,  f[n,k] = sum_{i,u} 1{u < points[n,i]//2} pairs[k,i,u]
    """
    t = (points >> 1).astype(jnp.int32)
    u2 = pairs.shape[-1]
    thermo = (jnp.arange(u2, dtype=jnp.int32)[None, None, :] < t[:, :, None])
    return jnp.einsum(
        "niu,kiu->nk", thermo.astype(jnp.int32), pairs.astype(jnp.int32),
    ).astype(jnp.int32)


def fused_rerank(dataset: jax.Array, queries: jax.Array, ids: jax.Array,
                 k: int):
    """Semantic ground truth for the fused rerank kernel (§Perf).

    Returns the k lexicographically-(dist, id)-smallest pairs over the
    *unique* valid candidate ids (slots < 0 or >= n invalid), ascending;
    invalid slots carry (INT32_MAX // 2, -1).  This is also exactly what a
    sort-dedup + chunked-scan + lax.top_k computes (duplicates tie with
    themselves, and top_k's positional tie-break over an id-ascending
    candidate list is the (dist, id) order).
    """
    n = dataset.shape[0]
    q = ids.shape[0]
    big = jnp.int32(_BIG)
    if n == 0 or ids.shape[1] == 0:
        return (jnp.full((q, k), big, jnp.int32),
                jnp.full((q, k), -1, jnp.int32))
    valid = (ids >= 0) & (ids < n)
    rows = dataset[jnp.clip(ids, 0, n - 1)]
    d = jnp.abs(rows.astype(jnp.int32)
                - queries[:, None, :].astype(jnp.int32)).sum(-1)
    d = jnp.where(valid, d, big)
    i = jnp.where(valid, ids, -1)
    sd, si = jax.lax.sort((d, i), dimension=-1, num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool),
         (sd[:, 1:] == sd[:, :-1]) & (si[:, 1:] == si[:, :-1])], axis=-1)
    sd = jnp.where(dup, big, sd)
    si = jnp.where(dup, -1, si)
    sd, si = jax.lax.sort((sd, si), dimension=-1, num_keys=2)
    pad = max(0, k - sd.shape[1])
    if pad:
        sd = jnp.pad(sd, ((0, 0), (0, pad)), constant_values=_BIG)
        si = jnp.pad(si, ((0, 0), (0, pad)), constant_values=-1)
    sd, si = sd[:, :k], si[:, :k]
    return sd, jnp.where(sd >= big, -1, si)


def fused_probe(sorted_keys: jax.Array, sorted_ids: jax.Array,
                probe_keys: jax.Array, cap: int, cbucket: int):
    """Semantic ground truth for the probe front-end (§8).

    Materializes the full worst-case ``(Q, L*P*C)`` slab (one slot per
    (table, probe, offset) under the cap — the thing the compacted gather
    avoids), then compacts it with a stable sort on the invalid flag — valid
    candidates packed to the front in their original (table, probe, offset)
    order, sentinel ``n`` tail, truncated at ``cbucket``.  Returns
    (ids (Q, cbucket) int32, counts (Q,) int32 — pre-truncation totals).
    """
    l, n = sorted_keys.shape
    q, _, p = probe_keys.shape
    if n == 0 or cbucket == 0 or q == 0:
        return (jnp.zeros((q, cbucket), jnp.int32),
                jnp.zeros((q,), jnp.int32))

    def per_table(sk, pk):
        return (jnp.searchsorted(sk, pk, side="left"),
                jnp.searchsorted(sk, pk, side="right"))

    lo, hi = jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
        sorted_keys, probe_keys)                        # (Q, L, P)
    slots = lo[..., None] + jnp.arange(cap, dtype=lo.dtype)
    valid = slots < jnp.minimum(hi, lo + cap)[..., None]
    slots = jnp.clip(slots, 0, n - 1)
    ids = jax.vmap(lambda sid, sl: sid[sl], in_axes=(0, 1), out_axes=1)(
        sorted_ids, slots)                              # (Q, L, P, C)
    full = jnp.where(valid, ids, n).reshape(q, l * p * cap)
    order = jnp.argsort(full == n, axis=-1, stable=True)
    packed = jnp.take_along_axis(full, order, axis=-1)
    counts = (full != n).sum(axis=-1).astype(jnp.int32)
    if cbucket <= packed.shape[1]:
        packed = packed[:, :cbucket]
    else:
        packed = jnp.pad(packed, ((0, 0), (0, cbucket - packed.shape[1])),
                         constant_values=n)
    return packed.astype(jnp.int32), counts


def topk_merge(da: jax.Array, ia: jax.Array, db: jax.Array, ib: jax.Array):
    """Merge two per-row ascending top-k lists into one ascending top-k.

    da, db : (Q, k) distances sorted ascending; ia, ib: matching ids.
    Returns (d, i) of the k smallest of the union, ascending —
    lexicographic on (dist, id) like ``kops.topk_merge``, so ties resolve
    identically on every backend.
    """
    k = da.shape[-1]
    d = jnp.concatenate([da, db], axis=-1)
    i = jnp.concatenate([ia, ib], axis=-1)
    sd, si = jax.lax.sort((d, i), dimension=-1, num_keys=2)
    return sd[..., :k], si[..., :k]
