"""Pallas TPU kernel: two-way sorted top-k merge (bitonic) + shared
compare-exchange machinery.

Used by the distributed query path's ring merge (DESIGN.md Sect. 4): each of
the R dataset shards holds an ascending per-query top-k; a ring of R-1
collective-permute steps each merges two sorted lists.  Merging two ascending
k-lists is one compare-exchange against the reversed partner (the k smallest
of a bitonic 2k sequence) followed by log2(k) bitonic clean-up stages —
O(k log k) compares, fully vectorized, no data-dependent control flow.

The row-wise bitonic helpers (``lex_gt``, ``bitonic_clean_rows``,
``bitonic_topk_merge_rows``, ``bitonic_sort_rows``) are plain jnp functions
usable inside any Pallas kernel body; the fused rerank kernel
(``kernels/fused_rerank.py``, DESIGN.md §Perf) reuses them for its running
top-k so both kernels share one compare-exchange implementation.

All compares are **lexicographic on (dist, id)**: ids are a total-order
tie-break, which makes every merge/sort here deterministic (two correct
implementations agree bit-for-bit even on tied distances).  Since distances
dominate the key, distance outputs are unchanged relative to a dist-only
compare.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "lex_gt",
    "bitonic_clean_rows",
    "bitonic_topk_merge_rows",
    "bitonic_sort_rows",
    "topk_merge_pallas",
    "topk_merge_xla",
]


def lex_gt(d1, i1, d2, i2):
    """Lexicographic (dist, id) greater-than; the one compare all kernels use."""
    return (d1 > d2) | ((d1 == d2) & (i1 > i2))


def _cx(swap, lo, hi):
    """Conditional exchange: returns (min-side, max-side) under ``swap``."""
    return jnp.where(swap, hi, lo), jnp.where(swap, lo, hi)


def bitonic_clean_rows(d, i, s0: int):
    """Bitonic clean-up: compare-exchange at distances s0, s0/2, ..., 1.

    d, i: (rows, L) with L a power of two and every 2*s0 block bitonic.
    After cleaning, every 2*s0 block is ascending (lex on (d, i)).
    """
    r, l = d.shape
    s = s0
    while s >= 1:
        dr = d.reshape(r, l // (2 * s), 2, s)
        ir = i.reshape(r, l // (2 * s), 2, s)
        lo_d, hi_d = dr[:, :, 0, :], dr[:, :, 1, :]
        lo_i, hi_i = ir[:, :, 0, :], ir[:, :, 1, :]
        swap = lex_gt(lo_d, lo_i, hi_d, hi_i)
        new_lo_d, new_hi_d = _cx(swap, lo_d, hi_d)
        new_lo_i, new_hi_i = _cx(swap, lo_i, hi_i)
        d = jnp.stack([new_lo_d, new_hi_d], axis=2).reshape(r, l)
        i = jnp.stack([new_lo_i, new_hi_i], axis=2).reshape(r, l)
        s //= 2
    return d, i


def bitonic_topk_merge_rows(da, ia, db, ib):
    """Merge two (rows, k) lex-ascending lists -> the k lex-smallest, ascending.

    Stage 0 takes the elementwise min against the reversed partner (the k
    smallest of the bitonic concat(a, reverse(b))), then log2(k) clean-ups.
    k must be a power of two.
    """
    k = da.shape[-1]
    dbr, ibr = db[:, ::-1], ib[:, ::-1]
    take_a = ~lex_gt(da, ia, dbr, ibr)
    d = jnp.where(take_a, da, dbr)
    i = jnp.where(take_a, ia, ibr)
    if k > 1:
        d, i = bitonic_clean_rows(d, i, k // 2)
    return d, i


def bitonic_sort_rows(d, i):
    """Full row-wise bitonic merge-sort, lex-ascending on (d, i).

    d, i: (rows, L) with L a power of two.  Batcher's network in its
    ascending-only form: at block size ``size`` the first sub-stage compares
    position p with position size-1-p ("triangle"), then straight clean-ups
    at distances size/4 ... 1.  O(L log^2 L) compares, fully vectorized.
    """
    r, l = d.shape
    size = 2
    while size <= l:
        dr = d.reshape(r, l // size, 2, size // 2)
        ir = i.reshape(r, l // size, 2, size // 2)
        lo_d, lo_i = dr[:, :, 0, :], ir[:, :, 0, :]
        hi_d, hi_i = dr[:, :, 1, ::-1], ir[:, :, 1, ::-1]   # triangle partner
        swap = lex_gt(lo_d, lo_i, hi_d, hi_i)
        new_lo_d, new_hi_d = _cx(swap, lo_d, hi_d)
        new_lo_i, new_hi_i = _cx(swap, lo_i, hi_i)
        d = jnp.stack([new_lo_d, new_hi_d[:, :, ::-1]], axis=2).reshape(r, l)
        i = jnp.stack([new_lo_i, new_hi_i[:, :, ::-1]], axis=2).reshape(r, l)
        if size > 2:
            d, i = bitonic_clean_rows(d, i, size // 4)
        size *= 2
    return d, i


def _merge_kernel(da_ref, ia_ref, db_ref, ib_ref, do_ref, io_ref):
    da, ia = da_ref[...], ia_ref[...]                  # (bq, k) asc
    db, ib = db_ref[...], ib_ref[...]
    do_ref[...], io_ref[...] = bitonic_topk_merge_rows(da, ia, db, ib)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def topk_merge_pallas(
    da: jax.Array, ia: jax.Array, db: jax.Array, ib: jax.Array,
    bq: int = 8, interpret: bool = False,
):
    """Merge ascending (Q, k) lists.  k is padded to a power of two."""
    q, k = da.shape
    kp = 1 << (k - 1).bit_length()
    big = (jnp.iinfo(jnp.int32).max // 2 if jnp.issubdtype(da.dtype, jnp.integer)
           else jnp.inf)
    if kp != k:
        pad = ((0, 0), (0, kp - k))
        da = jnp.pad(da, pad, constant_values=big)
        db = jnp.pad(db, pad, constant_values=big)
        ia = jnp.pad(ia, pad, constant_values=-1)
        ib = jnp.pad(ib, pad, constant_values=-1)
    pq = (-q) % bq
    if pq:
        da, db = (jnp.pad(x, ((0, pq), (0, 0)), constant_values=big) for x in (da, db))
        ia, ib = (jnp.pad(x, ((0, pq), (0, 0)), constant_values=-1) for x in (ia, ib))
    grid = (da.shape[0] // bq,)
    spec = pl.BlockSpec((bq, kp), lambda i: (i, 0))
    do, io = pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 2,
        out_shape=[
            jax.ShapeDtypeStruct(da.shape, da.dtype),
            jax.ShapeDtypeStruct(ia.shape, ia.dtype),
        ],
        interpret=interpret,
    )(da, ia, db, ib)
    return do[:q, :k], io[:q, :k]


@jax.jit
def topk_merge_xla(da: jax.Array, ia: jax.Array, db: jax.Array,
                   ib: jax.Array):
    """Same contract as ``topk_merge_pallas`` in XLA ops: one
    lexicographic (dist, id) sort of the concatenated lists."""
    k = da.shape[-1]
    d, i = jax.lax.sort((jnp.concatenate([da, db], axis=-1),
                         jnp.concatenate([ia, ib], axis=-1)),
                        dimension=-1, num_keys=2)
    return d[:, :k], i[:, :k]
