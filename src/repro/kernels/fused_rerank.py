"""Fused rerank: gather + L1 + running top-k in one kernel (DESIGN.md §Perf).

The exact L1 rerank dominates MP-RW-LSH query cost (paper Sect. 5: multi-probe
trades cheap extra probes for fewer tables, so candidate reranking is where
the time goes).  The pre-fusion pipeline paid three full HBM round-trips per
candidate chunk — materialize ``dataset[ids]`` as a (Q, chunk, m) intermediate,
write the (Q, chunk) distances, then concat + ``lax.top_k`` against the
running best — plus an O(Ctot log) sort-like cost in the repeated top_k and a
full ``jnp.sort`` over (Q, Ctot) in the dedup stage before it.

This module fuses all of that into a single pass with two executors that are
**bit-identical** to each other and to ``ref.fused_rerank`` (pinned by
tests/test_fused_rerank.py):

* ``fused_rerank_pallas`` — the Pallas kernel.  Grid over query tiles;
  candidate rows are gathered into VMEM tiles inside the kernel, |diff| sums
  accumulate in registers over an m-chunk loop (the (Q, C, m) intermediate
  never exists in HBM), and a per-query bitonic running top-k — the same
  compare-exchange machinery as ``kernels/topk_merge.py`` — replaces the
  repeated ``lax.top_k``.  Duplicate candidate ids are suppressed *inside*
  the kernel by id-keyed masking (within-tile lower-triangle compare + a
  compare against the running best), which is what lets the pipeline skip
  the sorting dedup stage entirely (``pipeline.stage_dedup`` sort-free path).
* ``fused_rerank_xla`` — the XLA executor for non-TPU backends: a chunked
  distance scan with **no per-chunk top_k**, then one lexicographic
  (dist, id) sort that performs dedup (equal ids imply equal dists, so
  duplicates land adjacent) and top-k selection in a single O(Ctot log Ctot)
  pass — strictly cheaper than the old sort-dedup + S-fold ``lax.top_k``.

Output contract (shared with the legacy scan path, which it reproduces
bit-for-bit including tie cases — see tests/test_segments.py):

    the k lexicographically-(dist, id)-smallest pairs over the *unique*
    valid candidate ids, ascending; invalid/padded slots carry
    (BIG_DIST, -1).  Candidate ids < 0 or >= n are invalid.

VMEM budget of the Pallas kernel (defaults bq=8, bc=128, bm=512): the gathered
tile is bq*bc*bm*4B = 2 MB, the running best 2*bq*bc*4B = 8 KB, plus the
query/ids blocks — well under the ~16 MB/core budget.  The dataset ref is
currently mapped as one block (fine for segment-sized shards); the
TPU-scale evolution is an ANY-space ref with per-id double-buffered DMA over
the candidate axis, which changes only the gather, not the semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .rows import widen_rows
from .topk_merge import bitonic_sort_rows, bitonic_topk_merge_rows

__all__ = ["fused_rerank_pallas", "fused_rerank_xla", "BIG_DIST"]

# Matches core.pipeline.BIG_DIST (kernels must not import core).
BIG_DIST = np.iinfo(np.int32).max // 2


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def _empty_result(q: int, k: int):
    return (jnp.full((q, k), BIG_DIST, jnp.int32),
            jnp.full((q, k), -1, jnp.int32))


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _fused_kernel(q_ref, ids_ref, data_ref, do_ref, io_ref, *,
                  n: int, bc: int, bm: int):
    big = jnp.int32(BIG_DIST)
    bq, mp = q_ref.shape
    ctp = ids_ref.shape[1]
    qs = q_ref[...].astype(jnp.int32)                   # (bq, mp)
    ids_all = ids_ref[...]                              # (bq, ctp)
    data = data_ref[...]                                # (n_rows, mp)
    n_rows = data.shape[0]
    m_tiles = mp // bm

    # Duplicate masks are id-keyed compares, not sorts: the lower triangle
    # kills repeats within a tile, the running-best compare kills repeats
    # across tiles.  Exactness: an id's later copy has the *identical*
    # (dist, id) key, so if its first copy is in the best list the copy is
    # masked, and if the first copy was evicted (or never admitted) the
    # best list only improved since, so the copy cannot enter either.
    tri = (jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 1)
           < jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 0))

    def tile_step(t, carry):
        best_d, best_i = carry                          # (bq, bc) lex-asc
        tid = jax.lax.dynamic_slice(ids_all, (0, t * bc), (bq, bc))
        valid = (tid >= 0) & (tid < n)
        safe = jnp.clip(tid, 0, n_rows - 1)

        # |diff| accumulation over m-chunks: the gathered candidate tile is
        # (bq, bc, bm) in VMEM, widened to int32 in registers; the full
        # (bq, bc, m) slab never exists.
        def m_step(u, acc):
            sub = jax.lax.dynamic_slice(data, (0, u * bm), (n_rows, bm))
            rows = jnp.take(sub, safe.reshape(-1), axis=0)
            rows = widen_rows(rows.reshape(bq, bc, bm))
            qsub = jax.lax.dynamic_slice(qs, (0, u * bm), (bq, bm))
            return acc + jnp.abs(rows - qsub[:, None, :]).sum(-1)

        d = jax.lax.fori_loop(0, m_tiles, m_step,
                              jnp.zeros((bq, bc), jnp.int32))
        d = jnp.where(valid, d, big)
        ti = jnp.where(valid, tid, -1)

        dup_tile = ((ti[:, :, None] == ti[:, None, :]) & tri[None]
                    & valid[:, :, None]).any(-1)
        in_best = ((ti[:, :, None] == best_i[:, None, :])
                   & (best_i[:, None, :] >= 0)).any(-1)
        dup = dup_tile | in_best
        d = jnp.where(dup, big, d)
        ti = jnp.where(dup, -1, ti)

        d, ti = bitonic_sort_rows(d, ti)
        return bitonic_topk_merge_rows(best_d, best_i, d, ti)

    init = (jnp.full((bq, bc), big, jnp.int32),
            jnp.full((bq, bc), -1, jnp.int32))
    best_d, best_i = jax.lax.fori_loop(0, ctp // bc, tile_step, init)
    ko = do_ref.shape[1]
    do_ref[...] = best_d[:, :ko]
    io_ref[...] = best_i[:, :ko]


@functools.partial(jax.jit,
                   static_argnames=("k", "bq", "bc", "bm", "interpret"))
def fused_rerank_pallas(
    dataset: jax.Array, queries: jax.Array, ids: jax.Array, k: int,
    bq: int = 8, bc: int = 128, bm: int = 512, interpret: bool = False,
):
    """Fused gather + L1 + running-top-k.  See module docstring for contract.

    dataset (n, m) stored rows (``kernels.rows``: uint8 rows hold x // 2);
    queries (Q, m) int; ids (Q, Ctot) int32 (slots < 0 or >= n are invalid;
    ids need NOT be deduplicated).  Returns
    (dists (Q, k) int32, ids (Q, k) int32), lex-(dist, id) ascending.
    """
    n, m = dataset.shape
    q, ctot = ids.shape
    if n == 0 or ctot == 0:
        return _empty_result(q, k)
    kp = _pow2_at_least(k)
    bc = max(_pow2_at_least(bc), kp)
    mp = _round_up(m, 128)
    bm = min(bm, mp)
    mp = _round_up(mp, bm)
    pq, pc = (-q) % bq, (-ctot) % bc
    qp = jnp.pad(queries, ((0, pq), (0, mp - m)))
    dp = jnp.pad(dataset, ((0, 0), (0, mp - m)))
    idp = jnp.pad(ids, ((0, pq), (0, pc)), constant_values=-1)
    grid = (qp.shape[0] // bq,)
    out_spec = pl.BlockSpec((bq, kp), lambda i: (i, 0))
    do, io = pl.pallas_call(
        functools.partial(_fused_kernel, n=n, bc=bc, bm=bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, mp), lambda i: (i, 0)),
            pl.BlockSpec((bq, idp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((n, mp), lambda i: (0, 0)),
        ],
        out_specs=[out_spec] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], kp), jnp.int32),
            jax.ShapeDtypeStruct((qp.shape[0], kp), jnp.int32),
        ],
        interpret=interpret,
    )(qp, idp, dp)
    do, io = do[:q, :k], io[:q, :k]
    return do, jnp.where(do >= BIG_DIST, -1, io)


# --------------------------------------------------------------------------
# XLA executor (non-TPU backends)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def fused_rerank_xla(
    dataset: jax.Array, queries: jax.Array, ids: jax.Array, k: int,
    chunk: int = 512,
):
    """Same contract as ``fused_rerank_pallas``, tuned for XLA backends.

    XLA CPU's variadic sort and TopK lower to a slow generic-comparator
    path, while single-array ``sort`` is a fast specialized loop — so this
    executor only ever sorts single int32 arrays:

    1. dedup: one values-only id sort, adjacent-equal -> sentinel (the
       surviving ids stay ascending, so *position* order == id order);
    2. chunked distance scan with NO per-chunk top_k (the (Q, chunk, m)
       gather is consumed in registers, one (Q, Ctot) dist row out);
    3. selection: pack (dist, position) into one int32 key — d * P + pos
       with P = next_pow2(Ctot) — and sort it; the first k keys ARE the
       lex-(dist, id)-smallest unique candidates.  Packing is validated at
       runtime (max dist <= (2^31 - 2) / P, true for any bounded-universe
       L1 workload, with INT32_MAX reserved for the invalid sentinel); the
       rare overflow case falls back to lax.top_k over the id-sorted list,
       which keeps the same positional tie-break.
    """
    n = dataset.shape[0]
    q, ctot = ids.shape
    if n == 0 or ctot == 0:
        return _empty_result(q, k)
    big = jnp.int32(BIG_DIST)

    # 1. dedup (sorted ascending, duplicates + invalid -> sentinel n).
    sid = jnp.sort(jnp.where((ids < 0) | (ids > n), n, ids), axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=-1)
    sid = jnp.where(dup, n, sid)

    # 2. distances, chunked, no per-chunk selection.
    pad = (-sid.shape[1]) % chunk
    if pad:
        sid = jnp.pad(sid, ((0, 0), (0, pad)), constant_values=n)
    steps = sid.shape[1] // chunk
    ids_steps = sid.reshape(q, steps, chunk).transpose(1, 0, 2)     # (S,Q,c)

    def body(_, step_ids):
        sl = jnp.clip(step_ids, 0, n - 1)                           # (Q,c)
        rows = dataset[sl]                                          # (Q,c,m)
        with jax.named_scope("widen"):
            rows = widen_rows(rows)
        diff = rows - queries[:, None, :].astype(jnp.int32)
        d = jnp.abs(diff).sum(axis=-1).astype(jnp.int32)
        return None, jnp.where(step_ids >= n, big, d)

    _, d_steps = jax.lax.scan(body, None, ids_steps)                # (S,Q,c)
    d_all = d_steps.transpose(1, 0, 2).reshape(q, -1)               # (Q,Ct')
    ctp = d_all.shape[1]
    valid = d_all < big

    # 3. selection by one packed-key sort (or top_k when unpackable).
    # d_cap reserves INT32_MAX for the invalid sentinel: the largest valid
    # key is d_cap * p2 + (p2 - 1) <= 2^31 - 2 < imax, so no real candidate
    # can collide with it.
    p2 = _pow2_at_least(ctp)
    d_cap = (2 ** 31 - 1 - p2) // p2
    imax = jnp.int32(np.iinfo(np.int32).max)
    pos = jnp.broadcast_to(jnp.arange(ctp, dtype=jnp.int32), (q, ctp))

    def packed(_):
        key = jnp.where(valid, d_all * p2 + pos, imax)
        skey = jnp.sort(key, axis=-1)
        if ctp < k:
            skey = jnp.pad(skey, ((0, 0), (0, k - ctp)),
                           constant_values=np.iinfo(np.int32).max)
        skey = skey[:, :k]
        kd = skey // p2
        kp_ = jnp.clip(skey & (p2 - 1), 0, ctp - 1)
        ki = jnp.take_along_axis(sid, kp_, axis=-1)
        bad = skey == imax
        return (jnp.where(bad, big, kd).astype(jnp.int32),
                jnp.where(bad, -1, ki))

    def via_topk(_):
        nd, sel = jax.lax.top_k(-d_all, min(k, ctp))
        kd, ki = -nd, jnp.take_along_axis(sid, sel, axis=-1)
        if kd.shape[1] < k:
            kd = jnp.pad(kd, ((0, 0), (0, k - kd.shape[1])),
                         constant_values=BIG_DIST)
            ki = jnp.pad(ki, ((0, 0), (0, k - ki.shape[1])),
                         constant_values=n)
        return kd, jnp.where(kd >= big, -1, ki)

    max_d = jnp.max(jnp.where(valid, d_all, 0))
    return jax.lax.cond(max_d <= d_cap, packed, via_topk, operand=None)
