"""Rerank: gather + L1 + top-k selection in one pass (DESIGN.md §Perf).

The exact L1 rerank dominates MP-RW-LSH query cost (paper Sect. 5: multi-probe
trades cheap extra probes for fewer tables, so candidate reranking is where
the time goes).  ``fused_rerank_xla`` runs it in XLA ops on every backend: a
chunked distance scan with **no per-chunk top_k**, then one lexicographic
(dist, id) selection sort.  Duplicate candidate ids (a point probed from
several tables) are dropped by one id sort up front, so the candidate slab
is reranked as phase B hands it over, duplicates and all.

Output contract (pinned bit for bit against ``ref.fused_rerank`` by
tests/test_fused_rerank.py, including tie cases):

    the k lexicographically-(dist, id)-smallest pairs over the *unique*
    valid candidate ids, ascending; invalid/padded slots carry
    (BIG_DIST, -1).  Candidate ids < 0 or >= n are invalid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .rows import widen_rows

__all__ = ["fused_rerank_xla", "BIG_DIST"]

# Matches core.pipeline.BIG_DIST (kernels must not import core).
BIG_DIST = np.iinfo(np.int32).max // 2


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def _empty_result(q: int, k: int):
    return (jnp.full((q, k), BIG_DIST, jnp.int32),
            jnp.full((q, k), -1, jnp.int32))


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def fused_rerank_xla(
    dataset: jax.Array, queries: jax.Array, ids: jax.Array, k: int,
    chunk: int = 512,
):
    """Rerank ``ids`` against ``dataset`` (module docstring contract).

    dataset (n, m) stored rows (``kernels.rows``: uint8 rows hold x // 2);
    queries (Q, m) int; ids (Q, Ctot) int32, duplicates allowed.  Returns
    (dists (Q, k) int32, ids (Q, k) int32), lex-(dist, id) ascending.

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
