"""Plain reference and the comparison that decides ``correct``.

The reference is brute-force L1 in plain ``jax.numpy``: it imports nothing
of the program and uses nothing the program made.  It runs on the chip
after the measured window, one block of queries at a time, scanning the
rows in chunks with a running top-k, so that it fits beside the data.

What is compared, for the answers the timed path returned:

* ``wrong_answers`` (limit 0): answers that break the exact-rerank
  guarantee.  An answer is wrong when its id is not a row of the index,
  repeats an id earlier in the same result, sits out of ascending order, or
  reports a distance that is not the exact L1 distance from the query to
  that row.  Checked for every answer the window served.
* ``recall_at_10`` (limit: the configuration's stated recall): the share of
  returned ids whose exact distance is at most the true k-th nearest
  distance (ANN-Benchmarks' definition, so ties at the k-th distance do not
  count against either side), averaged over a fixed prefix of the served
  queries.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["l1_exact", "knn_dist", "knn_bf16", "compare"]

BIG = np.iinfo(np.int32).max
QUERY_BLOCK = 32
STEP_ELEMENTS = 1 << 26        # queries x rows x dims in one scan step


def _chunk_rows(n: int, dim: int) -> int:
    return max(1, min(n, STEP_ELEMENTS // (QUERY_BLOCK * dim)))


def _l1_int32(rows, queries):
    """(Q, r) exact L1 distances in int32 (the configuration's precision)."""
    diff = rows[None, :, :].astype(jnp.int32) - queries[:, None, :]
    return jnp.abs(diff).sum(axis=-1, dtype=jnp.int32)


def _l1_bf16(rows, queries):
    """(Q, r) L1 distances computed and kept in bfloat16 (the control)."""
    diff = (rows[None, :, :].astype(jnp.bfloat16)
            - queries[:, None, :].astype(jnp.bfloat16))
    d = jnp.abs(diff).sum(axis=-1, dtype=jnp.bfloat16)
    return jnp.minimum(d.astype(jnp.float32), BIG // 2).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "dist"))
def _scan_topk(data, queries, *, k: int, dist):
    """(Q, k) smallest distances and their row ids, ascending.

    Rows are scanned in chunks of ``_chunk_rows``; the last chunk is
    clamped to the end of the data and its rows already seen are masked,
    so no row is counted twice and no row is padded.
    """
    n, dim = data.shape
    rows = _chunk_rows(n, dim)
    steps = -(-n // rows)
    q = queries.shape[0]

    def body(s, carry):
        best_d, best_i = carry
        start = jnp.minimum(s * rows, n - rows)
        chunk = jax.lax.dynamic_slice_in_dim(data, start, rows)
        ids = start + jnp.arange(rows, dtype=jnp.int32)
        d = dist(chunk, queries)
        d = jnp.where(ids[None, :] < s * rows, BIG, d)
        cd = jnp.concatenate([best_d, d], axis=1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, (q, rows))],
                             axis=1)
        neg, sel = jax.lax.top_k(-cd, k)
        return -neg, jnp.take_along_axis(ci, sel, axis=1)

    init = (jnp.full((q, k), BIG, jnp.int32), jnp.full((q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, steps, body, init)


def _blocked(data, queries, k, dist):
    queries = np.asarray(queries, np.int32)
    out_d, out_i = [], []
    for lo in range(0, queries.shape[0], QUERY_BLOCK):
        block = queries[lo:lo + QUERY_BLOCK]
        pad = QUERY_BLOCK - block.shape[0]
        if pad:
            block = np.concatenate([block, np.repeat(block[:1], pad, 0)])
        d, i = _scan_topk(data, jnp.asarray(block), k=k, dist=dist)
        out_d.append(np.asarray(d)[:QUERY_BLOCK - pad])
        out_i.append(np.asarray(i)[:QUERY_BLOCK - pad])
    return np.concatenate(out_d), np.concatenate(out_i)


def knn_dist(data, queries, k: int) -> np.ndarray:
    """(Q, k) exact k smallest L1 distances, ascending (int32)."""
    return _blocked(data, queries, k, _l1_int32)[0]


def knn_bf16(data, queries, k: int):
    """The control: brute force with distances in bfloat16.

    Stands where the program stands and returns (dists, ids) as it does;
    ranking and reported distances carry bfloat16's rounding.
    """
    return _blocked(data, queries, k, _l1_bf16)


@jax.jit
def _l1_of_ids(data, queries, ids):
    n = data.shape[0]
    rows = data[jnp.clip(ids, 0, n - 1)]                    # (Q, k, dim)
    diff = rows.astype(jnp.int32) - queries[:, None, :]
    return jnp.abs(diff).sum(axis=-1, dtype=jnp.int32)


def l1_exact(data, queries, ids) -> np.ndarray:
    """(Q, k) exact L1 distance from each query to each of its ids' rows."""
    queries = np.asarray(queries, np.int32)
    ids = np.asarray(ids, np.int32)
    out = []
    for lo in range(0, queries.shape[0], 256):
        out.append(np.asarray(_l1_of_ids(
            data, jnp.asarray(queries[lo:lo + 256]),
            jnp.asarray(ids[lo:lo + 256]))))
    return np.concatenate(out) if out else np.zeros(ids.shape, np.int32)


def compare(data, queries, dists, ids, recall_queries: int, k: int) -> dict:
    """The numbers that decide ``correct`` for answers (dists, ids).

    ``queries`` (Q, dim) are the served queries in order, ``dists``/``ids``
    (Q, k) what the timed path returned for them; the recall uses the first
    ``recall_queries`` of them.  Returns ``wrong_answers``,
    ``failed_queries`` (queries with any wrong answer) and ``recall_at_10``.
    """
    n = data.shape[0]
    dists = np.asarray(dists, np.int64)
    ids = np.asarray(ids, np.int64)
    exact = l1_exact(data, queries, ids).astype(np.int64)
    valid = (ids >= 0) & (ids < n)
    repeat = np.tril(ids[:, :, None] == ids[:, None, :], -1).any(axis=2)
    order = np.concatenate([np.ones((ids.shape[0], 1), bool),
                            dists[:, 1:] >= dists[:, :-1]], axis=1)
    wrong = ~valid | repeat | ~order | (exact != dists)
    r = int(recall_queries)
    kth = knn_dist(data, queries[:r], k)[:, k - 1].astype(np.int64)
    hit = valid[:r] & ~repeat[:r] & (exact[:r] <= kth[:, None])
    return {"wrong_answers": int(wrong.sum()),
            "failed_queries": int(wrong.any(axis=1).sum()),
            "recall_at_10": float(hit.sum() / (r * k))}
