"""Probe front-end: bucket extents, then a compacted candidate gather
(DESIGN.md §8).

Multi-probe trades tables for probes (the paper's economy), so the probe
count ``L*P`` is large while each probed bucket holds far fewer than
``candidate_cap`` points: a fixed worst-case ``(Q, L*P*C)`` candidate slab
would be mostly sentinels, and the rerank would pay for every one.  This
module **compacts** instead: valid candidates are packed to the front of a
``(Q, cbucket)`` slab (callers pick ``cbucket`` from the per-query
valid-candidate counts — the same pow-2 shape-bucket discipline the serving
engine uses for batch sizes), so the rerank runs at about the actual
occupancy.

Plain XLA ops on every backend, pinned bit for bit against ``ref.fused_probe``
and a python oracle by tests/test_fused_probe.py:

* ``probe_extents_xla`` — phase A: ``searchsorted`` for each probed
  bucket's start, its raw occupancy, and the per-query totals under the cap;
* ``compact_gather_xla`` — phase B: a dense slot->bucket map.  Each
  bucket's base delta (its flat read offset minus the previous bucket's)
  is scatter-added at the bucket's start slot, and a prefix sum over the
  slots turns those into every slot's flat index into ``sorted_ids``; one
  gather then reads the ids.  The HBM intermediates are ``(Q, L*P)``
  count rows and two ``(Q, cbucket)`` slabs;
* ``fused_probe_xla`` — the two in one pass.

Output contract:

    ids    : (Q, cbucket) int32 — the valid candidates in (table-major,
             probe, bucket-offset) order, packed to the front; tail slots
             carry the sentinel ``n``.  When a query's count exceeds
             ``cbucket`` the surplus is truncated (callers derive
             ``cbucket`` from the counts, so a non-binding bucket never
             truncates).
    counts : (Q,) int32 — per-query valid candidates, i.e.
             ``sum_{l,p} min(hi - lo, cap)``, NOT clipped to ``cbucket``
             (so callers can detect a binding bucket and re-bucket).

    Per-bucket truncation is a deterministic *sorted-order prefix*: a bucket
    with occupancy > cap contributes exactly its first ``cap`` rows in
    sorted-ids order (slots ``lo .. lo+cap``).  DESIGN.md §9's two-level
    compaction leans on this — a tighter cap is reproducible and
    oracle-checkable (the python/np oracle applies the same prefix rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["fused_probe_xla", "probe_extents_xla", "compact_gather_xla"]


def _empty(q: int, cbucket: int):
    # n == 0: every slot invalid and the sentinel for n=0 is 0 itself.
    return (jnp.zeros((q, cbucket), jnp.int32), jnp.zeros((q,), jnp.int32))


def probe_extents_xla(sorted_keys: jax.Array, probe_keys: jax.Array,
                      cap: int, occ_from=None):
    """Raw bucket extents: the fused front-end's phase-A state.

    Returns (lo (Q, L*P) int32, occ (Q, L*P) int32 — the *unclamped*
    per-bucket occupancies ``hi - lo`` — and counts (Q,) int32 = per-query
    totals under ``cap``, i.e. ``sum min(occ, cap)``).  The two-phase
    serving path carries (lo, occ) across the host-side candidate-bucket
    pick so the gather phase neither re-searches nor re-scans — C× smaller
    than a worst-case (Q, L*P*C) slab, the minimal state that can cross the
    pick.
    Keeping ``occ`` raw (clamping deferred to ``compact_gather_xla``) is
    what makes two-level compaction free: the gather phase can apply ANY
    per-bucket cap ``c_cap <= cap`` to the same extents, so the overflow
    pick (DESIGN.md §9) costs no extra phase-A work.

    ``occ_from`` — the build-time run-length table (``IndexState.occ_from``:
    ``occ_from[t, i]`` = length of the equal-key run starting at ``i``) —
    replaces the entire ``side='right'`` search with two gathers: ``lo`` is
    always a run start, so ``hi - lo == occ_from[lo]`` when the probed key
    exists (and the probe hit/miss is one key compare at ``lo``).  That
    halves the front-end's binary-search work; without it the extents fall
    back to the two-sided search.
    """
    l, n = sorted_keys.shape
    q = probe_keys.shape[0]
    p = probe_keys.shape[2]
    if n == 0:
        z = jnp.zeros((q, l * p), jnp.int32)
        return z, z, jnp.zeros((q,), jnp.int32)

    if occ_from is None:
        def per_table(sk, pk):  # sk (n,), pk (Q, P)
            lo = jnp.searchsorted(sk, pk, side="left")
            hi = jnp.searchsorted(sk, pk, side="right")
            return lo, hi

        lo, hi = jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
            sorted_keys, probe_keys)                    # (Q, L, P)
        occ = (hi - lo).reshape(q, l * p).astype(jnp.int32)
        lo = lo.reshape(q, l * p).astype(jnp.int32)
    else:
        # 'scan_unrolled' trades code size for ~25% less per-step overhead
        # on the XLA CPU searchsorted loop — this is the serving hot path.
        lo = jax.vmap(
            lambda sk, pk: jnp.searchsorted(sk, pk, side="left",
                                            method="scan_unrolled"),
            in_axes=(0, 1), out_axes=1)(sorted_keys, probe_keys)
        lo = lo.reshape(q, l * p).astype(jnp.int32)
        pk_flat = probe_keys.reshape(q, l * p)
        table_base = (jnp.arange(l * p, dtype=jnp.int32) // p) * n
        safe = table_base[None, :] + jnp.minimum(lo, n - 1)
        hit = (jnp.take(sorted_keys.reshape(-1), safe) == pk_flat) & (lo < n)
        occ = jnp.where(hit, jnp.take(occ_from.reshape(-1), safe),
                        0).astype(jnp.int32)
    with jax.named_scope("counts"):
        counts = jnp.minimum(occ, cap).sum(axis=-1).astype(jnp.int32)
    return lo, occ, counts


@functools.partial(jax.jit, static_argnames=("p", "cbucket", "cap"))
def compact_gather_xla(sorted_ids: jax.Array, lo: jax.Array,
                       occ: jax.Array, p: int, cbucket: int, cap: int):
    """Phase B: compacted gather from precomputed extents.

    sorted_ids (L, n); lo/occ (Q, L*P) from ``probe_extents_xla`` (same
    probe order, table-major).  Each bucket contributes its first
    ``min(occ, cap)`` rows (sorted-order-prefix truncation — deterministic,
    so a capped gather is oracle-checkable); ``cap`` may be any value, not
    just the ``cap`` the extents were computed at, which is how the
    two-level overflow rung applies a tighter per-bucket cap without
    re-running phase A.  Returns (ids (Q, cbucket) int32 sentinel n,
    counts (Q,) — totals under THIS cap).

    Slot j of bucket s reads ``sorted_ids`` at the flat index
    ``base[s] + j``, with ``base[s] = lo[s] - start[s] + (s // p) * n``
    and ``start`` the exclusive prefix of the clamped counts.  The map
    from slots to buckets is built densely: the deltas ``base[s] -
    base[s-1]`` are scatter-added at the starts into a zero
    ``(Q, cbucket)`` slab and prefix-summed over the slots, so each slot
    holds the base of the last bucket starting at or before it — its
    owner.  O(Q*L*P + Q*cbucket): no per-slot search over the prefix sums.
    """
    l, n = sorted_ids.shape
    q, lp = lo.shape
    if n == 0 or cbucket == 0 or q == 0:
        return _empty(q, cbucket)
    cnt = jnp.minimum(occ, cap).astype(jnp.int32)
    csum = jnp.cumsum(cnt, axis=-1).astype(jnp.int32)   # inclusive prefix
    total = csum[:, -1]
    start = csum - cnt                                  # exclusive prefix

    # An empty bucket shares its start with the next one, so coinciding
    # starts telescope; starts past cbucket own no slot and drop out.
    table = jnp.arange(lp, dtype=jnp.int32) // p
    base = lo - start + table[None, :] * n
    delta = jnp.diff(base, axis=-1, prepend=0)
    rows = jnp.arange(q, dtype=jnp.int32)[:, None]
    marks = jnp.zeros((q, cbucket), jnp.int32).at[rows, start].add(
        delta, mode="drop")
    slot = jnp.arange(cbucket, dtype=jnp.int32)
    flat = jnp.cumsum(marks, axis=-1, dtype=jnp.int32) + slot[None, :]
    valid = slot[None, :] < total[:, None]
    ids = jnp.take(sorted_ids.reshape(-1), jnp.clip(flat, 0, l * n - 1))
    return jnp.where(valid, ids, n), total


@functools.partial(jax.jit, static_argnames=("cap", "cbucket"))
def fused_probe_xla(
    sorted_keys: jax.Array, sorted_ids: jax.Array, probe_keys: jax.Array,
    cap: int, cbucket: int,
):
    """Extents and compacted gather in one pass (module docstring contract).

    One-pass composition of ``probe_extents_xla`` + ``compact_gather_xla``:
    the per-(table, probe) extents exist only as ``(Q, L*P)`` count rows;
    no ``(Q, L, P, C)`` slab ever does.
    """
    q = probe_keys.shape[0]
    p = probe_keys.shape[2]
    if sorted_keys.shape[1] == 0 or cbucket == 0 or q == 0:
        return _empty(q, cbucket)
    lo, occ, _ = probe_extents_xla(sorted_keys, probe_keys, cap)
    return compact_gather_xla(sorted_ids, lo, occ, p, cbucket, cap)
