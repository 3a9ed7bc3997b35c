"""Fused probe front-end: bucket lookup + compacted candidate gather in one
pass (DESIGN.md §8).

The staged front-end (``pipeline.stage_bucket_lookup`` +
``stage_candidate_gather``) materializes per-(table, probe) ``lo/hi`` range
arrays in HBM and then a fixed worst-case ``(Q, L*P*C)`` candidate slab that
is *mostly sentinels* — multi-probe trades tables for probes (the paper's
economy), so the probe count ``L*P`` is large while each probed bucket holds
far fewer than ``candidate_cap`` points.  The fused rerank then pays for
every sentinel lane.

This module fuses lookup + gather and **compacts** the result: valid
candidates are packed to the front of a ``(Q, cbucket)`` slab (callers pick
``cbucket`` from the per-query valid-candidate counts — the same pow-2
shape-bucket discipline the serving engine uses for batch sizes), so the
rerank runs at ~actual occupancy instead of worst-case ``L*P*C``.

Two executors, **bit-identical** to each other and to ``ref.fused_probe``
(pinned by tests/test_fused_probe.py):

* ``fused_probe_pallas`` — the Pallas kernel.  Grid over query tiles; per
  tile the binary search over each table's sorted keys runs in-kernel
  (vectorized bisection over the ``(bq, L*P)`` probe keys — the ``lo/hi``
  extents live in registers/VMEM and never reach HBM), bucket occupancies
  are clamped to ``cap`` and prefix-summed, and the compaction gather maps
  every output slot back to its (table, probe, offset) via a second
  in-kernel bisection over the prefix sums.
* ``fused_probe_xla`` — the XLA executor, which every backend runs
  (``kernels/ops.executors``): ``searchsorted`` for the extents, then a
  dense slot->bucket map in place of the kernel's second bisection.  Each
  bucket's base delta (its flat read offset minus the previous bucket's)
  is scatter-added at the bucket's start slot, and a prefix sum over the
  slots turns those into every slot's flat index into ``sorted_ids``; one
  gather then reads the ids.  The HBM intermediates are ``(Q, L*P)``
  count rows (already ~C× smaller than the staged slab) and two
  ``(Q, cbucket)`` slabs.  The parity tests keep it bit-identical to the
  bisecting Pallas kernel.

Output contract:

    ids    : (Q, cbucket) int32 — the valid candidates of the staged gather
             in the same (table-major, probe, bucket-offset) order, packed
             to the front; tail slots carry the sentinel ``n``.  When a
             query's count exceeds ``cbucket`` the surplus is truncated
             (callers derive ``cbucket`` from the counts, so a non-binding
             bucket never truncates).
    counts : (Q,) int32 — per-query valid candidates, i.e.
             ``sum_{l,p} min(hi - lo, cap)``, NOT clipped to ``cbucket``
             (so callers can detect a binding bucket and re-bucket).

    Per-bucket truncation is a deterministic *sorted-order prefix*: a bucket
    with occupancy > cap contributes exactly its first ``cap`` rows in
    sorted-ids order (slots ``lo .. lo+cap``).  DESIGN.md §9's two-level
    compaction leans on this — a tighter cap is reproducible and
    oracle-checkable (the python/np oracle applies the same prefix rule).

VMEM budget of the Pallas kernel (bq=8): sorted keys + ids are mapped as one
(L, n) block each (2*L*n*4 B — segment-sized shards fit easily), the probe
keys tile is bq*L*P*4 B, and the compact output tile bq*cbucket*4 B.  The
TPU-scale evolution is an ANY-space keys ref with per-table DMA, which
changes only the load, not the semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["fused_probe_pallas", "fused_probe_xla", "probe_extents_xla",
           "compact_gather_xla"]

_UINT32_MAX = np.uint32(0xFFFFFFFF)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _empty(q: int, cbucket: int):
    # n == 0: every slot invalid and the sentinel for n=0 is 0 itself
    # (matches pipeline.stage_candidate_gather's zero-point convention).
    return (jnp.zeros((q, cbucket), jnp.int32), jnp.zeros((q,), jnp.int32))


def _bisect(gather, targets, hi0: int, steps: int, right: bool):
    """Vectorized binary search: per-element insertion point in [0, hi0].

    ``gather(idx)`` returns the sorted value at ``idx`` (same shape as
    ``targets``); ``right`` selects bisect_right (first index whose value is
    > target) vs bisect_left.  ``steps`` must be >= ceil(log2(hi0 + 1)).
    Pure integer bisection — both executors use this exact recurrence, so
    they agree with ``jnp.searchsorted`` bit-for-bit (the insertion point
    is unique).
    """
    lo = jnp.zeros(targets.shape, jnp.int32)
    hi = jnp.full(targets.shape, hi0, jnp.int32)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        v = gather(mid)
        go_right = (v <= targets) if right else (v < targets)
        return jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _probe_kernel(pk_ref, keys_ref, ids_ref, out_ref, cnt_ref, *,
                  n: int, p: int, cap: int, cbucket: int):
    bq, lp = pk_ref.shape
    keys_flat = keys_ref[...].reshape(-1)               # (L * n_pad,)
    ids_flat = ids_ref[...].reshape(-1)
    n_pad = keys_ref.shape[1]
    pk = pk_ref[...]                                    # (bq, L*P) uint32

    # Per-(table, probe) bucket extents via in-kernel bisection.  The search
    # spans the padded tail (pad keys are UINT32_MAX), so hi is clamped to n
    # — a probe key equal to UINT32_MAX would otherwise count pad rows.
    table_base = (jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 1) // p) * n_pad
    steps = max(1, int(n_pad).bit_length())
    lo = _bisect(lambda m: jnp.take(keys_flat, table_base + m), pk,
                 n_pad, steps, right=False)
    hi = _bisect(lambda m: jnp.take(keys_flat, table_base + m), pk,
                 n_pad, steps, right=True)
    lo = jnp.minimum(lo, n)
    hi = jnp.minimum(hi, n)

    cnt = jnp.minimum(hi - lo, cap)                     # (bq, L*P)
    csum = jnp.cumsum(cnt, axis=-1).astype(jnp.int32)   # inclusive prefix
    total = csum[:, -1:]                                # (bq, 1)
    start = csum - cnt                                  # exclusive prefix

    # Compaction gather: output slot j belongs to the first segment whose
    # inclusive prefix exceeds j; its offset within the segment is
    # j - start[seg].  Bisection again — over the per-row prefix sums.
    slot = jax.lax.broadcasted_iota(jnp.int32, (bq, cbucket), 1)
    row_base = jax.lax.broadcasted_iota(jnp.int32, (bq, cbucket), 0) * lp
    csum_flat = csum.reshape(-1)
    seg = _bisect(lambda m: jnp.take(csum_flat, row_base + jnp.minimum(m, lp - 1)),
                  slot, lp, max(1, lp.bit_length()), right=True)
    seg = jnp.minimum(seg, lp - 1)
    valid = slot < total                                # (bq, cbucket)

    def row_take(arr2d, idx):                           # (bq, lp)[row, idx]
        return jnp.take(arr2d.reshape(-1), row_base + idx)

    pos = row_take(lo, seg) + (slot - row_take(start, seg))
    flat = (seg // p) * n_pad + jnp.clip(pos, 0, n_pad - 1)
    ids = jnp.take(ids_flat, flat)
    out_ref[...] = jnp.where(valid, ids, n)
    cnt_ref[...] = total


@functools.partial(jax.jit,
                   static_argnames=("cap", "cbucket", "bq", "interpret"))
def fused_probe_pallas(
    sorted_keys: jax.Array, sorted_ids: jax.Array, probe_keys: jax.Array,
    cap: int, cbucket: int, bq: int = 8, interpret: bool = False,
):
    """Fused lookup + compacted gather.  See module docstring for contract.

    sorted_keys (L, n) uint32 ascending per table; sorted_ids (L, n) int32;
    probe_keys (Q, L, P) uint32.  Returns (ids (Q, cbucket) int32 sentinel n,
    counts (Q,) int32).
    """
    l, n = sorted_keys.shape
    q = probe_keys.shape[0]
    p = probe_keys.shape[2]
    if n == 0 or cbucket == 0 or q == 0:
        return _empty(q, cbucket)
    n_pad = _round_up(n, 128)
    kp = jnp.pad(sorted_keys, ((0, 0), (0, n_pad - n)),
                 constant_values=_UINT32_MAX)
    ip = jnp.pad(sorted_ids, ((0, 0), (0, n_pad - n)), constant_values=n)
    pk = probe_keys.reshape(q, l * p)
    pq = (-q) % bq
    if pq:
        pk = jnp.pad(pk, ((0, pq), (0, 0)))
    cbp = _round_up(cbucket, 128)
    grid = (pk.shape[0] // bq,)
    out, cnt = pl.pallas_call(
        functools.partial(_probe_kernel, n=n, p=p, cap=cap, cbucket=cbp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, l * p), lambda i: (i, 0)),
            pl.BlockSpec((l, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((l, n_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, cbp), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pk.shape[0], cbp), jnp.int32),
            jax.ShapeDtypeStruct((pk.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(pk, kp, ip)
    return out[:q, :cbucket], cnt[:q, 0]


# --------------------------------------------------------------------------
# XLA executor (non-TPU backends)
# --------------------------------------------------------------------------

def probe_extents_xla(sorted_keys: jax.Array, probe_keys: jax.Array,
                      cap: int, occ_from=None):
    """Raw bucket extents: the fused front-end's phase-A state.

    Returns (lo (Q, L*P) int32, occ (Q, L*P) int32 — the *unclamped*
    per-bucket occupancies ``hi - lo`` — and counts (Q,) int32 = per-query
    totals under ``cap``, i.e. ``sum min(occ, cap)``).  The two-phase
    serving path carries (lo, occ) across the host-side candidate-bucket
    pick so the gather phase neither re-searches nor re-scans — C× smaller
    than the staged slab, the minimal state that can cross the pick.
    Keeping ``occ`` raw (clamping deferred to ``compact_gather_xla``) is
    what makes two-level compaction free: the gather phase can apply ANY
    per-bucket cap ``c_cap <= cap`` to the same extents, so the overflow
    pick (DESIGN.md §9) costs no extra phase-A work.  (The one-pass Pallas
    kernel keeps even this in VMEM; on TPU the gather phase simply
    re-searches in-kernel from the probe keys instead of consuming
    extents.)

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
    owner.  O(Q*L*P + Q*cbucket), against the Pallas kernel's per-slot
    bisection over the prefix sums (same result, pinned by the parity
    tests).
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
    """Same contract as ``fused_probe_pallas``, expressed in XLA ops.

    One-pass composition of ``probe_extents_xla`` + ``compact_gather_xla``:
    the per-(table, probe) extents exist only as fused ``(Q, L*P)`` count
    rows; the ``(Q, L, P, C)`` slab of the staged gather never does.
    """
    q = probe_keys.shape[0]
    p = probe_keys.shape[2]
    if sorted_keys.shape[1] == 0 or cbucket == 0 or q == 0:
        return _empty(q, cbucket)
    lo, occ, _ = probe_extents_xla(sorted_keys, probe_keys, cap)
    return compact_gather_xla(sorted_ids, lo, occ, p, cbucket, cap)
