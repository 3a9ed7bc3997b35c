"""MP-RW-LSH query stages (DESIGN.md Sect. 3).

Every query, flat, segmented, sharded or served, runs one composition:

    phase A : hash -> probe keys -> bucket extents + candidate counts
    rung    : one host read of the batch's max count picks a static slab
              width (``pick_rung``; the flat and sharded paths take the
              full ``L*P*C`` slab instead and read nothing)
    phase B : compacted gather at the rung -> rerank at k + T -> gid map
              -> tombstone -> first k
    merge   : per-segment or per-shard (Q, k) lists folded by (dist, id)

``core.index.probe_index`` is phase A and ``core.index.finish_query`` is
phase B; the flat query (``core.index.query_index``), the segmented index
(``core.segments``), the shard_map body (``launch.dist_index``) and the
serving engine all call those two.  The stages here take raw arrays (no
``IndexState``), each with one executor (``kernels.ops``).

Stage contracts (Q queries, L tables, M hashes, P probes/table, C cap):

  stage_hash       : queries (Q, m)            -> bucket, x_neg (Q, L, M)
  stage_probe_keys : bucket, x_neg             -> probe_keys (Q, L, P) uint32
  stage_probe_extents : sorted_keys, probe_keys -> lo, occ (Q, L*P), counts (Q,)
  stage_fused_probe : sorted_ids, extents      -> ids (Q, Cb), counts (Q,)
  stage_rerank     : dataset, queries, ids     -> (dists, ids) (Q, k) asc
  stage_tombstone  : (dists, gids) (Q, k + T), tombstones
                                               -> first k live (Q, k) asc
  rerank_candidates : rerank -> gid map -> tombstone (T = tombstone length)
  stage_merge_pair : two (Q, k) ascending lists -> one (Q, k) ascending list
  stage_merge_concat : (Q, R*k) stacked lists  -> (Q, k)

The gather packs valid candidates to the front of a ``(Q, cbucket)`` slab,
duplicates (a point probed from several tables) included; the rerank drops
duplicates itself and returns the k lexicographically-(dist, id)-smallest
unique candidates.  That contract depends only on the candidate *set*, so
any rung that covers the batch's counts gives the bits of the full slab —
which is also exactly what the pre-refactor monolithic ``query_index``
computed (tests/test_segments.py proves it against a frozen copy of the
seed implementation).

Tombstones (DESIGN.md §3.1) apply after selection, not before it: the
rerank selects the k + T lex-(dist, id)-smallest unique candidates, where T
is the static length of the tombstone array; at most T of them are dead,
so the first k live ones among them are exactly the k a per-candidate mask
ahead of the rerank would leave.  The check then costs Q * (k + T) lookups
instead of one per slot of the candidate slab.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

from . import hashes as hashes_lib
from . import multiprobe as mp_lib

__all__ = [
    "BIG_DIST",
    "stage_hash",
    "stage_probe_keys",
    "stage_probe_extents",
    "stage_probe_counts",
    "stage_fused_probe",
    "stage_rerank",
    "stage_tombstone",
    "tomb_select",
    "rerank_candidates",
    "stage_merge_pair",
    "stage_merge_concat",
    "max_bucket_occupancy",
    "oracle_candidate_cap",
    "occupancy_quantile",
    "candidate_ladder",
    "candidate_bucket",
    "rung_ladder",
    "pick_rung",
]

# Sentinel distance for invalid/padded slots; iinfo//2 so two of them still
# fit in int32 when summed inside merge kernels.
BIG_DIST = np.iinfo(np.int32).max // 2


def stage_hash(cfg, params: hashes_lib.LshParams, queries: jax.Array):
    """Raw-hash + quantize.  Returns (bucket (Q,L,M) int32, x_neg (Q,L,M))."""
    f = hashes_lib.raw_hash(params, queries, impl=cfg.hash_impl)
    return hashes_lib.bucket_and_offsets(params, f)


def stage_probe_keys(
    cfg, params: hashes_lib.LshParams, template: jax.Array,
    bucket: jax.Array, x_neg: jax.Array,
) -> jax.Array:
    """Instantiate the universal template and mix probe buckets into keys.

    Returns (Q, L, P) uint32 probe keys (P = num_probes + 1, epicenter first).
    """
    # (Q, L, P, M) perturbations — paper refinement 3, batched.
    deltas = mp_lib.instantiate_template(template, x_neg, float(cfg.width))
    probe_buckets = bucket[:, :, None, :] + deltas.astype(jnp.int32)
    # mix_keys expects (..., L, M): move the probe axis ahead of L.
    probe_keys = hashes_lib.mix_keys(
        params, probe_buckets.transpose(0, 2, 1, 3))            # (Q, P, L)
    return probe_keys.transpose(0, 2, 1)                        # (Q, L, P)


def stage_probe_extents(cfg, sorted_keys: jax.Array, probe_keys: jax.Array,
                        occ_from=None):
    """Raw bucket extents + per-query candidate counts — phase A.

    Returns (lo (Q, L*P) int32, occ (Q, L*P) int32 — *unclamped* per-bucket
    occupancies — and counts (Q,) int32 totals under ``cfg.candidate_cap``).
    The two-phase serving path runs this as its own jitted phase, pulls
    ``counts.max()`` to the host, picks a rung (``pick_rung``), and hands
    (lo, occ) back to ``stage_fused_probe`` so the gather phase neither
    re-searches nor re-scans.  The counts are exactly what the gather
    reports, so a bucket >= the max count can never truncate; the raw
    occupancies let the overflow rung apply a tighter per-bucket cap
    (``c_cap``) to the same extents (DESIGN.md §9).

    ``occ_from`` (``IndexState.occ_from``, the build-time run-length table)
    replaces the ``side='right'`` search with two gathers — pass it on the
    serving hot path.
    """
    return kops.probe_extents(sorted_keys, probe_keys, cfg.candidate_cap,
                              occ_from=occ_from)


def stage_probe_counts(cfg, sorted_keys: jax.Array, probe_keys: jax.Array,
                       occ_from=None) -> jax.Array:
    """Per-query valid-candidate count: ``sum_{l,p} min(hi - lo, cap)``."""
    return stage_probe_extents(cfg, sorted_keys, probe_keys, occ_from)[2]


def stage_fused_probe(
    cfg, sorted_keys: jax.Array, sorted_ids: jax.Array,
    probe_keys: jax.Array, n: int, cbucket: Optional[int] = None,
    extents=None, c_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Bucket lookup + compacted candidate gather (DESIGN.md §8).

    Returns (ids (Q, cbucket) int32 sentinel n — valid candidates packed to
    the front in (table, probe, offset) order, counts (Q,) int32).
    ``cbucket`` defaults to the full worst-case ``L*P*C`` width (the
    (Q, L, P, C) slab never exists, but the slab is not narrowed); a
    caller-picked static ``cbucket`` shrinks the slab the rerank pays for.
    ``cbucket`` must cover the actual counts or the tail candidates are
    dropped (callers derive it from ``stage_probe_extents``, whose (lo,
    occ) pair can be passed back here as ``extents`` to skip the
    re-search).  ``c_cap`` tightens the per-bucket cap below
    ``cfg.candidate_cap`` — the two-level truncate rung (DESIGN.md §9);
    truncation is the deterministic sorted-order prefix of each bucket.
    """
    cap = cfg.candidate_cap
    if c_cap is not None:
        cap = min(cap, max(1, int(c_cap)))
    if cbucket is None:
        cbucket = cfg.num_tables * cfg.probes_per_table * cap
    return kops.fused_probe(
        sorted_keys, sorted_ids, probe_keys, cap, cbucket,
        extents=extents)


# --------------------------------------------------------------------------
# Candidate-count shape buckets (host-side policy helpers)
# --------------------------------------------------------------------------

def max_bucket_occupancy(sorted_keys, occ_from=None) -> int:
    """Largest run of equal bucket keys over all tables (host-side).

    The one shared derivation of "how many candidates can a single probed
    bucket hold": the quality oracle's union-exactness cap
    (``oracle_candidate_cap``) and the candidate-compaction ladder
    (``candidate_ladder`` via segments' per-segment ctot cap) both build on
    it, so the two cannot drift.  When the build-time run-length table
    (``IndexState.occ_from``) is at hand its max IS this quantity — one
    device reduce instead of a host run-length sweep.
    """
    if occ_from is not None and occ_from.size:
        # device reduce + scalar transfer — never np.asarray the (L, n)
        # table to host (this runs at every segment seal/compaction)
        return max(1, int(occ_from.max()))
    keys = np.asarray(sorted_keys)
    if keys.size == 0:
        return 1
    runs = keys[..., 1:] == keys[..., :-1]
    if not runs.any():
        return 1
    best = 1
    for t in range(keys.shape[0]):
        r = runs[t]
        # lengths of True-runs, vectorized: positions where runs flip
        idx = np.flatnonzero(np.diff(np.concatenate(([False], r, [False]))))
        if idx.size:
            best = max(best, int((idx[1::2] - idx[::2]).max()) + 1)
    return best


def oracle_candidate_cap(cfg, sorted_keys, occ_from=None) -> int:
    """Candidate cap that makes any gather over ``sorted_keys`` exhaustive.

    At this cap no probed bucket is ever truncated, so per-shard/per-segment
    candidate sets union to exactly the flat index's set — the precondition
    for the cross-layer bit-identity oracles (eval/quality.py).
    """
    return max(cfg.candidate_cap, max_bucket_occupancy(sorted_keys, occ_from))


def occupancy_quantile(occ_hist, q: float = 0.999) -> int:
    """Bucket-weighted occupancy quantile from ``IndexState.occ_hist``.

    ``occ_hist`` (L, B) counts non-empty buckets per ceil-log2 occupancy
    bin (bin b holds buckets with occupancy in (2^(b-1), 2^b]).  Returns
    the pow-2 upper edge of the first bin whose cumulative bucket count
    reaches quantile ``q`` — i.e. a per-bucket cap that leaves at most a
    ``1-q`` fraction of buckets truncated.  Pow-2 by construction, which
    is the static-stride rung discipline the per-bucket cap ladder wants
    (DESIGN.md §9).  Host-side; call at segment seal, not per query.
    """
    h = np.asarray(occ_hist).reshape(-1, np.asarray(occ_hist).shape[-1])
    h = h.sum(axis=0).astype(np.int64)                  # (B,) over tables
    total = int(h.sum())
    if total == 0:
        return 1
    target = int(np.ceil(min(max(q, 0.0), 1.0) * total))
    b = int(np.searchsorted(np.cumsum(h), max(target, 1)))
    return 1 << min(b, 31)


def candidate_ladder(ctot_cap: int, floor: int = 64) -> Tuple[int, ...]:
    """Pow-2 candidate-count buckets [floor, 2*floor, ...] topped by
    ``ctot_cap`` (the shard's real worst case, which may not be pow-2).

    The serving engine pre-compiles the gather+rerank phase at every rung
    (warmup's (batch-bucket x candidate-bucket) grid) and
    ``candidate_bucket`` only ever picks rungs, so live traffic cannot hit
    an uncompiled candidate shape.
    """
    ctot_cap = max(1, int(ctot_cap))
    floor = max(1, int(floor))
    out = []
    b = 1 << (floor - 1).bit_length()
    while b < ctot_cap:
        out.append(b)
        b *= 2
    out.append(ctot_cap)
    return tuple(out)


def candidate_bucket(count: int, ctot_cap: int, floor: int = 64) -> int:
    """Smallest ladder rung covering ``count`` valid candidates.

    O(1) bit-length arithmetic, not a ladder scan: the rung is the pow-2
    ceiling of ``max(count, floor)``, clipped to the ladder's non-pow-2
    top ``ctot_cap``.  Matches ``candidate_ladder`` exactly (pinned by
    tests) — the ladder enumerates rungs for warmup, this picks one per
    batch on the serving hot path.
    """
    ctot_cap = max(1, int(ctot_cap))
    need = max(1, int(count), int(floor))
    b = 1 << (need - 1).bit_length()
    return b if b < ctot_cap else ctot_cap


def rung_ladder(ctot_cap: int, floor: int = 64,
                ctot_norm: Optional[int] = None,
                c_cap: Optional[int] = None,
                overflow: str = "escalate",
                ) -> Tuple[Tuple[int, Optional[int]], ...]:
    """Two-level rung ladder: ``((cbucket, c_cap or None), ...)``.

    ``c_cap=None`` means the full ``cfg.candidate_cap`` per-bucket clamp
    (exact, bit-identical to the uncompacted query).  Without a
    ``ctot_norm`` this degenerates to the PR-5 single-level ladder.  With
    one, the normal rungs stop at ``ctot_norm`` — the high quantile of
    *realized* per-query candidate totals, not the global-max-bucket worst
    case — and exactly one overflow rung handles hot-bucket queries:

    * ``overflow='escalate'``: the overflow rung is ``(ctot_cap, None)`` —
      exact but expensive; correctness-default.
    * ``overflow='truncate'``: the overflow rung is ``(ctot_norm, c_cap)``
      — hot buckets are prefix-truncated to ``c_cap`` rows each so the
      slab stays at ``ctot_norm``; bounded cost, <=0.5%-recall knob
      (``ServeConfig.cand_overflow``).

    Either way the intermediate pow-2 rungs between ``ctot_norm`` and
    ``ctot_cap`` vanish from the warmup grid.
    """
    ctot_cap = max(1, int(ctot_cap))
    if not ctot_norm or int(ctot_norm) >= ctot_cap:
        return tuple((b, None) for b in candidate_ladder(ctot_cap, floor))
    ctot_norm = max(1, int(ctot_norm))
    rungs = [(b, None) for b in candidate_ladder(ctot_norm, floor)]
    if overflow == "escalate":
        rungs.append((ctot_cap, None))
    elif overflow == "truncate":
        rungs.append((ctot_norm, max(1, int(c_cap)) if c_cap else None))
    else:
        raise ValueError(f"unknown overflow policy: {overflow!r}")
    return tuple(rungs)


def pick_rung(count: int, ctot_cap: int, floor: int = 64,
              ctot_norm: Optional[int] = None,
              c_cap: Optional[int] = None,
              overflow: str = "escalate",
              ) -> Tuple[int, Optional[int], bool]:
    """Pick the ``rung_ladder`` rung for a batch's max candidate count.

    Returns ``(cbucket, c_cap or None, overflowed)``.  This is the one
    host-side decision of the two-phase query: ``count`` is the single
    scalar phase A transfers, and every return value here is a member of
    ``rung_ladder(...)`` with the same arguments — so the warmup grid
    covers every live pick.
    """
    ctot_cap = max(1, int(ctot_cap))
    if not ctot_norm or int(ctot_norm) >= ctot_cap:
        return candidate_bucket(count, ctot_cap, floor), None, False
    ctot_norm = max(1, int(ctot_norm))
    if count <= ctot_norm:
        return candidate_bucket(count, ctot_norm, floor), None, False
    if overflow == "escalate":
        return ctot_cap, None, True
    if overflow == "truncate":
        return ctot_norm, max(1, int(c_cap)) if c_cap else None, True
    raise ValueError(f"unknown overflow policy: {overflow!r}")


# --------------------------------------------------------------------------
# Rerank + merge stages
# --------------------------------------------------------------------------

def stage_rerank(
    cfg, dataset: jax.Array, queries: jax.Array, ids: jax.Array,
    k: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact-rerank stage: gather + L1 + top-k (``kops.fused_rerank``).

    Accepts the raw candidate gather, duplicates included.  Returns the
    ``k`` (default ``cfg.k``) lex-(dist, id)-smallest unique candidates,
    ascending; invalid -> (BIG_DIST, -1).
    """
    k = cfg.k if k is None else k
    if dataset.shape[0] == 0:
        # No rows to rank against; the gather would read a zero-length
        # dataset.  Emit the all-invalid result directly.
        q = ids.shape[0]
        return (jnp.full((q, k), BIG_DIST, jnp.int32),
                jnp.full((q, k), -1, jnp.int32))
    return kops.fused_rerank(
        dataset, queries, ids, k, chunk=cfg.rerank_chunk)


def stage_tombstone(
    dists: jax.Array, gids: jax.Array, tombstones: jax.Array, k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Drop deleted points from a selected list; keep the first k live.

    dists, gids : (Q, K) ascending selection mapped to global ids, K >= k;
                  invalid slots carry (BIG_DIST, -1).
    tombstones  : (t,) ascending int32 global ids, padded with INT32_MAX
                  (the pad matches no real gid, so no count is needed).
    Returns (dists, gids) (Q, k): the live entries in their order, then
    (BIG_DIST, -1).  Exact when the selection holds at least k live
    candidates or all of them, which ``tomb_select`` guarantees.
    """
    pos = jnp.searchsorted(tombstones, gids)
    dead = tombstones[jnp.clip(pos, 0, tombstones.shape[0] - 1)] == gids
    live = (gids >= 0) & ~dead
    # A stable sort on the flag moves the live entries to the front in
    # their order; K is k + T, so this is a few lanes per query.
    order = jnp.argsort(~live, axis=-1, stable=True)[:, :k]

    def first_live(a, invalid):
        return jnp.take_along_axis(jnp.where(live, a, invalid), order,
                                   axis=-1)

    return first_live(dists, BIG_DIST), first_live(gids, -1)


def tomb_select(k: int, t: int, ctot: int) -> int:
    """How many candidates the rerank selects ahead of ``stage_tombstone``.

    k + t, where t is the tombstone array's static length: at most t
    selected candidates are dead, so k live ones remain whenever the slab
    holds them.  Never more than the slab's ``ctot`` slots (all of them
    are then kept), and never fewer than k (the rerank pads).
    """
    return max(k, min(k + t, ctot))


def rerank_candidates(
    cfg, dataset: jax.Array, queries: jax.Array, ids: jax.Array,
    gids: jax.Array, tombstones: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """rerank at k + T -> gid map -> tombstone -> first k (DESIGN.md §3.1).

    The one tail of every query (``index.finish_query``, which the flat,
    segmented and sharded paths share, and ``segments._query_delta``).
    ids (Q, Ctot) local candidate ids, sentinel n, duplicates allowed;
    gids (n,) the global id of each local row.  Returns
    (dists, gids) (Q, k), lex-(dist, local id) ascending, invalid ->
    (BIG_DIST, -1): the same bits as masking dead candidates out of the
    slab before a rerank at k.
    """
    n = dataset.shape[0]
    sel = tomb_select(cfg.k, tombstones.shape[0], ids.shape[1])
    with jax.named_scope("rerank"):
        d, i = stage_rerank(cfg, dataset, queries, ids, k=sel)
    if n == 0:  # zero-point segment: all invalid, gids is empty
        return d[:, :cfg.k], i[:, :cfg.k]
    with jax.named_scope("gid_map"):
        gid = jnp.where(i >= 0, gids[jnp.clip(i, 0, n - 1)], -1)
    with jax.named_scope("tombstone"):
        return stage_tombstone(d, gid, tombstones, cfg.k)


def stage_merge_pair(
    da: jax.Array, ia: jax.Array, db: jax.Array, ib: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two ascending (Q, k) top-k lists into one.

    Invalid entries must carry dist >= BIG_DIST (id -1 or sentinel).  Runs
    ``kops.topk_merge``, the op the distributed ring and tree merges use;
    it tie-breaks on (dist, id), as ``stage_merge_concat`` does, so the two
    return identical ids even on tied distances.
    """
    return kops.topk_merge(da, ia, db, ib)


def stage_merge_concat(
    ds: jax.Array, is_: jax.Array, k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge R stacked top-k lists at once: (Q, R*k) -> (Q, k) ascending.

    The all-gather distributed merge and any >2-way host merge use this.
    Lexicographic on (dist, id) like every other merge/rerank path, so the
    allgather and ring/tree distributed merges agree bit-for-bit on ids
    even when distances tie.
    """
    # Variadic 2-key sort is the slow comparator path on XLA CPU, but R*k
    # rows are tiny (<= ~1k) and ids here are arbitrary gids, which rules
    # out the int32 (dist, position) key packing the rerank uses.
    sd, si = jax.lax.sort((ds, is_), dimension=-1, num_keys=2)
    return sd[:, :k], si[:, :k]
