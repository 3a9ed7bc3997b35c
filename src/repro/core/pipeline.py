"""Staged MP-RW-LSH query pipeline (DESIGN.md Sect. 3).

The query flow is decomposed into pure, statically-shaped stages

    hash -> probe-gen -> bucket-lookup -> candidate-gather -> dedup
         [-> tombstone] -> rerank -> merge

so that the single-shard path (``core.index.query_index``), the shard_map
path (``launch.dist_index``), and the serving engine (``serve.engine``)
compose the *same* functions instead of re-implementing the flow.  Every
stage takes raw arrays (no ``IndexState``), which is what lets the
shard_map body call them on its per-shard slices directly.

Stage contracts (Q queries, L tables, M hashes, P probes/table, C cap):

  stage_hash       : queries (Q, m)            -> bucket, x_neg (Q, L, M)
  stage_probe_keys : bucket, x_neg             -> probe_keys (Q, L, P) uint32
  stage_bucket_lookup : sorted_keys, probe_keys -> lo, hi (Q, L, P)
  stage_candidate_gather : sorted_ids, lo, hi  -> ids (Q, L*P*C), sentinel n
  stage_probe_counts : sorted_keys, probe_keys -> counts (Q,) valid cands
  stage_fused_probe : sorted_keys/ids, probe_keys -> ids (Q, Cb), counts (Q,)
  stage_dedup      : ids                       -> ids, duplicates -> sentinel
  stage_tombstone  : ids, gids, tombstones     -> ids, deleted -> sentinel
  stage_rerank     : dataset, queries, ids     -> (dists, ids) (Q, k) asc
  stage_merge_pair : two (Q, k) ascending lists -> one (Q, k) ascending list
  stage_merge_concat : (Q, R*k) stacked lists  -> (Q, k)

Probe dispatch (DESIGN.md §8): ``cfg.probe_impl`` selects between the fused
lookup+gather kernel (``kernels/fused_probe``, the default) and the legacy
staged ``stage_bucket_lookup`` + ``stage_candidate_gather`` pair.  The fused
path packs valid candidates to the front of the slab and can emit a
**compacted** ``(Q, cbucket)`` slab when the caller passes a static
``cbucket`` (picked from ``stage_probe_counts`` via ``candidate_bucket`` —
the same pow-2 shape-bucket discipline the serving engine uses for batch
sizes).  The rerank contract is order/width-invariant over the candidate
*set*, so every choice yields bit-identical final (dists, ids).

Rerank dispatch (DESIGN.md §Perf): ``cfg.rerank_impl`` selects between the
fused gather+L1+running-top-k kernel (``kernels/fused_rerank``, the default)
and the legacy chunked scan + ``lax.top_k`` (``l1_distance_chunked``).  The
fused kernel suppresses duplicate candidate ids itself via id-keyed masking,
so ``probe_candidates`` skips the sorting dedup stage entirely on that path
(sort-free dedup).  Both paths produce bit-identical results — the k
lexicographically-(dist, id)-smallest unique candidates — which is also
exactly what the pre-refactor monolithic ``query_index`` computed
(tests/test_segments.py proves it against a frozen copy of the seed
implementation; tests/test_fused_rerank.py pins the kernel executors).
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
    "stage_bucket_lookup",
    "stage_candidate_gather",
    "stage_probe_counts",
    "stage_fused_probe",
    "stage_dedup",
    "stage_tombstone",
    "probe_candidates",
    "rerank_handles_duplicates",
    "stage_rerank",
    "stage_merge_pair",
    "stage_merge_concat",
    "l1_distance_chunked",
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


def stage_bucket_lookup(sorted_keys: jax.Array, probe_keys: jax.Array):
    """searchsorted per table.  Returns (lo, hi) (Q, L, P) bucket extents."""

    def per_table(sk, pk):  # sk (n,), pk (Q, P)
        lo = jnp.searchsorted(sk, pk, side="left")
        hi = jnp.searchsorted(sk, pk, side="right")
        return lo, hi

    return jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
        sorted_keys, probe_keys)


def stage_candidate_gather(
    cfg, sorted_ids: jax.Array, lo: jax.Array, hi: jax.Array, n: int,
) -> jax.Array:
    """Gather up to candidate_cap row ids per probed bucket.

    Returns (Q, L*P*C) int32 local ids with sentinel n for empty slots.
    """
    q = lo.shape[0]
    l, p, c = cfg.num_tables, cfg.probes_per_table, cfg.candidate_cap
    if n == 0:
        # Zero-point shard (e.g. a segment compacted down to nothing, or an
        # empty seed): clip(slots, 0, n-1) is ill-formed and the id gather
        # would read a zero-length array.  Every slot is invalid, and the
        # sentinel for n=0 is 0 itself.
        return jnp.zeros((q, l * p * c), jnp.int32)
    slots = lo[..., None] + jnp.arange(c, dtype=lo.dtype)       # (Q,L,P,C)
    valid = slots < jnp.minimum(hi, lo + c)[..., None]
    slots = jnp.clip(slots, 0, n - 1)

    def gather_ids(sid, sl):  # sid (n,), sl (Q, P, C)
        return sid[sl]

    ids = jax.vmap(gather_ids, in_axes=(0, 1), out_axes=1)(
        sorted_ids, slots)                                      # (Q,L,P,C)
    return jnp.where(valid, ids, n).reshape(q, l * p * c)


def stage_probe_extents(cfg, sorted_keys: jax.Array, probe_keys: jax.Array,
                        occ_from=None):
    """Raw bucket extents + per-query candidate counts — the fused
    front-end's phase A.

    Returns (lo (Q, L*P) int32, occ (Q, L*P) int32 — *unclamped* per-bucket
    occupancies — and counts (Q,) int32 totals under ``cfg.candidate_cap``).
    The two-phase serving path runs this as its own jitted phase, pulls
    ``counts.max()`` to the host, picks a rung (``pick_rung``), and hands
    (lo, occ) back to ``stage_fused_probe`` so the gather phase neither
    re-searches nor re-scans.  The counts are exactly what the fused probe
    kernel reports, so a bucket >= the max count can never truncate; the
    raw occupancies let the overflow rung apply a tighter per-bucket cap
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
    """Fused bucket-lookup + compacted candidate gather (DESIGN.md §8).

    Returns (ids (Q, cbucket) int32 sentinel n — valid candidates packed to
    the front in (table, probe, offset) order, counts (Q,) int32).
    ``cbucket`` defaults to the full worst-case ``L*P*C`` width (still
    fused — the (Q, L, P, C) slab never exists — just not compacted); a
    caller-picked static ``cbucket`` shrinks the slab the rerank pays for.
    ``cbucket`` must cover the actual counts or the tail candidates are
    dropped (callers derive it from ``stage_probe_extents``, whose (lo,
    occ) pair can be passed back here as ``extents`` to skip the re-search
    on XLA backends).  ``c_cap`` tightens the per-bucket cap below
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


def rerank_handles_duplicates(cfg) -> bool:
    """True when ``stage_rerank``'s implementation suppresses duplicates.

    The fused rerank kernel dedups via id-keyed masking (DESIGN.md §Perf),
    so the pipeline's sorting ``stage_dedup`` becomes redundant work and
    ``probe_candidates`` skips it (the sort-free dedup path).  Only the
    legacy ``scan`` impl still needs the pre-sort.
    """
    return getattr(cfg, "rerank_impl", "fused") != "scan"


def stage_dedup(ids: jax.Array, n: int) -> jax.Array:
    """Sort ascending; equal-adjacent -> sentinel n.

    Guarantees no candidate is reranked twice even when it falls in several
    tables/probes (sentinel slots sort to the tail and stay sentinel).
    Skipped when the fused rerank kernel dedups internally — see
    ``rerank_handles_duplicates``.
    """
    q = ids.shape[0]
    ids = jnp.sort(ids, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), ids[:, 1:] == ids[:, :-1]], axis=-1)
    return jnp.where(dup, n, ids)


def stage_tombstone(
    ids: jax.Array, gids: jax.Array, tombstones: jax.Array, n: int,
) -> jax.Array:
    """Mask deleted points out of the candidate list (DESIGN.md Sect. 3).

    ids        : (Q, Ctot) local ids with sentinel n.
    gids       : (n,) global id of each local row.
    tombstones : (t,) ascending int32 global ids, padded with INT32_MAX
                 (the pad value matches no real gid, so no count is needed).
    Applied *before* rerank so a deleted point can never occupy a top-k slot.
    """
    if n == 0:
        return ids  # nothing to map: every slot already carries the sentinel
    gid = gids[jnp.clip(ids, 0, n - 1)]
    pos = jnp.searchsorted(tombstones, gid)
    hit = tombstones[jnp.clip(pos, 0, tombstones.shape[0] - 1)] == gid
    return jnp.where((ids < n) & hit, n, ids)


def probe_candidates(
    cfg, params: hashes_lib.LshParams, template: jax.Array,
    sorted_keys: jax.Array, sorted_ids: jax.Array, n: int,
    queries: jax.Array, dedup: Optional[bool] = None,
    cbucket: Optional[int] = None, c_cap: Optional[int] = None,
) -> jax.Array:
    """hash -> probe-gen -> lookup+gather [-> dedup], composed.

    Returns candidate local ids, sentinel n.  The lookup+gather runs per
    ``cfg.probe_impl``: 'fused' (default) uses the fused front-end kernel
    (valid candidates packed first; slab width ``cbucket`` when given, else
    the worst-case L*P*C; per-bucket cap tightened to ``c_cap`` when given
    — the two-level truncate rung), 'staged' the legacy two-stage pair at
    fixed L*P*C width (``cbucket``/``c_cap`` unsupported there).  ``dedup``
    defaults to cfg-driven: the sorting dedup only runs when the configured
    rerank impl does not dedup internally (``rerank_handles_duplicates``);
    the fused rerank consumes the raw gather and masks duplicates
    in-kernel.
    """
    bucket, x_neg = stage_hash(cfg, params, queries)
    probe_keys = stage_probe_keys(cfg, params, template, bucket, x_neg)
    impl = getattr(cfg, "probe_impl", "fused")
    if impl == "fused":
        ids, _ = stage_fused_probe(
            cfg, sorted_keys, sorted_ids, probe_keys, n, cbucket,
            c_cap=c_cap)
    elif impl == "staged":
        if cbucket is not None or c_cap is not None:
            raise ValueError("slab compaction requires probe_impl='fused'")
        lo, hi = stage_bucket_lookup(sorted_keys, probe_keys)
        ids = stage_candidate_gather(cfg, sorted_ids, lo, hi, n)
    else:
        raise ValueError(f"unknown probe_impl: {impl!r}")
    if dedup is None:
        dedup = not rerank_handles_duplicates(cfg)
    return stage_dedup(ids, n) if dedup else ids


# --------------------------------------------------------------------------
# Rerank + merge stages
# --------------------------------------------------------------------------

def l1_distance_chunked(
    dataset: jax.Array, queries: jax.Array, ids: jax.Array, k: int,
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Legacy exact L1 rerank: chunked scan with a ``lax.top_k`` running best.

    dataset (n, m) int; queries (Q, m) int; ids (Q, Ctot) int32 with sentinel
    n marking invalid, **deduplicated** (duplicates would each take a top-k
    slot here — feed it ``stage_dedup`` output).  Returns (dists (Q,k) int32,
    ids (Q,k) int32) sorted ascending; invalid entries have dist =
    INT32_MAX/2 and id = -1.

    Kept as the `scan` rerank impl and as the benchmark baseline; the fused
    kernel path (DESIGN.md §Perf) avoids this function's per-chunk HBM
    round-trips and repeated top_k.
    """
    n = dataset.shape[0]
    q, ctot = ids.shape
    big = jnp.int32(BIG_DIST)
    pad = (-ctot) % chunk
    if pad:
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=n)
    steps = ids.shape[1] // chunk
    ids_steps = ids.reshape(q, steps, chunk).transpose(1, 0, 2)     # (S,Q,c)

    def body(carry, step_ids):
        best_d, best_i = carry                                      # (Q,k)
        sl = jnp.clip(step_ids, 0, n - 1)                           # (Q,c)
        rows = dataset[sl]                                          # (Q,c,m)
        # HBM gather stays at dataset dtype (int16 under §Perf C1);
        # the |diff| accumulation is widened to int32 in registers.
        diff = rows.astype(jnp.int32) - queries[:, None, :].astype(jnp.int32)
        d = jnp.abs(diff).sum(axis=-1).astype(jnp.int32)
        d = jnp.where(step_ids >= n, big, d)
        cd = jnp.concatenate([best_d, d], axis=-1)
        ci = jnp.concatenate([best_i, step_ids], axis=-1)
        nd, sel = jax.lax.top_k(-cd, k)
        return (-nd, jnp.take_along_axis(ci, sel, axis=-1)), None

    init = (jnp.full((q, k), big, jnp.int32), jnp.full((q, k), n, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(body, init, ids_steps)
    best_i = jnp.where(best_d >= big, -1, best_i)
    return best_d, best_i


def stage_rerank(
    cfg, dataset: jax.Array, queries: jax.Array, ids: jax.Array,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact-rerank stage; dispatches on ``cfg.rerank_impl``.

    'fused' (default): the fused gather+L1+running-top-k kernel — dedups
    internally, so it accepts the raw (non-deduplicated) candidate gather.
    'scan': the legacy chunked scan + lax.top_k — requires deduplicated ids.
    Both return identical bits (the k lex-(dist, id)-smallest unique
    candidates, ascending; invalid -> (BIG_DIST, -1)).
    """
    impl = impl or getattr(cfg, "rerank_impl", "fused")
    if dataset.shape[0] == 0:
        # No rows to rank against; both executors would gather from a
        # zero-length dataset.  Emit the all-invalid result directly.
        q = ids.shape[0]
        return (jnp.full((q, cfg.k), BIG_DIST, jnp.int32),
                jnp.full((q, cfg.k), -1, jnp.int32))
    if impl == "scan":
        return l1_distance_chunked(
            dataset, queries, ids, cfg.k, cfg.rerank_chunk)
    if impl != "fused":
        raise ValueError(f"unknown rerank_impl: {impl!r}")
    return kops.fused_rerank(
        dataset, queries, ids, cfg.k, chunk=cfg.rerank_chunk)


def stage_merge_pair(
    da: jax.Array, ia: jax.Array, db: jax.Array, ib: jax.Array,
    use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two ascending (Q, k) top-k lists into one.

    Invalid entries must carry dist >= BIG_DIST (id -1 or sentinel).  With
    ``use_kernel`` the merge runs ``kops.topk_merge`` (the same op the
    distributed ring merge uses), whose executor is chosen per backend
    (``kops.executors``: the bitonic Pallas kernel off TPU, the
    lexicographic sort merge on TPU); without it, the lexicographic concat
    sort.  All of them tie-break on (dist, id), so they return identical
    ids even on tied distances.
    """
    if use_kernel:
        return kops.topk_merge(da, ia, db, ib)
    return stage_merge_concat(jnp.concatenate([da, db], axis=-1),
                              jnp.concatenate([ia, ib], axis=-1),
                              da.shape[-1])


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
    # out the int32 (dist, position) key packing fused_rerank_xla uses.
    sd, si = jax.lax.sort((ds, is_), dimension=-1, num_keys=2)
    return sd[:, :k], si[:, :k]
