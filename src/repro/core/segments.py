"""Segmented mutable MP-RW-LSH index (DESIGN.md Sect. 3).

The paper builds once and queries forever; a serving system needs inserts
and deletes without an O(n log n) rebuild per mutation.  LSM-style layout:

  * an ordered list of immutable sorted **segments** — each is a plain
    ``IndexState`` (one sort per table) over its own point set, plus a
    ``gids`` vector mapping local rows to stable global ids;
  * a small mutable **delta buffer** of freshly inserted points.  It is
    unindexed; queries scan it with the exact L1 rerank stage (it is tiny
    by construction, so the scan is cheaper than hashing it per mutation);
  * a **tombstone set** of deleted global ids, checked on the k + T
    candidates each query's rerank selects (``pipeline.rerank_candidates``,
    T the tombstone array's length) so a dead point can never occupy a
    top-k slot;
  * ``compact()`` merges segments + delta - tombstones back into ONE
    segment, after which a query is bit-identical (in distances) to a fresh
    ``build_index`` over the surviving points in insertion order.

All query work is statically shaped and jit-compiled: the delta buffer has
a fixed capacity (padded; a row count masks the tail), tombstones live in a
power-of-two device array padded with INT32_MAX (the pad matches no real
gid, so no count is carried), and the per-segment top-k lists are folded
with the same ``topk_merge`` op the distributed ring merge uses — the
single-host path exercises the distributed merge machinery.

Every segment shares one ``LshParams`` (the paper's fixed cost, Sect. 3.2):
a point hashes to the same buckets whichever segment holds it, which is
what makes per-segment top-k lists mergeable.  ``hashes.params_fingerprint``
guards this invariant.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hashes as hashes_lib
from . import pipeline as pipe
from .index import (IndexConfig, IndexState, build_index, finish_query,
                    make_params, make_template, probe_index)
from repro.kernels.rows import widen_rows
from repro.obs import trace as obs_trace

__all__ = ["Segment", "SegmentedIndex"]

_INT32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class Segment:
    """One immutable sorted segment: an IndexState plus stable global ids."""

    state: IndexState                 # built with row_offset = 0
    gids: jax.Array                   # (n,) int32 global row ids
    fingerprint: int                  # hashes.params_fingerprint(state.params)
    ctot_cap: int = 0                 # worst-case valid candidates per query:
                                      # L*P*min(cap, max bucket occupancy);
                                      # 0 = not yet derived (see _seg_ctot_cap)
    ctot_norm: int = 0                # normal-rung ladder top: pow-2 headroom
                                      # over the sampled high quantile of
                                      # realized per-query candidate totals
                                      # (DESIGN.md §9); 0 = not yet derived
                                      # (SegmentedIndex._ensure_caps, lazy)
    c_norm: int = 0                   # per-bucket cap of the truncate
                                      # overflow rung (occupancy-histogram
                                      # quantile); 0 = not yet derived
    occ_stats: Optional[dict] = None  # cached skew_summary quantiles; the
                                      # histogram/keys are immutable once
                                      # sealed, so one host read per segment
                                      # lifetime instead of one per poll

    @property
    def size(self) -> int:
        return int(self.gids.shape[0])


def _seg_ctot_cap(cfg: IndexConfig, state: IndexState) -> int:
    """Ladder top for candidate compaction over this segment (DESIGN.md §8).

    Uses the same occupancy derivation as the quality oracle's
    union-exactness cap (``pipe.max_bucket_occupancy``), so the compaction
    bound and the oracle cap cannot drift.  One host read of the sorted
    keys per segment seal — amortized over every query the segment serves.
    """
    occ = pipe.max_bucket_occupancy(  # repro: allow[r1-host-sync] seal-time cap derivation, once per segment seal
        state.sorted_keys, state.occ_from)
    return (cfg.num_tables * cfg.probes_per_table
            * min(cfg.candidate_cap, occ))


# Phase A over one segment is the flat index's phase A (a segment IS an
# IndexState), so the flat and segmented paths cannot drift.
_probe_segment = probe_index


@partial(jax.jit, static_argnums=(2, 3))
def _truncated_total(occ: jax.Array, counts: jax.Array, c_cap: int,
                     cbucket: int):
    """Candidates dropped by the truncate rung vs the full-cap gather.

    ``counts`` are phase A's totals under the full cap; the rung gathers
    ``min(sum min(occ, c_cap), cbucket)`` per query.  Observability only
    (engine stats) — runs solely on the rare overflow path.
    """
    got = jnp.minimum(jnp.minimum(occ, c_cap).sum(axis=-1), cbucket)
    return (counts - got).sum()


@partial(jax.jit, static_argnums=(0, 1, 2))
def _finish_segment(cfg: IndexConfig, cbucket: int, c_cap: Optional[int],
                    state: IndexState, gids: jax.Array, tombstones: jax.Array,
                    probe_keys: jax.Array, lo: jax.Array, occ: jax.Array,
                    queries: jax.Array):
    """Phase B over one segment (``index.finish_query``): compacted gather
    at the (static) rung -> rerank at k + T -> gid map -> tombstone ->
    first k.  The tombstones are checked on the k + T selected candidates,
    not on every slot of the rung (``pipe.rerank_candidates``).  Results
    are those of the full slab at any non-truncating ``cbucket`` with
    ``c_cap=None``; an int ``c_cap`` is the two-level truncate rung's
    tighter per-bucket cap (deterministic sorted-prefix truncation,
    DESIGN.md §9).
    """
    return finish_query(cfg, cbucket, c_cap, state, gids, tombstones,
                        probe_keys, lo, occ, queries)


@partial(jax.jit, static_argnums=0)
def _query_delta(cfg: IndexConfig, buffer: jax.Array, gids: jax.Array,
                 count: jax.Array, tombstones: jax.Array, queries: jax.Array):
    """Exact scan of the delta buffer via the rerank stage (no hashing)."""
    cap = buffer.shape[0]
    ids = jnp.broadcast_to(
        jnp.where(jnp.arange(cap, dtype=jnp.int32) < count,
                  jnp.arange(cap, dtype=jnp.int32), cap),
        (queries.shape[0], cap))
    return pipe.rerank_candidates(cfg, buffer, queries, ids, gids,
                                  tombstones)


class SegmentedIndex:
    """Mutable index = immutable segments + delta buffer + tombstones.

    Host-side orchestrator; all heavy work happens in jitted pipeline
    stages.  Not thread-safe: the serving engine serializes mutations and
    compactions against queries.
    """

    def __init__(self, cfg: IndexConfig, key: jax.Array, dim: int,
                 delta_cap: int = 1024,
                 params: Optional[hashes_lib.LshParams] = None,
                 cap_quantile: float = 0.999, cap_sample: int = 32):
        if params is None:
            params = make_params(cfg, key, dim)
        self.cfg = cfg
        self.dim = dim
        self.delta_cap = int(delta_cap)
        # two-level compaction policy (DESIGN.md §9): occupancy-histogram
        # quantile for the per-bucket cap, and how many of the segment's
        # own rows to probe as surrogate queries when sizing the normal
        # ladder top from realized candidate totals.  quantile >= 1
        # disables the second level (single-level PR-5 ladder).
        self.cap_quantile = float(cap_quantile)
        self.cap_sample = int(cap_sample)
        self.params = params
        self.fingerprint = hashes_lib.params_fingerprint(params)
        # cfg-only-dependent; computed once, reused by every seal/compact
        self._template = jnp.asarray(make_template(cfg))
        self.segments: List[Segment] = []
        self._delta_points = np.zeros((self.delta_cap, dim), np.int32)
        self._delta_gids = np.full((self.delta_cap,), -1, np.int32)
        self._delta_count = 0
        self._tombstones: set = set()
        self._next_gid = 0
        self.compactions = 0
        # device-side snapshots of the mutable state, rebuilt lazily after a
        # mutation so steady-state queries pay no host copies / transfers
        self._delta_cache: Optional[Tuple[jax.Array, jax.Array]] = None
        self._tomb_cache: Optional[jax.Array] = None

    @classmethod
    def from_dataset(cls, cfg: IndexConfig, key: jax.Array,
                     dataset: jax.Array, delta_cap: int = 1024,
                     params: Optional[hashes_lib.LshParams] = None,
                     cap_quantile: float = 0.999, cap_sample: int = 32,
                     ) -> "SegmentedIndex":
        """Seed with one segment holding ``dataset`` (gids 0..n-1).

        Bulk path: one build_index over the whole dataset, no delta churn.
        """
        dataset = jnp.asarray(dataset)
        n, dim = dataset.shape
        idx = cls(cfg, key, int(dim), delta_cap, params,
                  cap_quantile=cap_quantile, cap_sample=cap_sample)
        state = build_index(cfg, key, dataset, params=idx.params,
                            template=idx._template)
        idx.segments = [Segment(state=state,
                                gids=jnp.arange(n, dtype=jnp.int32),
                                fingerprint=idx.fingerprint,
                                ctot_cap=_seg_ctot_cap(cfg, state))]
        idx._next_gid = int(n)
        return idx

    @classmethod
    def from_checkpoint(cls, cfg: IndexConfig, state: IndexState,
                        gids: jax.Array, next_gid,
                        delta_cap: int = 1024,
                        cap_quantile: float = 0.999,
                        cap_sample: int = 32) -> "SegmentedIndex":
        """Rebuild a serving index from a ``checkpoint_payload()`` triple.

        ``next_gid`` must come from the payload — recomputing it as
        ``max(gids) + 1`` would re-issue the ids of points deleted and
        compacted away before the checkpoint, breaking gid stability for
        clients that still hold them.
        """
        gids = jnp.asarray(gids, jnp.int32)
        idx = cls(cfg, jax.random.PRNGKey(0), int(state.dataset.shape[1]),
                  delta_cap, params=state.params,
                  cap_quantile=cap_quantile, cap_sample=cap_sample)
        idx.segments = [Segment(state=state, gids=gids,
                                fingerprint=idx.fingerprint,
                                ctot_cap=_seg_ctot_cap(cfg, state))]
        idx._next_gid = int(next_gid)
        return idx

    def checkpoint_payload(self) -> Tuple[IndexState, jax.Array, jax.Array]:
        """Durable shard payload: ``(IndexState, gids, next_gid)``.

        Compacts first when the index carries uncheckpointable mutations
        (extra segments, delta inserts, tombstones), so the payload always
        reflects every acknowledged insert/delete.  Restore with
        ``SegmentedIndex.from_checkpoint``.
        """
        if (self.num_segments != 1 or self._delta_count
                or self._tombstones):
            self.compact()
        if not self.segments:
            raise RuntimeError("empty index; nothing to checkpoint")
        seg = self.segments[0]
        return seg.state, seg.gids, jnp.int32(self._next_gid)

    # -- introspection ----------------------------------------------------

    @property
    def num_segments(self) -> int:
        return len(self.segments)

    @property
    def delta_fill(self) -> float:
        return self._delta_count / self.delta_cap

    @property
    def num_live(self) -> int:
        total = sum(s.size for s in self.segments) + self._delta_count
        return total - len(self._tombstones)

    def device_bytes(self) -> int:
        """Device bytes of the sealed segments' rows and tables: stored
        rows, sorted keys and ids, run lengths and gids (the delta buffer
        and the tombstones are host state until a query copies them)."""
        return sum(int(a.nbytes) for seg in self.segments
                   for a in (seg.state.dataset, seg.state.sorted_keys,
                             seg.state.sorted_ids, seg.state.occ_from,
                             seg.gids)
                   if a is not None)

    @property
    def num_tombstones(self) -> int:
        return len(self._tombstones)

    @property
    def next_gid(self) -> int:
        """The gid the next insert will receive (durable in checkpoints)."""
        return self._next_gid

    # -- mutations --------------------------------------------------------

    def insert(self, points) -> np.ndarray:
        """Append points to the delta buffer; returns their global ids.

        A full delta buffer is sealed into an immutable segment (one sort
        per table over delta_cap points — the LSM 'minor compaction').
        """
        pts = np.atleast_2d(np.asarray(points, np.int32))
        if pts.shape[1] != self.dim:
            raise ValueError(f"expected dim {self.dim}, got {pts.shape[1]}")
        gids = np.arange(self._next_gid, self._next_gid + pts.shape[0],
                         dtype=np.int32)
        self._next_gid += pts.shape[0]
        pos = 0
        while pos < pts.shape[0]:
            if self._delta_count == self.delta_cap:
                self._seal_delta()
            take = min(self.delta_cap - self._delta_count, pts.shape[0] - pos)
            lo = self._delta_count
            self._delta_points[lo:lo + take] = pts[pos:pos + take]
            self._delta_gids[lo:lo + take] = gids[pos:pos + take]
            self._delta_count += take
            pos += take
        self._delta_cache = None
        return gids

    def delete(self, gids) -> int:
        """Tombstone global ids; returns how many were newly tombstoned.

        Unknown / already-deleted ids are ignored (idempotent), so replayed
        delete requests are safe.  Caveat: a gid already removed by an
        earlier compaction is indistinguishable from a live one here, so
        re-deleting it costs one tombstone slot and skews the advisory
        ``num_live`` until the next compaction (query results unaffected).
        """
        before = len(self._tombstones)
        for g in np.atleast_1d(np.asarray(gids, np.int64)):
            if 0 <= g < self._next_gid:
                self._tombstones.add(int(g))
        if len(self._tombstones) != before:
            self._tomb_cache = None
        return len(self._tombstones) - before

    def _seal_delta(self) -> None:
        """Delta buffer -> immutable segment (shared params, row_offset 0)."""
        n = self._delta_count
        if n == 0:
            return
        # .copy() is load-bearing: jnp.asarray of a numpy buffer can be
        # zero-copy on CPU, and the delta buffer is reused right after.
        state = build_index(
            self.cfg, jax.random.PRNGKey(0),
            jnp.asarray(self._delta_points[:n].copy()), params=self.params,
            template=self._template)
        self.segments.append(Segment(
            state=state, gids=jnp.asarray(self._delta_gids[:n].copy()),
            fingerprint=self.fingerprint,
            ctot_cap=_seg_ctot_cap(self.cfg, state)))
        self._delta_count = 0
        self._delta_gids[:] = -1
        self._delta_cache = None

    def compact(self) -> None:
        """Major compaction: segments + delta - tombstones -> one segment.

        Surviving points keep insertion order and their global ids, so a
        post-compaction query returns the same distances as a fresh
        ``build_index`` over the surviving points (tests prove this).
        """
        parts, gid_parts = [], []
        for seg in self.segments:
            if seg.fingerprint != self.fingerprint:
                raise ValueError("segment params diverged; cannot compact")
            parts.append(np.asarray(widen_rows(seg.state.dataset)))  # repro: allow[r1-host-sync] compaction materializes on host by design
            gid_parts.append(np.asarray(seg.gids))  # repro: allow[r1-host-sync] compaction materializes on host by design
        if self._delta_count:
            parts.append(self._delta_points[:self._delta_count].copy())
            gid_parts.append(self._delta_gids[:self._delta_count].copy())
        if not parts:
            return
        data = np.concatenate(parts)
        gids = np.concatenate(gid_parts)
        # insertion order + drop tombstoned rows
        order = np.argsort(gids, kind="stable")
        data, gids = data[order], gids[order]
        if self._tombstones:
            dead = np.asarray(sorted(self._tombstones), np.int32)
            live = ~np.isin(gids, dead)
            data, gids = data[live], gids[live]
        self.segments = []
        self._delta_count = 0
        self._delta_gids[:] = -1
        self._tombstones = set()
        self._delta_cache = None
        self._tomb_cache = None
        self.compactions += 1
        if data.shape[0] == 0:
            return
        state = build_index(self.cfg, jax.random.PRNGKey(0),
                            jnp.asarray(data), params=self.params,
                            template=self._template)
        self.segments = [Segment(state=state, gids=jnp.asarray(gids),
                                 fingerprint=self.fingerprint,
                                 ctot_cap=_seg_ctot_cap(self.cfg, state))]

    # -- query ------------------------------------------------------------

    def structure_signature(self) -> tuple:
        """Shapes the jitted query path specializes on, besides the batch.

        (per-segment sizes, delta-scan active, tombstone-array capacity) —
        the serving engine keys its compiled-executable bookkeeping on this
        (DESIGN.md §Perf).  Owned here so the tombstone pow2 padding policy
        (``_tombstone_array``) and the delta-scan condition
        (``query_compact``) stay in one module.
        """
        tomb = len(self._tombstones)
        tomb_cap = 1 << (tomb - 1).bit_length() if tomb else 1
        return (tuple(s.size for s in self.segments),
                self._delta_count > 0
                or not any(s.size for s in self.segments), tomb_cap)

    def _tombstone_array(self) -> jax.Array:
        """Ascending device array padded to a power of two with INT32_MAX.

        Cached between mutations — steady-state queries reuse the device
        array instead of re-sorting and re-uploading the set every call.
        """
        if self._tomb_cache is None:
            dead = sorted(self._tombstones)
            cap = 1 << (len(dead) - 1).bit_length() if dead else 1
            out = np.full((cap,), _INT32_MAX, np.int32)
            out[:len(dead)] = dead
            self._tomb_cache = jnp.asarray(out)
        return self._tomb_cache

    def _delta_arrays(self) -> Tuple[jax.Array, jax.Array]:
        """Device snapshot of the delta buffer, cached between mutations.

        The .copy() is load-bearing (zero-copy jnp.asarray would alias the
        live buffer); caching makes it once per mutation epoch, not per
        query.
        """
        if self._delta_cache is None:
            self._delta_cache = (jnp.asarray(self._delta_points.copy()),
                                 jnp.asarray(self._delta_gids.copy()))
        return self._delta_cache

    # -- rung caps (DESIGN.md §8, two-level §9) ---------------------------

    def _ensure_caps(self, seg: Segment) -> None:
        """Derive the segment's two-level caps (lazy; once per seal).

        ``c_norm`` comes from the build-time occupancy histogram
        (``pipe.occupancy_quantile`` at ``cap_quantile``) — the per-bucket
        cap that leaves all but the hot tail of buckets untouched.
        ``ctot_norm`` — the normal-rung ladder top — comes from *realized*
        per-query candidate totals: ``cap_sample`` of the segment's own
        rows are probed as surrogate queries and the p90 of their totals
        **under the c_norm cap** gets 2x pow-2 headroom.  Both clamps are
        load-bearing: the per-bucket cap tames *depth* (a probe landing in
        a hot bucket contributes at most ``c_norm``, however deep it is),
        the p90 tames *breadth* (a surrogate from a dense cluster touches
        many occupied buckets the cap can't shrink) — either outlier alone
        would drag ``ctot_norm`` right back to the worst case, which is
        the exact failure this PR removes.  Queries past the p90 land on
        the overflow rung, which is that rung's whole job.
        Derivation is lazy (first query / warmup), so an index pays for it
        only once it serves.
        """
        if seg.ctot_norm or seg.size == 0:
            return
        cfg = self.cfg
        state = seg.state
        if not seg.ctot_cap:
            seg.ctot_cap = _seg_ctot_cap(cfg, state)
        lp = cfg.num_tables * cfg.probes_per_table
        c_full = max(1, seg.ctot_cap // lp)
        if state.occ_hist is None or self.cap_quantile >= 1.0:
            # legacy state (no histogram) or policy disabled: single-level
            seg.ctot_norm, seg.c_norm = seg.ctot_cap, c_full
            return
        c_norm = max(1, min(c_full, pipe.occupancy_quantile(  # repro: allow[r1-host-sync] seal-time cap derivation, once per segment
            state.occ_hist, self.cap_quantile)))
        ctot_norm = lp * c_norm
        s = min(self.cap_sample, seg.size)
        if s > 0:
            stride = max(1, seg.size // s)
            sample = widen_rows(state.dataset[::stride][:s])
            _, _, occ, _ = _probe_segment(cfg, state, sample)
            totals = np.minimum(np.asarray(occ), c_norm).sum(axis=-1)  # repro: allow[r1-host-sync] seal-time occupancy sampling, once per segment
            realized = int(np.percentile(totals, 90))
            ctot_norm = min(ctot_norm,
                            1 << max(0, 2 * realized - 1).bit_length())
        seg.ctot_norm = max(1, min(ctot_norm, seg.ctot_cap))
        seg.c_norm = c_norm

    def skew_summary(self):
        """Per-segment occupancy/cap snapshot for serving metrics.

        One dict per segment: size, the derived caps (None until
        ``_ensure_caps`` ran), and bucket-occupancy quantiles off the
        build-time histogram — the signals that make a skew regression
        visible in ``engine.summary()`` before it costs latency.
        """
        out = []
        for seg in self.segments:
            entry = {
                "size": seg.size,
                "ctot_cap": seg.ctot_cap or None,
                "ctot_norm": seg.ctot_norm or None,
                "c_norm": seg.c_norm or None,
            }
            hist = seg.state.occ_hist
            if hist is not None and seg.size:
                if seg.occ_stats is None:
                    # One host read per segment lifetime: the histogram and
                    # sorted keys are immutable once sealed, so telemetry
                    # polls reuse the cached dict instead of forcing four
                    # device transfers per segment per poll.
                    seg.occ_stats = {
                        "p50": pipe.occupancy_quantile(hist, 0.5),  # repro: allow[r1-host-sync] cache fill, once per sealed segment
                        "p99": pipe.occupancy_quantile(hist, 0.99),  # repro: allow[r1-host-sync] cache fill, once per sealed segment
                        "p999": pipe.occupancy_quantile(hist, 0.999),  # repro: allow[r1-host-sync] cache fill, once per sealed segment
                        "max": pipe.max_bucket_occupancy(  # repro: allow[r1-host-sync] cache fill, once per sealed segment
                            seg.state.sorted_keys, seg.state.occ_from),
                    }
                entry["occ_quantiles"] = dict(seg.occ_stats)
            out.append(entry)
        return out

    def candidate_ladders(self, floor: int = 64, overflow: str = "escalate"):
        """Per-segment rung ladders, aligned with ``segments``.

        Each ladder is a tuple of ``(cbucket, c_cap or None)`` rungs
        (``pipe.rung_ladder``): pow-2 normal rungs up to the segment's
        ``ctot_norm`` plus one overflow rung per ``overflow`` policy.
        Zero-point segments have no candidates and get an empty ladder.
        The engine pre-compiles phase B at every rung (warmup's
        (batch-bucket x rung) grid) — two-level shrinks this grid, since
        the pow-2 rungs between ``ctot_norm`` and the worst-case
        ``ctot_cap`` no longer exist.
        """
        ladders = []
        for seg in self.segments:
            if not seg.size:
                ladders.append(())
                continue
            self._ensure_caps(seg)
            ladders.append(pipe.rung_ladder(
                seg.ctot_cap, floor, seg.ctot_norm, seg.c_norm, overflow))
        return tuple(ladders)

    # -- query ------------------------------------------------------------

    def query_compact(self, queries: jax.Array, floor: int = 64,
                      overflow: str = "escalate", stats=None):
        """Probe every segment, scan the delta, fold the per-source top-k.

        Per segment: one jitted phase A (probe keys + extents + counts),
        one scalar host read to pick the rung (``pipe.pick_rung`` —
        two-level, DESIGN.md §9), then the jitted phase B at that (static)
        rung — small/sparse segments stop paying the worst-case ``L*P*C``
        slab, and hot-bucket batches stop dragging everyone to the
        worst-case rung.  The normal and ``overflow='escalate'`` rungs give
        the bits of the full slab (the oracle pins it); ``overflow=
        'truncate'`` bounds the overflow rung by per-bucket prefix
        truncation instead.  A segment with no rows has no candidates and
        is skipped; with no rows anywhere the delta scan answers all-invalid.
        Each source contributes its own candidate_cap per probed bucket, so
        a fragmented index examines a superset of the compacted index's
        candidates — distances can only improve until compaction.

        Returns (dists (Q, k) int32 ascending, gids (Q, k) int32, -1 pad,
        used) where ``used`` is a tuple of (segment_size, cbucket, c_cap or
        None) triples — the shapes this call specialized on, for the
        engine's honest cold-hit tracking.  ``stats``, when a dict,
        accumulates ``overflow_hits`` and (truncate only)
        ``truncated_candidates``.
        """
        queries = jnp.asarray(queries)
        tomb = self._tombstone_array()
        results, used = [], []
        for seg in self.segments:
            if seg.size == 0:
                continue
            self._ensure_caps(seg)
            with obs_trace.span("phase_a", segment=int(seg.size)):
                probe_keys, lo, occ, counts = _probe_segment(
                    self.cfg, seg.state, queries)
            # the one host read of a batch: it waits for phase A, runs the
            # ``jit__reduce_max`` program and copies its scalar back
            with obs_trace.span("rung_pick") as sp:
                max_count = int(counts.max())  # repro: allow[r1-host-sync] THE sanctioned phase-A rung-pick read (DESIGN.md §8)
                cb, c_cap, over = pipe.pick_rung(
                    max_count, seg.ctot_cap, floor,
                    seg.ctot_norm, seg.c_norm, overflow)
                sp.set(rung=cb, max_count=max_count, c_cap=c_cap)
            rows = seg.state.dataset
            with obs_trace.span("phase_b_rerank", segment=int(seg.size),
                                cbucket=int(cb),
                                c_cap=None if c_cap is None else int(c_cap),
                                row_bytes=rows.shape[1] * rows.dtype.itemsize,
                                tomb_select=pipe.tomb_select(
                                    self.cfg.k, int(tomb.shape[0]), cb)):
                res = _finish_segment(
                    self.cfg, cb, c_cap, seg.state, seg.gids, tomb,
                    probe_keys, lo, occ, queries)
            results.append(res)
            used.append((seg.size, cb, c_cap))
            if stats is not None and over:
                stats["overflow_hits"] = stats.get("overflow_hits", 0) + 1
                if c_cap is not None:
                    dropped = int(_truncated_total(occ, counts, c_cap, cb))  # repro: allow[r1-host-sync] overflow-rung stats, rare by construction
                    stats["truncated_candidates"] = (
                        stats.get("truncated_candidates", 0) + dropped)
        if self._delta_count or not results:
            with obs_trace.span("delta_scan", fill=int(self._delta_count)):
                delta_pts, delta_gids = self._delta_arrays()
                results.append(_query_delta(
                    self.cfg, delta_pts, delta_gids,
                    jnp.int32(self._delta_count), tomb, queries))
        with obs_trace.span("merge", parts=len(results)):
            d, i = results[0]
            for dn, in_ in results[1:]:
                d, i = pipe.stage_merge_pair(d, i, dn, in_)
        return d, i, tuple(used)

    def warm_compact(self, queries: jax.Array, floor: int = 64,
                     overflow: str = "escalate"):
        """Compile ``query_compact`` for this batch shape.

        Runs phase A once per segment and phase B at EVERY ladder rung
        (not just the rung this batch would pick), plus one full
        ``query_compact`` for the delta/merge executables —
        live traffic on any rung then hits compiled code
        (``pipe.pick_rung`` only ever returns ladder members).  Returns
        every (segment_size, cbucket, c_cap) triple compiled.
        """
        queries = jnp.asarray(queries)
        tomb = self._tombstone_array()
        warmed = []
        for seg, ladder in zip(self.segments,
                               self.candidate_ladders(floor, overflow)):
            if not ladder:
                continue
            probe_keys, lo, occ, counts = _probe_segment(
                self.cfg, seg.state, queries)
            counts.block_until_ready()
            for cb, c_cap in ladder:
                d, _ = _finish_segment(
                    self.cfg, cb, c_cap, seg.state, seg.gids, tomb,
                    probe_keys, lo, occ, queries)
                d.block_until_ready()
                warmed.append((seg.size, cb, c_cap))
        d, _, used = self.query_compact(queries, floor, overflow=overflow)
        d.block_until_ready()
        return tuple(warmed) + used
