"""Batched ANN serving engine (the paper's system as a service).

Production posture on a single process:
  * request queue -> **shape-bucketed** batches (DESIGN.md §Perf): a batch of
    Q live requests is padded up to the smallest power-of-two bucket in
    [bucket_min, batch_size] instead of always to batch_size.  Each bucket
    shape compiles once (jit's executable cache is keyed on shapes); the
    engine warms every bucket at startup and tracks cold-bucket hits, so
    mixed live traffic triggers **zero recompiles after warm-up** while
    small batches stop paying full-batch padding FLOPs;
  * a **mutable segmented index** (core.segments): ``insert``/``delete``
    endpoints mutate the delta buffer / tombstone set without a rebuild,
    and a compaction pass — triggered by the delta-buffer watermark or by
    segment-count growth — folds everything back into one sorted segment.
    Single-process it runs opportunistically between batches; the
    multi-replica deployment runs it on the background thread pool
    (DESIGN.md Sect. 3);
  * queries probe every segment with the staged pipeline and fold the
    per-segment top-k lists with the same ``topk_merge`` op the
    distributed ring merge uses (executor per backend, DESIGN.md §5.4);
  * per-batch deadline timing + straggler hedging hook: if a batch misses
    the hedge deadline the event is recorded in ``stats['hedges']``; the
    cluster runtime (``repro.cluster``, DESIGN.md §7) turns this into a real
    re-issue — a slow/dead replica's batch goes to a peer and the first
    complete result wins.  ``run_padded``/``query_batch`` are the seams the
    replica layer drives;
  * index checkpoint/restore via repro.ckpt (a serving node can be replaced
    and re-load the shard it owns);
  * exact L1 rerank guarantees results are exact over probed candidates.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import (
    compilation_cache as cc_public)

from repro.analysis import racecheck
from repro.core.index import IndexConfig, IndexState
from repro.core.segments import SegmentedIndex
from repro.obs import FlightRecorder, MetricsRegistry
from repro.obs import trace as obs_trace

__all__ = ["ServeConfig", "AnnServingEngine", "enable_compilation_cache",
           "compilation_cache_stats", "shape_buckets", "bucket_for",
           "validate_queries"]


# --------------------------------------------------------------------------
# Persistent compilation cache (DESIGN.md §8)
# --------------------------------------------------------------------------
# Cold engine start is compile-dominated: warmup compiles every (batch
# bucket x candidate rung) executable.  The executables depend only on
# (config, shapes), so the JAX persistent compilation cache turns every
# restart after the first into disk reads.  Enabled once per process; the
# hit/miss counters come from jax.monitoring events and are surfaced in
# ``AnnServingEngine.summary()`` so operators can verify warm starts
# actually hit.  The directory is placed from outside: where
# ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and no
# directory is set in code; otherwise it is ``<repo>/.jax_cache``, one
# fixed path inside the checkout (the path is part of what lets a later
# process find an entry, so it must not move between runs).

REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))

_CACHE_STATS = {"enabled": False, "dir": None, "hits": 0, "misses": 0,
                "atomic_writes": False}
_LISTENING = False


def _cache_listener(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_STATS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE_STATS["misses"] += 1


def _install_atomic_cache_writes() -> bool:
    """Make jax's on-disk cache writes atomic (write-temp + os.replace).

    ``LRUCache.put`` writes cache files with a bare ``write_bytes`` and,
    with eviction disabled (our config), takes no lock — so a reader in
    another process can observe a half-written entry, and a worker
    SIGKILL'd mid-write (the §10 chaos drills) leaves a torn file on disk
    forever.  Either way ``deserialize_executable`` later segfaults the
    READER on the truncated bytes.  Pre-writing the entry to a
    same-directory temp file and ``os.replace``-ing it into place means
    readers see the old entry, the complete new one, or a miss — never a
    prefix; the original ``put`` then hits its entry-already-exists early
    return.  Private API: when its layout is not the one this patch knows,
    the stock behavior stays, a warning says so, and False is returned.
    """
    import tempfile
    import warnings

    try:
        from jax._src import lru_cache as _lru
    except ImportError as err:
        warnings.warn(f"compile cache writes stay non-atomic: {err}")
        return False
    if not (hasattr(_lru, "LRUCache") and hasattr(_lru, "_CACHE_SUFFIX")):
        warnings.warn("compile cache writes stay non-atomic: "
                      "jax._src.lru_cache has an unknown layout")
        return False
    if getattr(_lru.LRUCache.put, "_repro_atomic", False):
        return True
    orig_put = _lru.LRUCache.put
    cache_suffix = _lru._CACHE_SUFFIX

    def atomic_put(self, key, val):
        if key and not self.eviction_enabled:
            try:
                cache_path = self.path / f"{key}{cache_suffix}"
                if not cache_path.exists():
                    fd, tmp = tempfile.mkstemp(
                        dir=str(self.path), suffix=".tmp")
                    try:
                        with os.fdopen(fd, "wb") as f:
                            f.write(val)
                        os.replace(tmp, cache_path)
                    except BaseException:
                        try:
                            os.unlink(tmp)
                        except OSError:
                            pass
                        raise
            except OSError:
                pass          # cache write trouble is never fatal
        return orig_put(self, key, val)

    atomic_put._repro_atomic = True
    _lru.LRUCache.put = atomic_put
    return True


def enable_compilation_cache() -> dict:
    """Turn on JAX's persistent compilation cache (idempotent).

    Uses ``$JAX_COMPILATION_CACHE_DIR`` when it is set (and then sets no
    directory in code), else ``REPO_CACHE_DIR``.  Returns the live stats
    dict (also via ``compilation_cache_stats()``).
    """
    global _LISTENING
    if _CACHE_STATS["enabled"]:
        return _CACHE_STATS
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    atomic = _install_atomic_cache_writes()
    # serving executables are small and numerous; cache all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax's "is the cache used" probe latches on the FIRST compile of the
    # process; any jit that ran before this config lands (dataset prep,
    # index build) would otherwise leave caching off for the whole process.
    # reset_cache() re-evaluates the gate under the new settings.
    cc_public.reset_cache()
    if not _LISTENING:
        jax.monitoring.register_event_listener(_cache_listener)
        _LISTENING = True
    _CACHE_STATS.update(enabled=True, dir=path, atomic_writes=atomic)
    return _CACHE_STATS


def compilation_cache_stats() -> dict:
    return dict(_CACHE_STATS)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_size: int = 64           # max queries per dispatch (largest bucket)
    bucket_min: int = 8            # smallest padded batch shape
    shape_buckets: bool = True     # pow2 buckets; False = always pad to batch_size
    warm_buckets: bool = True      # pre-compile every bucket at startup
    compact_probe: bool = True     # fused probe front-end + pow2 candidate
                                   # buckets (DESIGN.md §8); False = the
                                   # worst-case L*P*C slab every batch
    cand_bucket_min: int = 128     # smallest candidate-count bucket
    cand_cap_quantile: float = 0.999  # occupancy-histogram quantile for the
                                   # two-level per-bucket cap (DESIGN.md §9);
                                   # >= 1.0 disables the second level
    cand_overflow: str = "escalate"  # hot-bucket overflow rung policy:
                                   # 'escalate' = exact worst-case rung
                                   # (bit-identical), 'truncate' = bounded
                                   # slab with per-bucket prefix truncation
                                   # (<0.5% recall cost at paper configs)
    cand_cap_sample: int = 32      # surrogate queries sampled per segment to
                                   # size the normal ladder top from realized
                                   # candidate totals
    persistent_cache: bool = True  # JAX persistent compilation cache: warm
                                   # restarts read executables off disk
                                   # (directory: enable_compilation_cache)
    hedge_ms: float = 50.0
    max_wait_ms: float = 2.0
    delta_cap: int = 1024          # delta-buffer capacity (points)
    compact_watermark: float = 0.5  # delta fill fraction that triggers compaction
    max_segments: int = 4           # segment count that triggers compaction
    tombstone_watermark: float = 0.25  # dead/live fraction that triggers compaction
    target_recall: Optional[float] = None  # quality target: autotune (L, T,
                                   # candidate_cap) at startup (DESIGN.md §6)
    autotune_calib: int = 32       # calibration queries for the autotuner


def shape_buckets(serve_cfg: ServeConfig) -> List[int]:
    """Padded batch shapes a ``serve_cfg`` dispatches: pow2 up to batch_size.

    Pure function of the config so remote clients (``RemoteReplica``) can
    compute bucket shapes without holding an engine — the padding decision
    must live router-side (pad once, fan out) even when every engine lives
    in another process.
    """
    if not serve_cfg.shape_buckets:
        return [serve_cfg.batch_size]
    out, b = [], max(1, serve_cfg.bucket_min)
    while b < serve_cfg.batch_size:
        out.append(b)
        b *= 2
    out.append(serve_cfg.batch_size)
    return out


def bucket_for(q: int, serve_cfg: ServeConfig) -> int:
    """Padded shape a q-row batch dispatches at under ``serve_cfg``."""
    for b in shape_buckets(serve_cfg):
        if q <= b:
            return b
    return serve_cfg.batch_size


def validate_queries(queries, dim: int) -> np.ndarray:
    """Normalize to (Q, dim) int32, failing *now* with a clear message.

    Without this, a wrong-dim or float query is accepted silently and only
    blows up batches later inside ``np.stack``/``np.concatenate`` (possibly
    poisoning a batch that mixes it with valid requests).  Module-level so
    the router can reject malformed input before it costs an RPC.
    """
    arr = np.atleast_2d(np.asarray(queries))
    if arr.ndim != 2:
        raise ValueError(
            f"queries must be (dim,) or (Q, dim); got shape {arr.shape}")
    if arr.shape[1] != dim:
        raise ValueError(
            f"query dim {arr.shape[1]} != index dim {dim} "
            f"(shape {arr.shape})")
    if not np.can_cast(arr.dtype, np.int32, casting="same_kind"):
        raise TypeError(
            f"queries must be integer-typed (castable to int32); got "
            f"dtype {arr.dtype}")
    return arr.astype(np.int32, copy=False)


class AnnServingEngine:
    """Single-shard engine; the distributed variant wraps dist_query_fn."""

    def __init__(self, cfg: IndexConfig, serve_cfg: ServeConfig,
                 dataset: Optional[jax.Array] = None,
                 key: Optional[jax.Array] = None,
                 index: Optional[SegmentedIndex] = None):
        """``dataset`` seeds a fresh index; ``index`` adopts an existing one
        (the cluster recovery path rebuilds a ``SegmentedIndex`` from a
        snapshot + WAL replay and hands it in — autotuning is skipped, the
        index is served as reconstructed)."""
        if (dataset is None) == (index is None):
            raise ValueError("pass exactly one of dataset= or index=")
        self.serve_cfg = serve_cfg
        if serve_cfg.persistent_cache:
            # before the first compile so warmup itself can hit the cache
            enable_compilation_cache()
        key = key if key is not None else jax.random.PRNGKey(0)
        self.autotune = None
        if index is not None:
            serve_cfg = dataclasses.replace(serve_cfg, target_recall=None)
            self.serve_cfg = serve_cfg
        if serve_cfg.target_recall is not None and dataset.shape[0] > 0:
            # Quality is a first-class config input: derive (L, T, cap) from
            # the analytical success model + a calibration split, then serve
            # with the tuned config (DESIGN.md §6).  Imported lazily so the
            # engine has no hard dependency on the eval subsystem.  An empty
            # dataset (cold start before any inserts) has nothing to
            # calibrate against; serve as configured and let the operator
            # re-tune once data exists.
            from repro.eval.autotune import tune_for_recall
            self.autotune = tune_for_recall(
                cfg, dataset, serve_cfg.target_recall, key=key,
                num_calib=serve_cfg.autotune_calib)
            cfg = self.autotune.cfg
        self.cfg = cfg
        if index is not None:
            self.index = index
            # serving policy belongs to the engine: adopted indexes serve
            # under this engine's two-level cap knobs (segments without
            # derived caps pick them up lazily under these values)
            index.cap_quantile = serve_cfg.cand_cap_quantile
            index.cap_sample = serve_cfg.cand_cap_sample
        elif self.autotune is not None and self.autotune.state is not None:
            # The tuner already built and validated exactly this index
            # (same cfg/key/dataset); seed the segment from it instead of
            # re-hashing and re-sorting the whole dataset.
            n = dataset.shape[0]
            self.index = SegmentedIndex.from_checkpoint(
                cfg, self.autotune.state,
                jnp.arange(n, dtype=jnp.int32), n,
                delta_cap=serve_cfg.delta_cap,
                cap_quantile=serve_cfg.cand_cap_quantile,
                cap_sample=serve_cfg.cand_cap_sample)
        else:
            self.index = SegmentedIndex.from_dataset(
                cfg, key, dataset, delta_cap=serve_cfg.delta_cap,
                cap_quantile=serve_cfg.cand_cap_quantile,
                cap_sample=serve_cfg.cand_cap_sample)
        self._dim = self.index.dim
        self._pending: List[np.ndarray] = []
        # typed metrics registry (DESIGN.md §12); the registry doubles as
        # the dict-style ``stats`` facade so every historical mutation
        # site below stays untouched, while per-batch latency lands in a
        # log2 histogram instead of the old unbounded list
        self.metrics = MetricsRegistry("engine")
        self.stats = self.metrics
        for k in ("batches", "queries", "hedges", "inserts", "deletes",
                  "bucket_cold_hits", "overflow_hits",
                  "truncated_candidates"):
            self.stats[k] = 0
        for k in ("compact_ms", "warmup_ms", "total_ms"):
            self.stats[k] = 0.0
        self.metrics.family("cand_buckets")
        self._lat = self.metrics.histogram("batch_ms")
        # flight recorder: bounded ring of recent batches + slow exemplars
        # (a batch past the hedge deadline is by definition worth a look)
        self.flight = FlightRecorder(slow_ms=serve_cfg.hedge_ms)
        # (bucket, index-structure signature) pairs already compiled; a
        # query against a missing pair implies an XLA compile (cold hit)
        self._warm: set = set()
        if serve_cfg.warm_buckets:
            self.warmup()
        # opt-in race sanitizer (REPRO_SANITIZE=1): wraps the entry points
        # with owner/epoch tokens AFTER construction so warmup and other
        # boot-time internal calls stay unwrapped (DESIGN.md §11)
        racecheck.maybe_instrument(
            self, f"engine@{id(self):x}",
            queries=("run_padded", "query_batch", "drain"),
            mutations=("insert", "delete", "compact"))

    # -- shape buckets -----------------------------------------------------

    def buckets(self) -> List[int]:
        """Padded batch shapes the engine dispatches: pow2 up to batch_size."""
        return shape_buckets(self.serve_cfg)

    def bucket_for(self, q: int) -> int:
        """Padded shape a q-row batch dispatches at (router reuses this so
        its fan-out batches land on shapes every replica has compiled)."""
        return bucket_for(q, self.serve_cfg)

    def _index_signature(self) -> tuple:
        """Shapes the jitted query path specializes on besides the batch.

        A new segment size, delta activation, or tombstone-array growth
        compiles fresh executables even for a warm bucket; tracking it keeps
        the cold-hit counter honest across mutations.  The formula lives on
        the index (``SegmentedIndex.structure_signature``) so it cannot
        drift from the actual padding policy.
        """
        return self.index.structure_signature()

    def warmup(self) -> None:
        """Compile every bucket shape against the current index structure.

        With ``compact_probe`` this is the **(batch-bucket x
        candidate-bucket) grid**: per batch bucket, the probe phase plus
        the gather+rerank phase at every rung of every segment's candidate
        ladder (DESIGN.md §8) — whichever candidate bucket live counts pick,
        the executable is already compiled.  After this, mixed live traffic
        hits cached executables only (``stats['bucket_cold_hits']`` stays
        flat) — recompile-free serving.
        """
        t0 = time.perf_counter()
        sig = self._index_signature()
        for b in self.buckets():
            if (b, sig) in self._warm:
                continue
            warm = jnp.zeros((b, self._dim), jnp.int32)
            if self.serve_cfg.compact_probe:
                for key in self.index.warm_compact(
                        warm, floor=self.serve_cfg.cand_bucket_min,
                        overflow=self.serve_cfg.cand_overflow):
                    self._warm.add((b, sig) + key)
            else:
                self.index.query(warm)[0].block_until_ready()
            self._warm.add((b, sig))
        self.stats["warmup_ms"] += (time.perf_counter() - t0) * 1e3

    @property
    def state(self) -> IndexState:
        """The compacted index's IndexState (legacy checkpoint payload).

        Refuses to hand out a partial view: with pending delta inserts,
        tombstones, or multiple segments, a single segment's state would
        silently drop acknowledged mutations — use ``checkpoint_payload``
        (or ``compact()`` first).
        """
        idx = self.index
        if not idx.segments:
            raise RuntimeError("index is empty; nothing to checkpoint")
        if idx.num_segments != 1 or idx.delta_fill > 0 or idx.num_tombstones:
            raise RuntimeError(
                "index has uncompacted mutations; call compact() first or "
                "checkpoint via checkpoint_payload()")
        return idx.segments[0].state

    def checkpoint_payload(self):
        """(IndexState, gids, next_gid) capturing every acknowledged mutation.

        Compacts as needed; restore with ``SegmentedIndex.from_checkpoint``.
        """
        return self.index.checkpoint_payload()

    # -- mutation endpoints ------------------------------------------------

    def insert(self, points: np.ndarray) -> np.ndarray:
        """Add points to the live index; returns their global ids."""
        gids = self.index.insert(points)
        self.stats["inserts"] += len(gids)
        self._maybe_compact()
        return gids

    def delete(self, gids) -> int:
        """Tombstone global ids; returns how many were newly deleted."""
        removed = self.index.delete(gids)
        self.stats["deletes"] += removed
        self._maybe_compact()
        return removed

    def compact(self) -> None:
        """Force a major compaction (also runs automatically, see below).

        The compaction count lives on the index (``index.compactions``) —
        the single source of truth ``summary()`` reports.
        """
        t0 = time.perf_counter()
        self.index.compact()
        self.stats["compact_ms"] += (time.perf_counter() - t0) * 1e3
        # Compaction changes structure_signature(), so every warm bucket
        # just went cold.  Re-warm immediately: the XLA compiles land in
        # warmup_ms instead of silently inflating the next batches, and
        # bucket_cold_hits stays an honest "unplanned recompile" counter.
        if self.serve_cfg.warm_buckets:
            self.warmup()

    def _maybe_compact(self) -> None:
        """Watermark-triggered compaction (DESIGN.md Sect. 3).

        Runs opportunistically between batches in this single-process
        engine; a multi-replica deployment runs the same check on a
        background thread against a swapped-in index copy.
        """
        idx = self.index
        if (idx.delta_fill >= self.serve_cfg.compact_watermark
                or idx.num_segments > self.serve_cfg.max_segments
                or (idx.num_tombstones
                    >= self.serve_cfg.tombstone_watermark
                    * max(idx.num_live, 1))):
            self.compact()

    # -- query path --------------------------------------------------------

    def _validate_queries(self, queries) -> np.ndarray:
        """Normalize to (Q, dim) int32 (module-level ``validate_queries``)."""
        return validate_queries(queries, self._dim)

    def submit(self, queries: np.ndarray) -> None:
        for q in self._validate_queries(queries):
            self._pending.append(q)

    def _next_batch(self) -> Optional[Tuple[np.ndarray, int]]:
        if not self._pending:
            return None
        take = self._pending[:self.serve_cfg.batch_size]
        self._pending = self._pending[len(take):]
        batch = np.stack(take)
        bucket = self.bucket_for(len(take))
        if batch.shape[0] < bucket:  # pad to the bucket's compiled shape
            pad = np.zeros((bucket - batch.shape[0], self._dim), np.int32)
            batch = np.concatenate([batch, pad])
        return batch, len(take)

    def _run_batch(self, batch: np.ndarray, n_real: int,
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Run one already-padded batch; returns PADDED (B, k) results.

        Single place for the warm/cold bookkeeping, latency stats, and the
        hedge-deadline check — ``drain`` and the cluster replica seam
        (``run_padded``) both land here, so their metrics agree.
        """
        sig = self._index_signature()
        key = (batch.shape[0], sig)
        if key not in self._warm:
            self.stats["bucket_cold_hits"] += 1
            self._warm.add(key)
        used = ()
        obs_trace.capture_begin()
        t0 = time.perf_counter()
        with obs_trace.span("engine_batch", bucket=int(batch.shape[0]),
                            n_real=int(n_real)):
            if self.serve_cfg.compact_probe:
                d, i, used = self.index.query_compact(
                    jnp.asarray(batch), floor=self.serve_cfg.cand_bucket_min,
                    overflow=self.serve_cfg.cand_overflow, stats=self.stats)
                for seg_key in used:
                    self.stats["cand_buckets"][seg_key[1]] += 1
                    ck = (batch.shape[0], sig) + seg_key
                    if ck not in self._warm:
                        # an unplanned (batch, candidate)-bucket compile:
                        # the honest recompile counter benchmarks assert on
                        self.stats["bucket_cold_hits"] += 1
                        self._warm.add(ck)
            else:
                d, i = self.index.query(jnp.asarray(batch))
            d.block_until_ready()
        ms = (time.perf_counter() - t0) * 1e3
        if ms > self.serve_cfg.hedge_ms:
            # hedge deadline missed: recorded here; the cluster router
            # additionally re-issues the batch to a peer replica (§7).
            self.stats["hedges"] += 1
        self.stats["batches"] += 1
        self.stats["queries"] += n_real
        self.stats["total_ms"] += ms
        self._lat.record_ms(ms)
        entry = {"bucket": int(batch.shape[0]), "n_real": int(n_real),
                 "rungs": [list(u) for u in used]}
        if ms > self.flight.slow_ms:
            # slow-path only: stamp the exemplar with a result preview
            entry["preview_d"] = np.asarray(d[:1]).tolist()  # repro: allow[r1-host-sync] flight-recorder slow-exemplar capture — batch-boundary read after block_until_ready, slow path only (DESIGN.md §12)
        self.flight.record(ms, entry, spans=obs_trace.capture_end())
        return np.asarray(d), np.asarray(i)  # repro: allow[r1-host-sync] batch-boundary result conversion after block_until_ready

    def run_padded(self, batch: np.ndarray, n_real: int,
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Cluster replica seam: serve one pre-padded batch, padded results.

        The router pads a fan-out batch ONCE to the shared bucket shape and
        every replica serves that exact shape — replicas reuse each other's
        compiled executables (same jit cache key) and the cross-shard merge
        sees one static shape.  Lazily re-warms like ``drain``.
        """
        if self.serve_cfg.warm_buckets:
            self.warmup()
        return self._run_batch(np.asarray(batch, np.int32), n_real)

    def query_batch(self, queries) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous one-shot query path (no pending-queue round trip).

        Validates, chunks to ``batch_size``, pads each chunk to its shape
        bucket, and returns unpadded ``(Q, k)`` dists/gids.  The single-node
        mirror the cluster consistency oracle compares against.
        """
        q = self._validate_queries(queries)
        if q.shape[0] == 0:
            return (np.zeros((0, self.cfg.k), np.int32),
                    np.zeros((0, self.cfg.k), np.int32))
        if self.serve_cfg.warm_buckets:
            self.warmup()
        out_d, out_i = [], []
        for lo in range(0, q.shape[0], self.serve_cfg.batch_size):
            chunk = q[lo: lo + self.serve_cfg.batch_size]
            n = chunk.shape[0]
            bucket = self.bucket_for(n)
            if n < bucket:
                pad = np.zeros((bucket - n, self._dim), np.int32)
                chunk = np.concatenate([chunk, pad])
            d, i = self._run_batch(chunk, n)
            out_d.append(d[:n])
            out_i.append(i[:n])
        return np.concatenate(out_d), np.concatenate(out_i)

    def drain(self) -> Tuple[np.ndarray, np.ndarray]:
        """Process all pending requests; returns (dists (B,k) int32 asc,
        gids (B,k) int32, -1 pad) stacked over requests.

        Lazy re-warm: mutations that did NOT trigger a compaction (delta
        activation, tombstone-array growth) also change the structure
        signature; warming here keeps the batch loop recompile-free for
        those too (warmup() is a set-membership no-op when already warm).
        """
        if self.serve_cfg.warm_buckets and self._pending:
            self.warmup()
        out_d, out_i = [], []
        while True:
            nb = self._next_batch()
            if nb is None:
                break
            batch, n_real = nb
            d, i = self._run_batch(batch, n_real)
            out_d.append(d[:n_real])
            out_i.append(i[:n_real])
        self._maybe_compact()
        if not out_d:
            # Same dtypes as the non-empty path (int32 dists/ids): callers
            # concatenate drain outputs, and a float64 empty row would
            # silently promote the whole result.
            return (np.zeros((0, self.cfg.k), np.int32),
                    np.zeros((0, self.cfg.k), np.int32))
        return np.concatenate(out_d), np.concatenate(out_i)

    def summary(self) -> dict:
        total_s = self.stats["total_ms"] / 1e3
        quality = None
        if self.autotune is not None:
            quality = {
                "target_recall": self.autotune.target_recall,
                "validated_recall": round(self.autotune.validated_recall, 4),
                "met_target": self.autotune.met_target,
                "num_tables": self.cfg.num_tables,
                "num_probes": self.cfg.num_probes,
                "candidate_cap": self.cfg.candidate_cap,
            }
        return {
            "quality": quality,
            "queries": self.stats["queries"],
            "batches": self.stats["batches"],
            "hedges": self.stats["hedges"],
            "inserts": self.stats["inserts"],
            "deletes": self.stats["deletes"],
            "compactions": self.index.compactions,
            "segments": self.index.num_segments,
            "delta_fill": round(self.index.delta_fill, 4),
            "buckets": self.buckets(),
            "bucket_cold_hits": self.stats["bucket_cold_hits"],
            "cand_buckets": dict(sorted(self.stats["cand_buckets"].items())),
            # two-level compaction skew telemetry (DESIGN.md §9): how often
            # a batch hit the overflow rung, how many candidates the
            # truncate policy dropped, and each segment's occupancy shape —
            # a skew regression shows up here before it costs latency.
            "skew": {
                "cand_overflow": self.serve_cfg.cand_overflow,
                "cand_cap_quantile": self.serve_cfg.cand_cap_quantile,
                "overflow_hits": self.stats["overflow_hits"],
                "overflow_rate": (self.stats["overflow_hits"]
                                  / max(1, self.stats["batches"])),
                "truncated_candidates": self.stats["truncated_candidates"],
                "segments": self.index.skew_summary(),
            },
            "compile_cache": compilation_cache_stats(),
            "warmup_ms": self.stats["warmup_ms"],
            "mean_batch_ms": self._lat.mean_ms,
            # exact-bound quantiles from the log2 latency histogram
            # (DESIGN.md §12): the reported value is the upper edge of the
            # bucket provably containing the quantile (≤12.5% wide), and
            # memory stays O(1) under sustained drain() — no sample list
            "p50_batch_ms": self._lat.quantile_ms(0.50),
            "p99_batch_ms": self._lat.quantile_ms(0.99),
            "p999_batch_ms": self._lat.quantile_ms(0.999),
            "flight": self.flight.summary(),
            "queries_per_s": (self.stats["queries"] / total_s
                              if total_s > 0 else 0.0),
        }
