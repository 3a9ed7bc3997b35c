"""MP-RW-LSH index: TPU-native build + batched multi-probe query.

The CPU design (chaining hash tables + per-query heap) is replaced by the
TPU-idiomatic design described in DESIGN.md Sect. 2:

  build : raw-hash all points -> bucket vectors -> uint32 mixed keys ->
          one sort per table.  Collective-free; embarrassingly shardable by
          dataset rows.
  query : phase A (``probe_index``: hash -> probe keys -> bucket extents
          + counts), then phase B (``finish_query``: compacted gather ->
          exact L1 rerank -> gid map -> tombstones), composed here over an
          ``IndexState``.  The segmented index, the shard_map path and the
          serving engine call the same two (DESIGN.md Sect. 3).

Everything is statically shaped and jit/vmap/shard_map friendly.  For the
mutable (insert/delete/compact) variant see ``core.segments``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import hashes as hashes_lib
from . import multiprobe as mp_lib
from . import pipeline as pipe
from repro.kernels.rows import STORED_DTYPES, UINT8_MAX_UNIVERSE, store_rows

__all__ = ["IndexConfig", "IndexState", "build_index", "query_index",
           "probe_index", "finish_query", "row_gids", "no_tombstones"]


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Static configuration (hashable; safe to close over in jit)."""

    num_tables: int = 8          # L
    num_hashes: int = 10         # M
    width: int = 8               # W (even for 'rw')
    num_probes: int = 100        # T extra buckets per table
    candidate_cap: int = 8       # max candidates gathered per probe
    universe: int = 256          # U, max (even) coordinate for 'rw'
    family: str = "rw"           # 'rw' | 'cauchy' | 'gaussian'
    hash_impl: str = "gather"    # 'gather' | 'thermo' | 'pallas'
    rerank_chunk: int = 512      # candidates per rerank scan step
    k: int = 50                  # neighbors returned
    dataset_dtype: str = "int32" # stored rows (``kernels.rows``): 'int16'
                                 # halves the rerank gather's bytes when
                                 # universe < 32768; 'uint8' stores x // 2
                                 # (quarter the bytes) when universe <= 510

    def __post_init__(self):
        if self.dataset_dtype not in STORED_DTYPES:
            raise ValueError(f"dataset_dtype must be one of {STORED_DTYPES}, "
                             f"got {self.dataset_dtype!r}")
        if (self.dataset_dtype == "uint8"
                and self.universe > UINT8_MAX_UNIVERSE):
            raise ValueError(
                f"dataset_dtype 'uint8' stores x // 2 of even coordinates "
                f"x <= {UINT8_MAX_UNIVERSE}; universe {self.universe} does "
                f"not fit")

    @property
    def probes_per_table(self) -> int:
        return self.num_probes + 1  # + epicenter


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IndexState:
    """Device-resident index for one dataset shard.

    params      : LshParams (walks/projections, offsets, key mixers)
    sorted_keys : (L, n) uint32   mixed bucket keys, ascending per table
    sorted_ids  : (L, n) int32    local row ids aligned with sorted_keys
    dataset     : (n, m)          the shard's points in their stored dtype
                  (``cfg.dataset_dtype``; uint8 holds x // 2), the rerank
                  source; readers widen them with ``kernels.rows.widen_rows``
    template    : (T+1, 2M) int8  universal probing template (row 0 = epicenter)
    row_offset  : ()  int32       global id of local row 0 (sharding)
    occ_from    : (L, n) int32    equal-key run length starting at each
                  position (DESIGN.md §8): a probed bucket's occupancy is
                  ``occ_from[lo]`` (every searchsorted-left hit lands on a
                  run start), so the probe's phase A needs no
                  ``side='right'`` search.  Optional (None on legacy/
                  abstract states; the extents then fall back to the
                  two-sided search).
    occ_hist    : (L, 32) int32   per-table bucket-occupancy histogram in
                  ceil-log2 bins (bin b = buckets with occupancy in
                  (2^(b-1), 2^b]), computed once at build/compaction.  The
                  two-level compaction policy (DESIGN.md §9) derives its
                  per-bucket cap from a high quantile of this histogram
                  (``pipeline.occupancy_quantile``) instead of the global
                  max bucket, so one hot bucket stops inflating every
                  query's ladder.  Optional like ``occ_from``.
    """

    params: hashes_lib.LshParams
    sorted_keys: jax.Array
    sorted_ids: jax.Array
    dataset: jax.Array
    template: jax.Array
    row_offset: jax.Array
    occ_from: Optional[jax.Array] = None
    occ_hist: Optional[jax.Array] = None

    def tree_flatten(self):
        return (
            self.params, self.sorted_keys, self.sorted_ids,
            self.dataset, self.template, self.row_offset, self.occ_from,
            self.occ_hist,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def make_template(cfg: IndexConfig) -> np.ndarray:
    """(T+1, 2M) template matrix with the epicenter (all-zero) row first."""
    sets = mp_lib.build_template(cfg.num_hashes, float(cfg.width), cfg.num_probes)
    mat = mp_lib.template_matrix(sets, cfg.num_hashes)
    return np.concatenate([np.zeros((1, 2 * cfg.num_hashes), np.int8), mat])


def make_params(cfg: IndexConfig, key: jax.Array, dim: int) -> hashes_lib.LshParams:
    if cfg.family == "rw":
        return hashes_lib.make_rw_params(
            key, cfg.num_tables, cfg.num_hashes, dim, cfg.universe, cfg.width)
    if cfg.family == "cauchy":
        return hashes_lib.make_cp_params(key, cfg.num_tables, cfg.num_hashes, dim, cfg.width)
    if cfg.family == "gaussian":
        return hashes_lib.make_gp_params(key, cfg.num_tables, cfg.num_hashes, dim, cfg.width)
    raise ValueError(cfg.family)


# Rows hashed per step of the build's loop: bounds the (rows, L, M) raw
# hashes and bucket vectors (about 1.4 KB a row at L = 8, M = 22) while the
# (L, n) keys fill in place; tests/test_chip_compile.py compiles the build
# at n = 10M for a v5e under its HBM.
BUILD_BLOCK_ROWS = 1 << 19


def _row_keys(cfg: IndexConfig, params: hashes_lib.LshParams,
              rows: jax.Array) -> jax.Array:
    """(b, m) rows -> (L, b) uint32 mixed bucket keys."""
    f = hashes_lib.raw_hash(params, rows, impl=cfg.hash_impl)       # (b, L, M)
    bucket, _ = hashes_lib.bucket_and_offsets(params, f)
    return hashes_lib.mix_keys(params, bucket).T


def _bucket_keys(cfg: IndexConfig, params: hashes_lib.LshParams,
                 dataset: jax.Array, block_rows: int) -> jax.Array:
    """(L, n) uint32 keys of every row, ``block_rows`` rows at a time.

    The last block is clamped to the end of the rows, so where
    ``block_rows`` does not divide n it hashes some rows twice and writes
    the same keys over them: each row's keys depend on that row alone.
    """
    n = dataset.shape[0]
    if n <= block_rows:
        return _row_keys(cfg, params, dataset)

    def block(b, keys):
        start = jnp.minimum(b * block_rows, n - block_rows)
        rows = jax.lax.dynamic_slice_in_dim(dataset, start, block_rows)
        return jax.lax.dynamic_update_slice_in_dim(
            keys, _row_keys(cfg, params, rows), start, axis=1)

    return jax.lax.fori_loop(
        0, -(-n // block_rows), block,
        jnp.zeros((cfg.num_tables, n), jnp.uint32))


def build_index(
    cfg: IndexConfig,
    key: jax.Array,
    dataset: jax.Array,
    row_offset: jax.Array | int = 0,
    params: Optional[hashes_lib.LshParams] = None,
    template: Optional[jax.Array] = None,
) -> IndexState:
    """Build the index over one dataset shard.  Collective-free.

    ``dataset`` holds the coordinates themselves (int32 or int16), never
    the stored form; the state keeps them as ``cfg.dataset_dtype``
    (``kernels.rows.store_rows``).  ``params`` may be passed in so that all
    shards share identical hash functions (required for distributed
    correctness); if None they are generated from ``key`` (fine for
    single-shard use since the same key yields the same params on every
    shard).  ``template`` likewise may be passed to reuse the
    (cfg-only-dependent) probing template — the segmented index rebuilds
    small segments often and the host-side template construction is not
    free.  The rows are hashed ``BUILD_BLOCK_ROWS`` at a time (any block
    size gives the same index); the sort, run lengths and histogram then
    run over the whole (L, n) keys.
    """
    n, dim = dataset.shape
    if dataset.dtype == jnp.uint8:
        raise ValueError("build_index takes coordinates, not uint8 stored "
                         "rows; widen them first (kernels.rows.widen_rows)")
    if params is None:
        params = make_params(cfg, key, dim)
    # The scopes name the build's device ops in a profiler trace.
    with jax.named_scope("build_rows"):
        keys_t = _bucket_keys(cfg, params, dataset, BUILD_BLOCK_ROWS)
    with jax.named_scope("build_sort"):
        # a stable sort of (key, row) pairs: argsort's order, and the keys
        # come out sorted beside it with no gather
        sorted_keys, order = jax.lax.sort(
            (keys_t, jax.lax.broadcasted_iota(jnp.int32, keys_t.shape, 1)),
            dimension=1, num_keys=1, is_stable=True)
    sorted_ids = order.astype(jnp.int32)
    if template is None:
        template = jnp.asarray(make_template(cfg))
    with jax.named_scope("run_lengths"):
        occ_from = _run_lengths(sorted_keys)
    with jax.named_scope("occ_hist"):
        occ_hist = _occ_histogram(sorted_keys, occ_from)
    if cfg.dataset_dtype != str(dataset.dtype):
        dataset = store_rows(dataset, cfg.dataset_dtype)
    return IndexState(
        params=params,
        sorted_keys=sorted_keys,
        sorted_ids=sorted_ids,
        dataset=dataset,
        template=template,
        row_offset=jnp.asarray(row_offset, jnp.int32),
        occ_from=occ_from,
        occ_hist=occ_hist,
    )


def _run_lengths(sorted_keys: jax.Array) -> jax.Array:
    """(L, n) equal-key run length starting at each position (§8).

    Each position's run ends at the next run start after it (or n): one
    reverse running minimum over the run starts' positions, computed once
    at build time, buys the query path out of every ``side='right'``
    search forever after.
    """
    l, n = sorted_keys.shape
    pos = jnp.arange(n, dtype=jnp.int32)
    if n == 0:
        return jnp.zeros((l, 0), jnp.int32)
    # position j + 1 where a run starts there, else n: its running minimum
    # from the right at j is the end of the run holding j
    next_start = jnp.where(sorted_keys[:, 1:] != sorted_keys[:, :-1],
                           pos[None, 1:], n)
    next_start = jnp.concatenate(
        [next_start, jnp.full((l, 1), n, jnp.int32)], axis=1)
    run_end = jax.lax.cummin(next_start, axis=1, reverse=True)
    return run_end - pos[None, :]


OCC_HIST_BINS = 32  # bin b: occupancy in (2^(b-1), 2^b]; bin 31 also > 2^30


def _occ_histogram(sorted_keys: jax.Array, occ_from: jax.Array) -> jax.Array:
    """(L, 32) bucket-occupancy histogram in ceil-log2 bins (§9).

    Counts *buckets* (equal-key runs), not rows: each run start contributes
    one count to the bin of its run length.  Ceil-log2 binning matches the
    pow-2 rung discipline — ``pipeline.occupancy_quantile`` reads a
    per-bucket cap straight off the bin edges.  Shard-local and additive,
    so the distributed build just psums it.
    """
    l, n = sorted_keys.shape
    if n == 0:
        return jnp.zeros((l, OCC_HIST_BINS), jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((l, 1), bool),
         sorted_keys[:, 1:] != sorted_keys[:, :-1]], axis=1)
    # ceil-log2 bin of each run length r >= 1, the bit length of r - 1 (a
    # run longer than 2^30 lands in the top bin anyway)
    bins = 32 - jax.lax.clz(occ_from - 1)
    bins = jnp.minimum(bins, OCC_HIST_BINS - 1)
    # non-starts go to a bin that is never counted
    bins = jnp.where(is_start, bins, OCC_HIST_BINS)
    # one (L,) count per bin, so no (L, n, bins) one-hot exists
    return jnp.stack([(bins == b).sum(axis=1, dtype=jnp.int32)
                      for b in range(OCC_HIST_BINS)], axis=1)


# --------------------------------------------------------------------------
# Query path (DESIGN.md §8)
# --------------------------------------------------------------------------
#
# Every query runs phase A (``probe_index``: probe keys, bucket extents and
# candidate counts), then phase B (``finish_query``: the gather at a static
# slab width, the rerank and the tombstone check).  The served path splits
# them into two jitted programs with one scalar host read between them, the
# batch's max count, which picks the narrowest rung that covers it
# (``pipeline.pick_rung``); ``query_index`` and the shard_map body take the
# full ``L*P*C`` slab in one program instead.  The rerank contract depends
# only on the candidate set, so every covering width gives the same bits.

@partial(jax.jit, static_argnums=0)
def probe_index(cfg: IndexConfig, state: IndexState, queries: jax.Array):
    """Phase A: probe keys + raw bucket extents + candidate counts.

    Returns (probe_keys (Q, L, P), lo (Q, L*P), occ (Q, L*P) raw bucket
    occupancies, counts (Q,)).  The extents cross the host-side rung pick
    so phase B never re-searches; phase B reads only the probe keys'
    shape.
    """
    # The scopes name this program's device ops in a profiler trace; the
    # counts' own scope is inside the extents (``counts``).
    with jax.named_scope("hash"):
        bucket, x_neg = pipe.stage_hash(cfg, state.params, queries)
    with jax.named_scope("probe_keys"):
        probe_keys = pipe.stage_probe_keys(
            cfg, state.params, state.template, bucket, x_neg)
    with jax.named_scope("extents"):
        lo, occ, counts = pipe.stage_probe_extents(
            cfg, state.sorted_keys, probe_keys, state.occ_from)
    return probe_keys, lo, occ, counts


def finish_query(cfg: IndexConfig, cbucket: Optional[int],
                 c_cap: Optional[int], state: IndexState, gids: jax.Array,
                 tombstones: jax.Array, probe_keys: jax.Array, lo: jax.Array,
                 occ: jax.Array, queries: jax.Array):
    """Phase B: compacted gather at the (static) rung -> rerank at k + T
    -> gid map -> tombstone -> first k (``pipe.rerank_candidates``).

    ``cbucket=None`` is the full ``L*P*C`` slab.  ``c_cap=None`` keeps the
    full per-bucket clamp (exact); an int is the two-level truncate rung's
    tighter cap (deterministic sorted-prefix truncation, DESIGN.md §9).
    ``gids`` (n,) maps local rows to global ids (``row_gids`` for a flat
    shard); ``tombstones`` is ascending and INT32_MAX-padded
    (``no_tombstones`` for none).  Not jitted itself: each caller's own
    program holds it.
    """
    n = state.dataset.shape[0]
    # The scopes name this program's device ops in a profiler trace.
    with jax.named_scope("compact_gather"):
        ids, _ = pipe.stage_fused_probe(
            cfg, state.sorted_keys, state.sorted_ids, probe_keys, n, cbucket,
            extents=(lo, occ), c_cap=c_cap)
    return pipe.rerank_candidates(cfg, state.dataset, queries, ids, gids,
                                  tombstones)


def row_gids(state: IndexState) -> jax.Array:
    """(n,) global id of each local row: ``row_offset`` + row."""
    return (jnp.arange(state.dataset.shape[0], dtype=jnp.int32)
            + state.row_offset)


def no_tombstones() -> jax.Array:
    """The tombstone array with nothing deleted: one INT32_MAX pad slot,
    the array ``SegmentedIndex`` ships before any delete."""
    return jnp.full((1,), np.iinfo(np.int32).max, jnp.int32)


@partial(jax.jit, static_argnums=0)
def query_index(cfg: IndexConfig, state: IndexState, queries: jax.Array):
    """Batched ANN query at the full slab, one program, no host read.

    Returns (dists (Q,k) int32, global_ids (Q,k) int32).
    """
    probe_keys, lo, occ, _ = probe_index(cfg, state, queries)
    return finish_query(cfg, None, None, state, row_gids(state),
                        no_tombstones(), probe_keys, lo, occ, queries)
