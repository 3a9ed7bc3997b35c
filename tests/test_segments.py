"""Segmented index + query path: parity with the seed implementation.

Two parity guarantees (ISSUE 1 acceptance):
  1. ``query_index`` (phase A + phase B at the full slab) is bit-identical
     to the seed monolithic implementation (frozen verbatim below).
  2. A segmented index after insert + delete + compact returns the same
     top-k as a fresh ``build_index`` over the equivalent dataset.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashes as hashes_lib
from repro.core import multiprobe as mp_lib
from repro.core.index import IndexConfig, build_index, query_index, make_params
from repro.core.segments import SegmentedIndex
from repro.data import ann_synthetic as ds
from repro.serve.engine import AnnServingEngine, ServeConfig

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    spec = ds.DatasetSpec("seg", n=3000, dim=16, universe=64, num_clusters=8)
    data = ds.make_dataset(spec)
    queries = ds.make_queries(spec, data, 16)
    return jnp.asarray(data), jnp.asarray(queries)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=30,
                       candidate_cap=32, universe=64, k=8, rerank_chunk=128)


# ---------------------------------------------------------------------------
# Frozen seed implementation (pre-pipeline monolith), kept verbatim from the
# seed commit so the staged refactor is pinned to bit-identical behaviour.
# ---------------------------------------------------------------------------

def _seed_probe_candidate_ids(cfg, state, queries):
    q = queries.shape[0]
    l, m = cfg.num_tables, cfg.num_hashes
    p, c = cfg.probes_per_table, cfg.candidate_cap
    n = state.dataset.shape[0]

    f = hashes_lib.raw_hash(state.params, queries, impl=cfg.hash_impl)
    bucket, x_neg = hashes_lib.bucket_and_offsets(state.params, f)
    deltas = mp_lib.instantiate_template(state.template, x_neg, float(cfg.width))
    probe_buckets = bucket[:, :, None, :] + deltas.astype(jnp.int32)
    probe_keys = hashes_lib.mix_keys(
        state.params, probe_buckets.transpose(0, 2, 1, 3))
    probe_keys = probe_keys.transpose(0, 2, 1)

    def per_table(sk, pk):
        lo = jnp.searchsorted(sk, pk, side="left")
        hi = jnp.searchsorted(sk, pk, side="right")
        return lo, hi

    lo, hi = jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
        state.sorted_keys, probe_keys)
    slots = lo[..., None] + jnp.arange(c, dtype=lo.dtype)
    valid = slots < jnp.minimum(hi, lo + c)[..., None]
    slots = jnp.clip(slots, 0, n - 1)

    def gather_ids(sid, sl):
        return sid[sl]

    ids = jax.vmap(gather_ids, in_axes=(0, 1), out_axes=1)(
        state.sorted_ids, slots)
    ids = jnp.where(valid, ids, n).reshape(q, l * p * c)

    ids = jnp.sort(ids, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), ids[:, 1:] == ids[:, :-1]], axis=-1)
    return jnp.where(dup, n, ids)


def _seed_l1_distance_chunked(dataset, queries, ids, k, chunk):
    n = dataset.shape[0]
    q, ctot = ids.shape
    big = jnp.int32(np.iinfo(np.int32).max // 2)
    pad = (-ctot) % chunk
    if pad:
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=n)
    steps = ids.shape[1] // chunk
    ids_steps = ids.reshape(q, steps, chunk).transpose(1, 0, 2)

    def body(carry, step_ids):
        best_d, best_i = carry
        sl = jnp.clip(step_ids, 0, n - 1)
        rows = dataset[sl]
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


def _seed_query_index(cfg, state, queries):
    ids = _seed_probe_candidate_ids(cfg, state, queries)
    d, i = _seed_l1_distance_chunked(
        state.dataset, queries, ids, cfg.k, cfg.rerank_chunk)
    gid = jnp.where(i >= 0, i + state.row_offset, -1)
    return d, gid


def test_pipeline_bit_identical_to_seed(cfg, small):
    data, queries = small
    state = build_index(cfg, KEY, data)
    sd, si = _seed_query_index(cfg, state, queries)
    pd, pi = query_index(cfg, state, queries)
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(pd))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pi))


# ---------------------------------------------------------------------------
# Segmented index behaviour
# ---------------------------------------------------------------------------

def test_single_segment_matches_query_index(cfg, small):
    data, queries = small
    state = build_index(cfg, KEY, data)
    d, i = query_index(cfg, state, queries)
    idx = SegmentedIndex.from_dataset(cfg, KEY, data)
    d2, i2, _ = idx.query_compact(queries)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


def test_insert_delete_compact_matches_fresh_build(cfg, small):
    data, queries = small
    rng = np.random.default_rng(3)
    extra = jnp.asarray(
        (rng.integers(0, 32, (300, data.shape[1])) * 2).astype(np.int32))

    idx = SegmentedIndex.from_dataset(cfg, KEY, data, delta_cap=128)
    new_gids = idx.insert(extra)                  # seals segments + delta
    dead = np.concatenate([np.arange(0, 50, dtype=np.int32),   # from seed seg
                           new_gids[:20]])                      # from inserts
    idx.delete(dead)
    idx.compact()
    assert idx.num_segments == 1 and idx.num_tombstones == 0
    assert idx.num_live == data.shape[0] + extra.shape[0] - len(dead)

    # equivalent dataset: survivors in insertion order, same shared params
    full = np.concatenate([np.asarray(data), np.asarray(extra)])
    live_mask = np.ones(full.shape[0], bool)
    live_mask[dead] = False
    fresh = build_index(cfg, KEY, jnp.asarray(full[live_mask]),
                        params=idx.params)
    fd, fi = query_index(cfg, fresh, queries)
    sd, si, _ = idx.query_compact(queries)
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(sd))
    # ids differ (stable gids vs fresh row numbers) but must name the same
    # points: map fresh local ids back through the survivor gid list.
    survivor_gids = np.arange(full.shape[0], dtype=np.int32)[live_mask]
    fi, si = np.asarray(fi), np.asarray(si)
    mapped = np.where(fi >= 0, survivor_gids[np.clip(fi, 0, None)], -1)
    np.testing.assert_array_equal(mapped, si)


def test_multi_segment_query_finds_inserts(cfg, small):
    data, queries = small
    idx = SegmentedIndex.from_dataset(cfg, KEY, data, delta_cap=64)
    gids = idx.insert(queries)                     # spans segments + delta
    assert idx.num_segments > 1 or idx.delta_fill > 0
    d, i, _ = idx.query_compact(queries)
    d, i = np.asarray(d), np.asarray(i)
    np.testing.assert_array_equal(d[:, 0], 0)      # exact copies found
    np.testing.assert_array_equal(i[:, 0], gids)
    assert (np.diff(d, axis=1) >= 0).all()         # merged lists stay sorted
    for row in i:                                  # merge never duplicates
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_delete_is_visible_before_compaction(cfg, small):
    data, queries = small
    idx = SegmentedIndex.from_dataset(cfg, KEY, data, delta_cap=64)
    gids = idx.insert(queries)
    idx.delete(gids)                               # tombstones only
    d, i, _ = idx.query_compact(queries)
    assert not np.isin(np.asarray(i), gids).any()
    # idempotent + unknown ids ignored
    assert idx.delete(gids) == 0
    assert idx.delete([10 ** 6]) == 0


def test_checkpoint_payload_roundtrip(cfg, small, tmp_path):
    from repro.ckpt import CheckpointManager

    data, queries = small
    idx = SegmentedIndex.from_dataset(cfg, KEY, data, delta_cap=64)
    gids = idx.insert(queries)                      # pending delta
    idx.delete(gids[-4:])                           # kill the NEWEST gids
    payload = idx.checkpoint_payload()              # compacts first
    assert idx.num_segments == 1 and idx.num_tombstones == 0
    d, i, _ = idx.query_compact(queries)

    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(1, payload)
    r_state, r_gids, r_next = mgr.restore(1, payload)
    node = SegmentedIndex.from_checkpoint(cfg, r_state, r_gids, r_next)
    d2, i2, _ = node.query_compact(queries)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    # gid stability across restore: the deleted-then-compacted tail gids
    # must NOT be re-issued (max(gids)+1 would resurrect them)
    assert int(r_gids.max()) + 1 < int(r_next)
    assert node.insert(np.asarray(queries[:1]))[0] == int(gids[-1]) + 1


def test_engine_state_refuses_partial_view(cfg, small):
    data, queries = small
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, delta_cap=256, compact_watermark=0.9),
        data)
    assert engine.state is not None                 # clean -> fine
    engine.insert(np.asarray(queries[:4]))          # below watermark
    with pytest.raises(RuntimeError, match="uncompacted"):
        _ = engine.state
    state, seg_gids, _next = engine.checkpoint_payload()  # compacts, then fine
    assert engine.state is state
    assert seg_gids.shape[0] == engine.index.num_live


def test_engine_serving_smoke(cfg, small):
    data, queries = small
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=16, delta_cap=64, compact_watermark=0.5),
        data)
    engine.submit(np.asarray(queries))
    d, i = engine.drain()
    assert d.shape == (queries.shape[0], cfg.k)

    rng = np.random.default_rng(11)
    new_pts = (rng.integers(0, 32, (40, data.shape[1])) * 2).astype(np.int32)
    gids = engine.insert(new_pts)                   # 40/64 > watermark
    assert engine.index.compactions >= 1
    assert engine.index.num_segments == 1
    engine.delete(gids[:5])
    engine.submit(new_pts[5:13])
    d2, i2 = engine.drain()
    assert not np.isin(i2, gids[:5]).any()
    np.testing.assert_array_equal(d2[:, 0], 0)      # surviving exact copies
    np.testing.assert_array_equal(i2[:, 0], gids[5:13])

    s = engine.summary()
    for key in ("p50_batch_ms", "p99_batch_ms", "queries_per_s",
                "inserts", "deletes", "compactions", "segments"):
        assert key in s
    assert s["queries"] == queries.shape[0] + 8
    assert s["queries_per_s"] > 0


def test_engine_empty_drain_matches_nonempty_dtypes(cfg, small):
    """ISSUE 3 satellite: the empty drain path must return the same int32
    dtypes as the non-empty path (a float64 empty row silently promotes any
    concatenation downstream)."""
    data, queries = small
    engine = AnnServingEngine(cfg, ServeConfig(batch_size=8), data)
    d0, i0 = engine.drain()                        # nothing pending
    assert d0.shape == (0, cfg.k) and i0.shape == (0, cfg.k)
    engine.submit(np.asarray(queries[:3]))
    d1, i1 = engine.drain()
    assert d0.dtype == d1.dtype == np.int32
    assert i0.dtype == i1.dtype == np.int32
    assert np.concatenate([d0, d1]).dtype == np.int32


def test_engine_cold_hits_flat_across_mutation_cycle(cfg, small):
    """ISSUE 3 satellite: compaction changes structure_signature(); the
    engine must re-warm (eagerly after compact, lazily before a drain) so an
    insert -> compact -> drain cycle never pays a cold XLA compile inside
    the batch loop."""
    data, queries = small
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=16, delta_cap=64, compact_watermark=0.5),
        data)
    warm_ms0 = engine.stats["warmup_ms"]
    assert engine.stats["bucket_cold_hits"] == 0
    rng = np.random.default_rng(21)
    pts = (rng.integers(0, 32, (40, data.shape[1])) * 2).astype(np.int32)
    engine.insert(pts)                              # 40/64 -> compaction
    assert engine.index.compactions >= 1
    engine.submit(np.asarray(queries))
    engine.drain()
    assert engine.stats["bucket_cold_hits"] == 0
    # the compiles happened, attributed to warmup, not silently to batches
    assert engine.stats["warmup_ms"] > warm_ms0

    # delta-only mutation (no compaction): lazy re-warm at drain time
    engine.insert(pts[:8])
    assert engine.index.delta_fill > 0
    engine.submit(np.asarray(queries[:5]))
    engine.drain()
    assert engine.stats["bucket_cold_hits"] == 0


def test_zero_point_segment_query(cfg):
    """ISSUE 3 satellite: n=0 shards (empty seed, or delete-everything +
    compact) must answer queries with all-invalid results instead of
    tripping the clip/gather of a zero-length table."""
    dim = 16
    queries = jnp.zeros((3, dim), jnp.int32)
    empty = jnp.zeros((0, dim), jnp.int32)

    idx = SegmentedIndex.from_dataset(cfg, KEY, empty)
    d, i, _ = idx.query_compact(queries)
    assert (np.asarray(i) == -1).all()

    gids = idx.insert(np.full((2, dim), 10, np.int32))  # delta over empty seg
    d, i, _ = idx.query_compact(jnp.full((1, dim), 10, jnp.int32))
    assert np.asarray(d)[0, 0] == 0 and np.asarray(i)[0, 0] == gids[0]

    idx.delete(gids)
    idx.compact()                                   # -> zero segments
    assert idx.num_segments == 0
    d, i, _ = idx.query_compact(queries)
    assert (np.asarray(i) == -1).all()

    # the flat path over an empty build_index is guarded too
    state = build_index(cfg, KEY, empty)
    d, i = query_index(cfg, state, queries)
    assert (np.asarray(i) == -1).all()
