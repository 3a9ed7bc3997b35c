"""Rows stored as uint8 (x // 2) answer exactly as rows stored as int32,
and the row-blocked build equals the one-shot build.

Every reader of stored rows widens them back to the paper's coordinates
(``kernels.rows.widen_rows``), so the served engine path, the flat
query, the rerank, the exact scan, compaction, checkpoints and a
replica's snapshot payload must not see the storage at all: same (dists,
ids) bit for bit, and, where the probes reach every row, the brute-force
answer.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import index as ci
from repro.core.baselines import l1_distance_chunked
from repro.core.index import IndexConfig, build_index, query_index
from repro.core.segments import SegmentedIndex
from repro.kernels import ref
from repro.kernels.fused_rerank import fused_rerank_xla
from repro.kernels.rows import store_rows, widen_rows
from repro.serve.engine import AnnServingEngine, ServeConfig

KEY = jax.random.PRNGKey(3)
N, DIM, U, K = 3000, 16, 510, 8

# An LSH configuration, and one whose probes reach every row (every row
# falls in one of a few buckets, and the cap holds them all), so that its
# answers are the brute-force answers.
CONFIGS = {
    "lsh": IndexConfig(num_tables=4, num_hashes=8, width=80, num_probes=30,
                       candidate_cap=32, universe=U, k=K, rerank_chunk=128),
    "exhaustive": IndexConfig(num_tables=2, num_hashes=1, width=1 << 20,
                              num_probes=2, candidate_cap=4096, universe=U,
                              k=K, rerank_chunk=128),
}


def _rows(rng, count):
    return (rng.integers(0, U // 2 + 1, (count, DIM)) * 2).astype(np.int32)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    return _rows(rng, N), _rows(rng, 16), _rows(rng, 100)


def _u8(cfg):
    return dataclasses.replace(cfg, dataset_dtype="uint8")


def _brute_force(rows, gids, queries, k):
    """(Q, k) exact L1 answers over ``rows`` by (dist, gid)."""
    d = np.abs(queries[:, None, :].astype(np.int64)
               - rows[None, :, :]).sum(-1)
    out_d, out_i = [], []
    for row in d:
        order = np.lexsort((gids, row))[:k]
        out_d.append(row[order])
        out_i.append(gids[order])
    return np.asarray(out_d), np.asarray(out_i)


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_stored_rows_round_trip_every_even_coordinate():
    x = jnp.arange(0, U + 1, 2, dtype=jnp.int32).reshape(1, -1)
    stored = store_rows(x, "uint8")
    assert stored.dtype == jnp.uint8 and stored.nbytes == x.size
    np.testing.assert_array_equal(np.asarray(widen_rows(stored)),
                                  np.asarray(x))
    for dt in ("int32", "int16"):
        np.testing.assert_array_equal(
            np.asarray(widen_rows(store_rows(x, dt))), np.asarray(x))


def test_uint8_refuses_a_universe_past_510():
    IndexConfig(universe=510, dataset_dtype="uint8")
    with pytest.raises(ValueError, match="universe 512"):
        IndexConfig(universe=512, dataset_dtype="uint8")
    with pytest.raises(ValueError, match="dataset_dtype"):
        IndexConfig(universe=510, dataset_dtype="float32")


def test_build_refuses_stored_rows_as_input(data):
    x, _, _ = data
    with pytest.raises(ValueError, match="coordinates"):
        build_index(_u8(CONFIGS["lsh"]), KEY,
                    store_rows(jnp.asarray(x), "uint8"))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_flat_compact_query_uint8_equals_int32(data, name):
    x, q, _ = data
    cfg = CONFIGS[name]
    s32 = build_index(cfg, KEY, jnp.asarray(x))
    s8 = build_index(_u8(cfg), KEY, jnp.asarray(x))
    assert s8.dataset.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(s8.dataset), x // 2)
    got32 = query_index(cfg, s32, jnp.asarray(q))
    got8 = query_index(_u8(cfg), s8, jnp.asarray(q))
    _same(got8, got32)
    if name == "exhaustive":
        _same(got8, _brute_force(x, np.arange(N), q, K))


def test_rerank_executors_read_uint8_rows(data):
    x, q, _ = data
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(-2, N + 2, (q.shape[0], 300)), jnp.int32)
    rows8 = store_rows(jnp.asarray(x), "uint8")
    want = ref.fused_rerank(jnp.asarray(x), jnp.asarray(q), ids, K)
    _same(fused_rerank_xla(rows8, jnp.asarray(q), ids, K, chunk=128), want)
    dedup = jnp.sort(jnp.where((ids < 0) | (ids >= N), N, ids), axis=-1)
    dedup = jnp.where(jnp.concatenate(
        [jnp.zeros((q.shape[0], 1), bool), dedup[:, 1:] == dedup[:, :-1]],
        axis=1), N, dedup)
    _same(l1_distance_chunked(rows8, jnp.asarray(q), dedup, K, 128),
          l1_distance_chunked(jnp.asarray(x), jnp.asarray(q), dedup, K, 128))


def _serve_through_mutations(cfg, x, q, inserts, tmp_path):
    """Answers at each stage: sealed, after inserts (a second segment and
    delta rows), after deletes, after compaction, after a checkpoint
    restore; and the engine's ``index_bytes`` at the start."""
    serve = ServeConfig(batch_size=16, bucket_min=16, warm_buckets=False,
                        cand_bucket_min=64, delta_cap=64,
                        compact_watermark=2.0, max_segments=8,
                        tombstone_watermark=2.0, hedge_ms=1e9)
    engine = AnnServingEngine(cfg, serve, dataset=jnp.asarray(x), key=KEY)
    out = {"index_bytes": engine.stats["index_bytes"]}
    out["sealed"] = engine.query_batch(q)
    new = engine.insert(inserts)
    assert engine.index.num_segments == 2
    out["inserted"] = engine.query_batch(q)
    dead = np.concatenate([np.arange(0, N, 7), new[::3]])
    engine.delete(dead)
    out["deleted"] = engine.query_batch(q)
    engine.compact()
    out["compacted"] = engine.query_batch(q)
    payload = engine.checkpoint_payload()
    mgr = CheckpointManager(str(tmp_path / cfg.dataset_dtype), keep=1)
    mgr.save(1, payload)
    state, gids, next_gid = mgr.restore(1, payload)
    assert state.dataset.dtype == jnp.dtype(cfg.dataset_dtype)
    node = AnnServingEngine(
        cfg, serve, index=SegmentedIndex.from_checkpoint(
            cfg, state, gids, next_gid, delta_cap=serve.delta_cap))
    out["restored"] = node.query_batch(q)
    return out, dead


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_engine_uint8_equals_int32_through_mutations_and_restore(
        data, name, tmp_path):
    x, q, inserts = data
    cfg = CONFIGS[name]
    got32, dead = _serve_through_mutations(cfg, x, q, inserts, tmp_path)
    got8, _ = _serve_through_mutations(_u8(cfg), x, q, inserts, tmp_path)
    stages = ("sealed", "inserted", "deleted", "compacted", "restored")
    for stage in stages:
        _same(got8[stage], got32[stage])
    assert got32["index_bytes"] - got8["index_bytes"] == N * DIM * 3
    if name == "exhaustive":
        rows = np.concatenate([x, inserts])
        gids = np.arange(rows.shape[0])
        live = ~np.isin(gids, dead)
        expect = {"sealed": (x, np.arange(N)),
                  "inserted": (rows, gids),
                  "deleted": (rows[live], gids[live]),
                  "compacted": (rows[live], gids[live]),
                  "restored": (rows[live], gids[live])}
        for stage, (r, g) in expect.items():
            _same(got8[stage], _brute_force(r, g, q, K))


def test_replica_snapshot_payload_holds_coordinates(data, tmp_path):
    from repro.cluster.replica import ShardReplica
    x, _, _ = data
    serve = ServeConfig(batch_size=16, bucket_min=16, warm_buckets=False,
                        hedge_ms=1e9)
    rep = ShardReplica(0, 0, _u8(CONFIGS["lsh"]), serve, KEY,
                       str(tmp_path / "r"), x[:500], wal_fsync=False)
    rows, gids, next_gid = rep.export_payload()
    assert rows.dtype == np.int32
    np.testing.assert_array_equal(rows, x[:500])
    np.testing.assert_array_equal(gids, np.arange(500))
    assert next_gid == 500


def _old_run_lengths(sorted_keys):
    n = sorted_keys.shape[1]
    end = jax.vmap(lambda sk: jnp.searchsorted(sk, sk, side="right"))(
        sorted_keys)
    return end - jnp.arange(n)[None, :]


def _old_occ_histogram(sorted_keys, occ_from):
    l = sorted_keys.shape[0]
    is_start = jnp.concatenate(
        [jnp.ones((l, 1), bool), sorted_keys[:, 1:] != sorted_keys[:, :-1]],
        axis=1)
    edges = jnp.asarray(2 ** np.arange(31, dtype=np.int64), jnp.int32)
    bins = jnp.minimum(jnp.searchsorted(edges, occ_from, side="left"),
                       ci.OCC_HIST_BINS - 1)
    bins = jnp.where(is_start, bins, ci.OCC_HIST_BINS)
    return (bins[:, :, None] == jnp.arange(ci.OCC_HIST_BINS)).sum(axis=1)


@pytest.mark.parametrize("block_rows", [1000, 700, 7])
@pytest.mark.parametrize("dtype", ["int32", "uint8"])
def test_blocked_build_equals_one_shot_build(data, block_rows, dtype,
                                             monkeypatch):
    """Blocks that divide n (1000), that do not (700, 7), against one
    block over all the rows; the run lengths and the histogram also
    against their former forms (an argsort and a gather, a right-side
    search, a one-hot sum)."""
    x, _, _ = data
    cfg = dataclasses.replace(CONFIGS["lsh"], dataset_dtype=dtype)
    monkeypatch.setattr(ci, "BUILD_BLOCK_ROWS", N)
    whole = build_index(cfg, KEY, jnp.asarray(x))
    monkeypatch.setattr(ci, "BUILD_BLOCK_ROWS", block_rows)
    blocked = build_index(cfg, KEY, jnp.asarray(x))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(blocked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(whole.occ_from),
                                  np.asarray(_old_run_lengths(
                                      whole.sorted_keys)))
    np.testing.assert_array_equal(
        np.asarray(whole.occ_hist),
        np.asarray(_old_occ_histogram(whole.sorted_keys, whole.occ_from)))
    keys = np.asarray(ci._bucket_keys(cfg, whole.params, jnp.asarray(x), N))
    order = np.argsort(keys, axis=1, kind="stable")
    np.testing.assert_array_equal(np.asarray(whole.sorted_ids), order)
    np.testing.assert_array_equal(np.asarray(whole.sorted_keys),
                                  np.take_along_axis(keys, order, axis=1))


@pytest.mark.parametrize("dtype, row_bytes", [("int32", 4 * DIM),
                                              ("uint8", DIM)])
def test_phase_b_span_carries_the_stored_row_bytes(data, monkeypatch,
                                                   tmp_path, dtype,
                                                   row_bytes):
    from repro.obs import trace as obs_trace
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
    x, q, _ = data
    cfg = dataclasses.replace(CONFIGS["lsh"], dataset_dtype=dtype)
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=16, bucket_min=16, warm_buckets=False,
                         cand_bucket_min=64, hedge_ms=1e9),
        dataset=jnp.asarray(x), key=KEY)
    engine.query_batch(q)
    obs_trace.flush()
    spans = [json.loads(line) for path in tmp_path.glob("*.jsonl")
             for line in path.read_text().splitlines()]
    got = {r["args"]["row_bytes"] for r in spans
           if r["name"] == "phase_b_rerank"}
    assert got == {row_bytes}


def test_phase_b_span_carries_the_tombstone_selection(data, monkeypatch,
                                                      tmp_path):
    """``tomb_select`` is k + T, T the tombstone array's length: 1 (the
    pad) before any delete, 8 after five."""
    from repro.obs import trace as obs_trace
    monkeypatch.setenv("REPRO_TRACE", "1")
    x, q, _ = data
    engine = AnnServingEngine(
        CONFIGS["lsh"], ServeConfig(batch_size=16, bucket_min=16,
                                    warm_buckets=False, cand_bucket_min=64,
                                    tombstone_watermark=2.0, hedge_ms=1e9),
        dataset=jnp.asarray(x), key=KEY)
    for dead, t in (((), 1), ((3, 5, 8, 13, 21), 8)):
        engine.delete(np.asarray(dead, np.int64))
        monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path / str(t)))
        engine.query_batch(q)
        obs_trace.flush()
        spans = [json.loads(line)
                 for path in (tmp_path / str(t)).glob("*.jsonl")
                 for line in path.read_text().splitlines()]
        assert {r["args"]["tomb_select"] for r in spans
                if r["name"] == "phase_b_rerank"} == {K + t}
