"""Rerank parity: the served rerank, the jnp oracle, and a sort-dedup +
scan + lax.top_k must agree bit-for-bit — including the adversarial cases
ISSUE 2 pins: all-sentinel candidate lists, Ctot < k, duplicate ids, tied
distances, Q=1 and Q not a multiple of any tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as pipe
from repro.core.baselines import l1_distance_chunked
from repro.core.index import IndexConfig, build_index, probe_index, query_index
from repro.kernels import ops, ref
from repro.kernels.fused_rerank import fused_rerank_xla

BIG = pipe.BIG_DIST


def _dedup(ids, n):
    """Sorted ids, invalid and repeated ones -> sentinel n: the unique
    candidates the exact scan needs."""
    ids = jnp.sort(jnp.where((ids < 0) | (ids > n), n, ids), axis=-1)
    dup = jnp.concatenate([jnp.zeros((ids.shape[0], 1), bool),
                           ids[:, 1:] == ids[:, :-1]], axis=-1)
    return jnp.where(dup, n, ids)


def _all_impls(dataset, queries, ids, k, chunk=16):
    """(name, (d, i)) for the served rerank, the oracle and the scan."""
    n = dataset.shape[0]
    return [
        ("oracle", ref.fused_rerank(dataset, queries, ids, k)),
        ("xla", fused_rerank_xla(dataset, queries, ids, k, chunk=chunk)),
        ("scan", l1_distance_chunked(
            dataset, queries, _dedup(ids, n), k, chunk)),
    ]


def _assert_all_equal(impls):
    ref_name, (rd, ri) = impls[0]
    for name, (d, i) in impls[1:]:
        np.testing.assert_array_equal(np.asarray(rd), np.asarray(d),
                                      err_msg=f"{name} vs {ref_name} dists")
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(i),
                                      err_msg=f"{name} vs {ref_name} ids")


@pytest.mark.parametrize("q,n,ctot,k,m", [
    (1, 40, 24, 5, 9),        # Q=1
    (5, 100, 67, 9, 17),      # non-multiple-of-tile Q and Ctot
    (7, 50, 3, 8, 12),        # Ctot < k
    (4, 30, 33, 1, 7),        # k=1
])
@pytest.mark.parametrize("dtype", [np.int32, np.int16])
def test_fused_shapes_sweep(q, n, ctot, k, m, dtype):
    rng = np.random.default_rng(q * 100 + ctot)
    dataset = jnp.asarray(rng.integers(0, 50, (n, m)).astype(dtype))
    queries = jnp.asarray(rng.integers(0, 50, (q, m)).astype(np.int32))
    ids = jnp.asarray(rng.integers(-1, n + 2, (q, ctot)).astype(np.int32))
    _assert_all_equal(_all_impls(dataset, queries, ids, k))


def test_fused_all_sentinel_rows():
    rng = np.random.default_rng(0)
    n, m, k = 20, 8, 6
    dataset = jnp.asarray(rng.integers(0, 9, (n, m)).astype(np.int32))
    queries = jnp.asarray(rng.integers(0, 9, (3, m)).astype(np.int32))
    ids = jnp.full((3, 16), n, jnp.int32)           # every slot invalid
    impls = _all_impls(dataset, queries, ids, k)
    _assert_all_equal(impls)
    d, i = impls[0][1]
    assert (np.asarray(d) == BIG).all() and (np.asarray(i) == -1).all()


def test_fused_duplicate_ids_take_one_slot():
    # one point appearing in many probe slots must produce ONE result: the
    # rerank takes the gather's slab as it is, duplicates included.
    rng = np.random.default_rng(1)
    dataset = jnp.asarray(rng.integers(0, 50, (6, 8)).astype(np.int32))
    ids = jnp.asarray([[2, 2, 2, 2, 4, 4, 6, 6]], jnp.int32)  # 6 == sentinel
    impls = _all_impls(dataset, dataset[:1], ids, 4, chunk=4)
    _assert_all_equal(impls)
    i = np.asarray(impls[0][1][1])[0]
    real = i[i >= 0]
    assert sorted(real.tolist()) == [2, 4]


def test_fused_tied_distances_deterministic():
    # constant dataset -> every candidate ties; the (dist, id) total order
    # pins the winners to the smallest unique ids, on every executor.
    n, m, k = 12, 4, 5
    dataset = jnp.full((n, m), 3, jnp.int32)
    queries = jnp.full((2, m), 1, jnp.int32)
    ids = jnp.asarray([[9, 7, 7, 11, 3, 9, 5, 3],
                       [10, 10, 10, 10, 2, 2, 2, 2]], jnp.int32)
    impls = _all_impls(dataset, queries, ids, k, chunk=4)
    _assert_all_equal(impls)
    d, i = (np.asarray(x) for x in impls[0][1])
    np.testing.assert_array_equal(i[0], [3, 5, 7, 9, 11])
    np.testing.assert_array_equal(i[1], [2, 10, -1, -1, -1])
    assert (d[i >= 0] == 2 * m).all()


def test_fused_duplicate_pressure_many_tiles():
    # duplicates of the global best spread across MANY scan chunks: one
    # copy each survives, wherever the copies fall.
    rng = np.random.default_rng(2)
    n, m, k = 64, 8, 8
    dataset = jnp.asarray(rng.integers(0, 100, (n, m)).astype(np.int32))
    queries = jnp.asarray(np.asarray(dataset[:2]))  # self-queries -> d=0 best
    ids = np.tile(np.arange(8, dtype=np.int32), (2, 16))  # every tile repeats
    ids = jnp.asarray(ids)
    impls = _all_impls(dataset, queries, ids, k, chunk=8)
    _assert_all_equal(impls)
    for row in np.asarray(impls[0][1][1]):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_fused_empty_dataset_and_empty_candidates():
    queries = jnp.zeros((2, 4), jnp.int32)
    d, i = ops.fused_rerank(jnp.zeros((0, 4), jnp.int32), queries,
                            jnp.zeros((2, 5), jnp.int32), 3)
    assert (np.asarray(d) == BIG).all() and (np.asarray(i) == -1).all()
    d, i = ops.fused_rerank(jnp.zeros((7, 4), jnp.int32), queries,
                            jnp.zeros((2, 0), jnp.int32), 3)
    assert (np.asarray(d) == BIG).all() and (np.asarray(i) == -1).all()


def test_stage_rerank_impls_bit_identical_end_to_end():
    # the rerank as the query path runs it, on the candidates phase A and
    # the gather hand it (duplicates included), against the oracle over
    # the same candidates; the whole query returns the same bits.
    from repro.data import ann_synthetic as ds
    spec = ds.DatasetSpec("fr", n=2000, dim=16, universe=64, num_clusters=6)
    data = jnp.asarray(ds.make_dataset(spec))
    queries = jnp.asarray(ds.make_queries(spec, np.asarray(data), 12))
    cfg = IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=20,
                      candidate_cap=16, universe=64, k=8, rerank_chunk=64)
    state = build_index(cfg, jax.random.PRNGKey(0), data)
    pk, lo, occ, _ = probe_index(cfg, state, queries)
    ids, _ = pipe.stage_fused_probe(cfg, state.sorted_keys, state.sorted_ids,
                                    pk, data.shape[0], extents=(lo, occ))
    rd, ri = ref.fused_rerank(state.dataset, queries, ids, cfg.k)
    sd, si = pipe.stage_rerank(cfg, state.dataset, queries, ids)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(sd))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(si))
    fd, fi = query_index(cfg, state, queries)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(fd))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(fi))


def test_packed_key_boundary_falls_back_exactly():
    # A candidate whose packed key d*P + pos would land exactly on the
    # INT32_MAX invalid sentinel must NOT be dropped: d_cap reserves the
    # sentinel, pushing this case onto the top_k fallback (regression for
    # an off-by-one caught in review).
    n, k = 512, 512                      # ctp == P == 512
    boundary = (2 ** 31 - 1) // 512      # old cap; key(pos=511) == INT32_MAX
    vals = np.zeros((n, 1), np.int32)
    vals[:, 0] = np.arange(n)            # id-sorted position == id
    vals[511, 0] = boundary
    dataset = jnp.asarray(vals)
    queries = jnp.zeros((1, 1), jnp.int32)
    ids = jnp.asarray(np.arange(n, dtype=np.int32)[None])
    rd, ri = ref.fused_rerank(dataset, queries, ids, k)
    xd, xi = fused_rerank_xla(dataset, queries, ids, k, chunk=512)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(xd))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(xi))
    assert np.asarray(xd)[0, -1] == boundary and np.asarray(xi)[0, -1] == 511
    # one notch below the boundary stays on the packed fast path, exactly
    vals[511, 0] = boundary - 512
    xd2, xi2 = fused_rerank_xla(jnp.asarray(vals), queries, ids, k, chunk=512)
    rd2, ri2 = ref.fused_rerank(jnp.asarray(vals), queries, ids, k)
    np.testing.assert_array_equal(np.asarray(rd2), np.asarray(xd2))
    np.testing.assert_array_equal(np.asarray(ri2), np.asarray(xi2))


def test_merge_backends_agree_on_tied_ids():
    # the merge op, the pipeline's pair merge, ref oracle, and concat
    # merge must return the SAME ids on tied distances (all lex on
    # (dist, id) — regression for a divergence caught in review).
    da = jnp.asarray([[5, 5]], jnp.int32); ia = jnp.asarray([[9, 10]], jnp.int32)
    db = jnp.asarray([[5, 5]], jnp.int32); ib = jnp.asarray([[1, 2]], jnp.int32)
    want_d, want_i = [[5, 5]], [[1, 2]]
    for name, (d, i) in [
        ("kernel", ops.topk_merge(da, ia, db, ib)),
        ("pair", pipe.stage_merge_pair(da, ia, db, ib)),
        ("ref", ref.topk_merge(da, ia, db, ib)),
        ("concat", pipe.stage_merge_concat(
            jnp.concatenate([da, db], -1), jnp.concatenate([ia, ib], -1), 2)),
    ]:
        np.testing.assert_array_equal(np.asarray(d), want_d, err_msg=name)
        np.testing.assert_array_equal(np.asarray(i), want_i, err_msg=name)
