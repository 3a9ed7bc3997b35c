"""Unit coverage for the query stages: duplicate candidates, tombstone
masking, and the topk_merge op's tie / all-invalid edge cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as pipe
from repro.core.index import (IndexConfig, build_index, probe_index,
                              query_index)
from repro.kernels import ops

BIG = pipe.BIG_DIST


# ---------------------------------------------------------------------------
# Duplicate candidates across tables/probes: the rerank takes the slab as
# the gather hands it and ranks each point once.
# ---------------------------------------------------------------------------

RERANK_CFG = IndexConfig(k=8, rerank_chunk=4)


def test_stage_dedup_maps_duplicates_to_sentinel():
    n = 10
    rng = np.random.default_rng(3)
    dataset = jnp.asarray(rng.integers(0, 50, (n, 8)), jnp.int32)
    ids = jnp.asarray([[3, 1, 3, 7, 1, 9, n, n],
                       [5, 5, 5, 5, n, n, n, n]], jnp.int32)
    d, i = (np.asarray(a) for a in
            pipe.stage_rerank(RERANK_CFG, dataset, dataset[:2], ids))
    # each real id exactly once, the rest the (BIG, -1) sentinel
    assert sorted(i[0][i[0] >= 0].tolist()) == [1, 3, 7, 9]
    assert (i[0] == -1).sum() == 4 and (d[0][i[0] < 0] == BIG).all()
    assert i[1][i[1] >= 0].tolist() == [5]
    assert (i[1] == -1).sum() == 7 and (d[1][i[1] < 0] == BIG).all()


def test_duplicate_candidates_reranked_once():
    # one real point appearing in many probe slots must produce ONE result
    rng = np.random.default_rng(0)
    dataset = jnp.asarray(rng.integers(0, 50, (6, 8)), jnp.int32)
    dup_ids = jnp.asarray([[2, 2, 2, 2, 4, 4, 6, 6]], jnp.int32)
    d, i = pipe.stage_rerank(RERANK_CFG, dataset, dataset[:1], dup_ids, k=4)
    i = np.asarray(i)[0]
    real = i[i >= 0]
    assert len(set(real.tolist())) == len(real)
    assert set(real.tolist()) == {2, 4}


def test_probe_candidates_unique_on_cloned_points():
    # identical points land in the same bucket of EVERY table and probe ->
    # maximal duplication pressure: the gather keeps every copy, the
    # query returns each clone once.
    cfg = IndexConfig(num_tables=4, num_hashes=6, width=16, num_probes=10,
                      candidate_cap=16, universe=32, k=5, rerank_chunk=64)
    point = (np.arange(8) * 2).astype(np.int32)
    data = jnp.asarray(np.tile(point, (5, 1)))     # 5 clones
    state = build_index(cfg, jax.random.PRNGKey(0), data)
    pk, lo, occ, _ = probe_index(cfg, state, data[:1])
    ids, _ = pipe.stage_fused_probe(cfg, state.sorted_keys, state.sorted_ids,
                                    pk, data.shape[0], extents=(lo, occ))
    ids = np.asarray(ids)[0]
    real = ids[ids < data.shape[0]]
    assert len(real) > len(set(real.tolist()))     # copies across tables
    assert set(real.tolist()) == {0, 1, 2, 3, 4}
    _, got = query_index(cfg, state, data[:1])
    assert sorted(np.asarray(got)[0].tolist()) == [0, 1, 2, 3, 4]


def test_stage_tombstone_masks_deleted_gids():
    # a selected (dist, gid) list of k + T = 4 entries, k = 3
    dists = jnp.asarray([[1, 2, 3, BIG]], jnp.int32)
    gids = jnp.asarray([[10, 12, 14, -1]], jnp.int32)
    tomb = jnp.asarray([12, np.iinfo(np.int32).max], jnp.int32)  # kill gid 12
    d, g = (np.asarray(a)[0] for a in
            pipe.stage_tombstone(dists, gids, tomb, 3))
    assert g.tolist() == [10, 14, -1]
    assert d.tolist() == [1, 3, BIG]
    # empty tombstone set (all padding) keeps the first k as they are
    pad = jnp.asarray([np.iinfo(np.int32).max], jnp.int32)
    d2, g2 = (np.asarray(a)[0] for a in
              pipe.stage_tombstone(dists, gids, pad, 3))
    assert g2.tolist() == [10, 12, 14]
    assert d2.tolist() == [1, 2, 3]


# ---------------------------------------------------------------------------
# topk_merge: ties and all-invalid inputs.
# ---------------------------------------------------------------------------

def _oracle_merge(da, ia, db, ib, k):
    cd = np.concatenate([da, db], axis=1)
    ci = np.concatenate([ia, ib], axis=1)
    order = np.argsort(cd, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(cd, order, axis=1), np.take_along_axis(ci, order, axis=1)


def test_topk_merge_all_ties():
    k = 8
    da = np.full((3, k), 7, np.int32)
    db = np.full((3, k), 7, np.int32)
    ia = np.arange(3 * k, dtype=np.int32).reshape(3, k)
    ib = ia + 100
    d, i = ops.topk_merge(jnp.asarray(da), jnp.asarray(ia),
                          jnp.asarray(db), jnp.asarray(ib))
    d, i = np.asarray(d), np.asarray(i)
    np.testing.assert_array_equal(d, 7)
    # every returned id is one of the tied inputs, no duplicates per row
    for r in range(3):
        ids = set(i[r].tolist())
        assert len(ids) == k
        assert ids <= set(ia[r].tolist()) | set(ib[r].tolist())


def test_topk_merge_partial_ties_match_oracle_dists():
    rng = np.random.default_rng(7)
    k = 16
    da = np.sort(rng.integers(0, 5, (9, k)).astype(np.int32), axis=1)  # ties
    db = np.sort(rng.integers(0, 5, (9, k)).astype(np.int32), axis=1)
    ia = rng.integers(0, 1000, (9, k)).astype(np.int32)
    ib = rng.integers(0, 1000, (9, k)).astype(np.int32)
    d, i = ops.topk_merge(jnp.asarray(da), jnp.asarray(ia),
                          jnp.asarray(db), jnp.asarray(ib))
    od, _ = _oracle_merge(da, ia, db, ib, k)
    np.testing.assert_array_equal(np.asarray(d), od)
    # each (dist, id) pair must come from an input pair
    pairs = set(zip(np.concatenate([da, db], 1).ravel().tolist(),
                    np.concatenate([ia, ib], 1).ravel().tolist()))
    got = set(zip(np.asarray(d).ravel().tolist(),
                  np.asarray(i).ravel().tolist()))
    assert got <= pairs


def test_topk_merge_all_invalid():
    k = 8
    da = np.full((4, k), BIG, np.int32)
    db = np.full((4, k), BIG, np.int32)
    ia = np.full((4, k), -1, np.int32)
    ib = np.full((4, k), -1, np.int32)
    d, i = ops.topk_merge(jnp.asarray(da), jnp.asarray(ia),
                          jnp.asarray(db), jnp.asarray(ib))
    np.testing.assert_array_equal(np.asarray(d), BIG)
    np.testing.assert_array_equal(np.asarray(i), -1)


def test_topk_merge_one_side_invalid():
    k = 8
    da = np.arange(k, dtype=np.int32)[None].repeat(2, 0)
    ia = np.arange(k, dtype=np.int32)[None].repeat(2, 0)
    db = np.full((2, k), BIG, np.int32)
    ib = np.full((2, k), -1, np.int32)
    d, i = ops.topk_merge(jnp.asarray(da), jnp.asarray(ia),
                          jnp.asarray(db), jnp.asarray(ib))
    np.testing.assert_array_equal(np.asarray(d), da)
    np.testing.assert_array_equal(np.asarray(i), ia)


@pytest.mark.parametrize("ties", ["few", "many"])
def test_stage_merge_pair_backends_agree_on_dists(ties):
    # distances from a wide range (ties rare) or a narrow one (most tie);
    # the pair merge keeps the oracle's k smallest, ascending, and breaks
    # ties by id like the concat merge
    rng = np.random.default_rng(1)
    k = 8
    hi = 100 if ties == "few" else 4
    da = np.sort(rng.integers(0, hi, (5, k)).astype(np.int32), axis=1)
    db = np.sort(rng.integers(0, hi, (5, k)).astype(np.int32), axis=1)
    ia = rng.integers(0, 1000, (5, k)).astype(np.int32)
    ib = rng.integers(0, 1000, (5, k)).astype(np.int32)
    d, i = pipe.stage_merge_pair(jnp.asarray(da), jnp.asarray(ia),
                                 jnp.asarray(db), jnp.asarray(ib))
    od, _ = _oracle_merge(da, ia, db, ib, k)
    np.testing.assert_array_equal(np.asarray(d), od)
    assert (np.diff(np.asarray(d), axis=1) >= 0).all()
    cd, ci = pipe.stage_merge_concat(
        jnp.concatenate([jnp.asarray(da), jnp.asarray(db)], axis=1),
        jnp.concatenate([jnp.asarray(ia), jnp.asarray(ib)], axis=1), k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ci))


def test_stage_merge_concat_matches_pairwise():
    rng = np.random.default_rng(2)
    k = 8
    lists = [(np.sort(rng.integers(0, 100, (4, k)).astype(np.int32), axis=1),
              rng.integers(0, 1000, (4, k)).astype(np.int32))
             for _ in range(3)]
    ds_ = jnp.asarray(np.concatenate([l[0] for l in lists], axis=1))
    is_ = jnp.asarray(np.concatenate([l[1] for l in lists], axis=1))
    cd, _ = pipe.stage_merge_concat(ds_, is_, k)
    d, i = map(jnp.asarray, lists[0])
    for dn, in_ in lists[1:]:
        d, i = pipe.stage_merge_pair(d, i, jnp.asarray(dn), jnp.asarray(in_))
    np.testing.assert_array_equal(np.asarray(cd), np.asarray(d))
