"""End-to-end index behaviour: exactness on self-queries, recall on clustered
data, dedup, sentinel handling, baselines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as bl
from repro.core import pipeline as pipe
from repro.core.baselines import l1_distance_chunked
from repro.core.index import (IndexConfig, build_index, probe_index,
                              query_index)
from repro.data import ann_synthetic as ds

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def clustered():
    spec = ds.DatasetSpec("t", n=8000, dim=32, universe=128, num_clusters=16)
    data = ds.make_dataset(spec)
    queries = ds.make_queries(spec, data, 48)
    return jnp.asarray(data), jnp.asarray(queries)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(num_tables=6, num_hashes=10, width=40, num_probes=100,
                       candidate_cap=64, universe=128, k=10, rerank_chunk=256)


@pytest.fixture(scope="module")
def state(cfg, clustered):
    return build_index(cfg, KEY, clustered[0])


def test_self_query_exact(cfg, state, clustered):
    data, _ = clustered
    d, i = query_index(cfg, state, data[:16])
    np.testing.assert_array_equal(np.asarray(d[:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(i[:, 0]), np.arange(16))


def test_results_sorted_and_consistent(cfg, state, clustered):
    data, queries = clustered
    d, i = query_index(cfg, state, queries)
    dn = np.asarray(d)
    assert (np.diff(dn, axis=1) >= 0).all()
    # distances actually match the returned points
    ii = np.asarray(i)
    for r in range(5):
        for c in range(3):
            if ii[r, c] >= 0:
                true = np.abs(np.asarray(data[ii[r, c]], np.int64) -
                              np.asarray(queries[r], np.int64)).sum()
                assert true == dn[r, c]


def test_no_duplicate_results(cfg, state, clustered):
    _, queries = clustered
    _, i = query_index(cfg, state, queries)
    for row in np.asarray(i):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_recall_beats_single_probe(cfg, clustered):
    data, queries = clustered
    td, ti = bl.brute_force_l1(data, queries, 10)
    mp_state = build_index(cfg, KEY, data)
    d, i = query_index(cfg, mp_state, queries)
    r_mp = bl.recall(np.asarray(i), np.asarray(ti))
    sp = bl.single_probe_config(cfg)
    sp_state = build_index(sp, KEY, data)
    d2, i2 = query_index(sp, sp_state, queries)
    r_sp = bl.recall(np.asarray(i2), np.asarray(ti))
    assert r_mp > r_sp + 0.2        # the paper's headline effect
    assert r_mp > 0.6
    ratio = bl.overall_ratio(np.asarray(d), np.asarray(td))
    assert 1.0 <= ratio < 1.2


def test_row_offset_global_ids(cfg, clustered):
    data, queries = clustered
    st = build_index(cfg, KEY, data, row_offset=1000)
    _, i = query_index(cfg, st, data[:4])
    np.testing.assert_array_equal(np.asarray(i[:, 0]), 1000 + np.arange(4))


def test_candidate_sentinel_handling(cfg, state, clustered):
    data, queries = clustered
    pk, lo, occ, _ = probe_index(cfg, state, queries[:8])
    n = data.shape[0]
    ids, _ = pipe.stage_fused_probe(cfg, state.sorted_keys, state.sorted_ids,
                                    pk, n, extents=(lo, occ))
    a = np.asarray(ids)
    assert a.max() <= n
    # rerank with an all-sentinel row -> id -1, huge dist
    all_bad = jnp.full((1, 16), n, jnp.int32)
    d, i = l1_distance_chunked(data, queries[:1], all_bad, 5, 8)
    assert (np.asarray(i) == -1).all()


def test_cp_lsh_family(clustered):
    data, queries = clustered
    cfg = IndexConfig(num_tables=6, num_hashes=10, width=8000, num_probes=0,
                      candidate_cap=64, universe=128, k=10, family="cauchy")
    st = build_index(cfg, KEY, data)
    d, i = query_index(cfg, st, data[:8])
    assert (np.asarray(d[:, 0]) == 0).all()


def test_srs_baseline(clustered):
    data, queries = clustered
    td, ti = bl.brute_force_l1(data, queries, 10)
    srs = bl.build_srs(jax.random.PRNGKey(5), data, 8)
    d, i = bl.query_srs(srs, queries, 512, 10)
    r = bl.recall(np.asarray(i), np.asarray(ti))
    assert r > 0.5  # brute-force projected t-NN is a strong SRS upper bound


def test_brute_force_is_exact(clustered):
    data, _ = clustered
    d, i = bl.brute_force_l1(data, data[:4], 3)
    np.testing.assert_array_equal(np.asarray(d[:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(i[:, 0]), np.arange(4))
