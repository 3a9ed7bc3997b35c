"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import walks as wl
from repro.kernels import ops, ref


@pytest.mark.parametrize("q,n,m", [(1, 1, 1), (7, 33, 17), (16, 128, 96),
                                   (130, 257, 100)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16])
def test_l1_distance_sweep(q, n, m, dtype):
    rng = np.random.default_rng(q * 1000 + n)
    qs = jnp.asarray(rng.integers(0, 100, (q, m))).astype(dtype)
    xs = jnp.asarray(rng.integers(0, 100, (n, m))).astype(dtype)
    got = ops.l1_distance(qs, xs, bq=8, bn=32, bm=64)
    want = ref.l1_distance(qs, xs)
    if dtype == jnp.int32:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   np.asarray(want, np.float64), rtol=2e-2)


@pytest.mark.parametrize("q,c,m", [(3, 5, 9), (16, 33, 64), (9, 128, 200)])
def test_l1_rows_sweep(q, c, m):
    rng = np.random.default_rng(c)
    qs = jnp.asarray(rng.integers(0, 200, (q, m)).astype(np.int32))
    rows = jnp.asarray(rng.integers(0, 200, (q, c, m)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.l1_distance_rows(qs, rows, bq=4, bm=64)),
        np.asarray(ref.l1_distance_rows(qs, rows)))


@pytest.mark.parametrize("f,m,u2,n", [(3, 2, 4, 5), (17, 8, 32, 40),
                                      (64, 16, 128, 20)])
def test_rw_hash_sweep(f, m, u2, n):
    wt = wl.make_walks(jax.random.PRNGKey(f), f, m, 2 * u2)
    rng = np.random.default_rng(n)
    pts = jnp.asarray((rng.integers(0, u2 + 1, (n, m)) * 2).astype(np.int32))
    got = ops.rw_hash(wt.pairs, pts, bn=8, bf=8, bi=2)
    want = ref.rw_hash(wt.pairs, pts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and equals the paper's prefix-table semantics
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(wl.eval_prefix(wt, pts)))


@settings(max_examples=20, deadline=None)
@given(q=st.integers(1, 9), k=st.integers(1, 33), seed=st.integers(0, 999))
def test_topk_merge_property(q, k, seed):
    rng = np.random.default_rng(seed)
    da = np.sort(rng.integers(0, 500, (q, k)).astype(np.int32), axis=-1)
    db = np.sort(rng.integers(0, 500, (q, k)).astype(np.int32), axis=-1)
    ia = rng.integers(0, 10_000, (q, k)).astype(np.int32)
    ib = rng.integers(0, 10_000, (q, k)).astype(np.int32)
    do, io = ops.topk_merge(*map(jnp.asarray, (da, ia, db, ib)))
    dr, _ = ref.topk_merge(*map(jnp.asarray, (da, ia, db, ib)))
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dr))
    # every returned (dist) must exist in the union with right multiplicity
    for r in range(q):
        union = np.concatenate([da[r], db[r]])
        got = np.asarray(do)[r]
        assert (np.sort(union)[:k] == got).all()


def test_topk_merge_ids_track_dists():
    da = jnp.asarray([[1, 5, 9]], jnp.int32); ia = jnp.asarray([[10, 50, 90]], jnp.int32)
    db = jnp.asarray([[2, 3, 4]], jnp.int32); ib = jnp.asarray([[20, 30, 40]], jnp.int32)
    do, io = ops.topk_merge(da, ia, db, ib)
    np.testing.assert_array_equal(np.asarray(do), [[1, 2, 3]])
    np.testing.assert_array_equal(np.asarray(io), [[10, 20, 30]])
