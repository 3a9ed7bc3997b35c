"""Probe front-end: gather parity + compaction properties (§8).

Three layers of pinning:
  1. gather parity — ``fused_probe_xla`` == ``kops.fused_probe`` ==
     ``ref.fused_probe`` == a plain-python oracle, across
     hypothesis-driven (Q, L, P, C, n) shapes and the named edge cases
     (empty buckets, all-sentinel queries, single-point segments,
     duplicate candidates across tables, truncating buckets);
  2. pipeline parity — phase A + the gather feed the rerank the oracle's
     candidate set, the full-slab ``query_index`` equals the frozen seed
     implementation, and every covering rung gives the full slab's bits;
  3. serving parity — the engine returns the bits of the full-slab query,
     with zero unplanned recompiles after the (batch-bucket x
     candidate-bucket) warmup grid.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pipeline as pipe
from repro.core.index import (IndexConfig, build_index, probe_index,
                              query_index)
from repro.core.segments import SegmentedIndex
from repro.data import ann_synthetic as ds
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.fused_probe import compact_gather_xla, fused_probe_xla
from test_segments import _seed_query_index

KEY = jax.random.PRNGKey(0)


def np_fused_probe(keys, ids, pk, cap, cbucket):
    """Plain-python oracle: per-(table, probe) bisect + clamped append."""
    l, n = keys.shape
    q, _, p = pk.shape
    out = np.full((q, cbucket), n, np.int32)
    counts = np.zeros((q,), np.int32)
    for qq in range(q):
        buf = []
        for t in range(l):
            for j in range(p):
                lo = int(np.searchsorted(keys[t], pk[qq, t, j], "left"))
                hi = int(np.searchsorted(keys[t], pk[qq, t, j], "right"))
                buf.extend(ids[t, lo:lo + min(hi - lo, cap)].tolist())
        counts[qq] = len(buf)
        out[qq, :min(len(buf), cbucket)] = buf[:cbucket]
    return out, counts


def _assert_all_equal(keys, ids, pk, cap, cbucket):
    keys_j, ids_j, pk_j = map(jnp.asarray, (keys, ids, pk))
    want_ids, want_cnt = np_fused_probe(keys, ids, pk, cap, cbucket)
    for name, got in {
        "xla": fused_probe_xla(keys_j, ids_j, pk_j, cap, cbucket),
        "ref": ref.fused_probe(keys_j, ids_j, pk_j, cap, cbucket),
        "ops": kops.fused_probe(keys_j, ids_j, pk_j, cap, cbucket),
    }.items():
        np.testing.assert_array_equal(np.asarray(got[0]), want_ids,
                                      err_msg=f"{name} ids")
        np.testing.assert_array_equal(np.asarray(got[1]), want_cnt,
                                      err_msg=f"{name} counts")


# ---------------------------------------------------------------------------
# 1. gather parity
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fused_probe_property_parity(data):
    """Every gather entry point agrees with the python oracle on random
    shapes/keys."""
    l = data.draw(st.integers(1, 5), label="L")
    n = data.draw(st.integers(0, 200), label="n")
    p = data.draw(st.integers(1, 12), label="P")
    cap = data.draw(st.integers(1, 16), label="cap")
    q = data.draw(st.integers(1, 9), label="Q")
    cbucket = data.draw(st.sampled_from([1, 8, 64, 300]), label="cbucket")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    rng = np.random.default_rng(seed)
    # small key universe -> many duplicate keys (occupied buckets); probe
    # keys drawn wider -> plenty of misses (empty buckets) too
    universe = max(1, n // 2)
    keys = np.sort(rng.integers(0, universe + 1, (l, n)).astype(np.uint32),
                   axis=-1)
    ids = (np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
           if n else np.zeros((l, 0), np.int32))
    pk = rng.integers(0, universe + 3, (q, l, p)).astype(np.uint32)
    _assert_all_equal(keys, ids, pk, cap, cbucket)


@pytest.mark.parametrize("n", [0, 1])
def test_tiny_segments(n):
    """Zero- and single-point segments (the compaction's best case)."""
    l, p, q = 3, 4, 5
    keys = np.zeros((l, n), np.uint32)
    ids = np.zeros((l, n), np.int32)
    rng = np.random.default_rng(0)
    pk = rng.integers(0, 3, (q, l, p)).astype(np.uint32)
    pk[0] = 0   # probe key that hits the single bucket in every table
    _assert_all_equal(keys, ids, pk, cap=4, cbucket=32)


def test_all_sentinel_query_and_uint32_extremes():
    """Probe keys that match nothing -> all-sentinel row, count 0; the
    UINT32_MAX probe key, above every key, counts nothing."""
    rng = np.random.default_rng(1)
    l, n, p = 2, 150, 6
    keys = np.sort(rng.integers(10, 50, (l, n)).astype(np.uint32), axis=-1)
    ids = np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
    pk = np.full((3, l, p), 5, np.uint32)        # all below every key
    pk[1] = 0xFFFFFFFF                           # above every key
    pk[2, 0, 0] = keys[0, 0]                     # one hit
    _assert_all_equal(keys, ids, pk, cap=8, cbucket=64)
    out, cnt = np_fused_probe(keys, ids, pk, 8, 64)
    assert cnt[0] == 0 and cnt[1] == 0 and (out[0] == n).all()


def test_duplicate_candidates_across_tables_survive():
    """A point present in every table's probed bucket appears once per
    (table, probe) hit — compaction must NOT dedup (the rerank owns that),
    or the compacted slab would diverge from the full slab's candidates."""
    l, n, p = 4, 8, 1
    keys = np.zeros((l, n), np.uint32)           # one bucket per table
    ids = np.tile(np.arange(n, dtype=np.int32), (l, 1))
    pk = np.zeros((1, l, p), np.uint32)
    out, cnt = np_fused_probe(keys, ids, pk, cap=n, cbucket=64)
    assert cnt[0] == l * n                        # every table contributes
    _assert_all_equal(keys, ids, pk, cap=n, cbucket=64)


def test_truncating_bucket_is_prefix():
    """A binding cbucket keeps exactly the first cbucket candidates in
    (table, probe, offset) order and still reports the full count."""
    rng = np.random.default_rng(2)
    l, n, p, cap = 3, 100, 5, 8
    keys = np.sort(rng.integers(0, 20, (l, n)).astype(np.uint32), axis=-1)
    ids = np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
    pk = rng.integers(0, 22, (4, l, p)).astype(np.uint32)
    wide, cnt_w = np_fused_probe(keys, ids, pk, cap, 512)
    for cb in (1, 5, 17):
        narrow, cnt_n = np_fused_probe(keys, ids, pk, cap, cb)
        np.testing.assert_array_equal(cnt_n, cnt_w)
        np.testing.assert_array_equal(narrow, wide[:, :cb])
        _assert_all_equal(keys, ids, pk, cap, cb)


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_two_level_cap_matches_oracle_prefix(data):
    """Phase-A extents computed at the full cap, gathered at a tighter
    ``c_cap``, must equal the oracle run directly at ``c_cap`` — the
    sorted-order-prefix truncation composes across caps, which is what
    lets the overflow rung reuse phase A (§9).  Includes the
    all-points-in-one-bucket worst case."""
    l = data.draw(st.integers(1, 4), label="L")
    n = data.draw(st.integers(1, 150), label="n")
    p = data.draw(st.integers(1, 8), label="P")
    cap = data.draw(st.integers(2, 16), label="cap")
    c_cap = min(data.draw(st.integers(1, 16), label="c_cap"), cap)
    q = data.draw(st.integers(1, 6), label="Q")
    cbucket = data.draw(st.sampled_from([1, 16, 128]), label="cbucket")
    one_bucket = data.draw(st.booleans(), label="one_bucket")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    rng = np.random.default_rng(seed)
    if one_bucket:
        keys = np.zeros((l, n), np.uint32)
        pk = np.zeros((q, l, p), np.uint32)
    else:
        universe = max(1, n // 2)
        keys = np.sort(rng.integers(0, universe + 1, (l, n))
                       .astype(np.uint32), axis=-1)
        pk = rng.integers(0, universe + 3, (q, l, p)).astype(np.uint32)
    ids = np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
    keys_j, ids_j, pk_j = map(jnp.asarray, (keys, ids, pk))
    lo, occ, _ = kops.probe_extents(keys_j, pk_j, cap)
    got_ids, got_cnt = kops.fused_probe(keys_j, ids_j, pk_j, c_cap, cbucket,
                                        extents=(lo, occ))
    want_ids, want_cnt = np_fused_probe(keys, ids, pk, c_cap, cbucket)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_array_equal(np.asarray(got_cnt), want_cnt)


def _miss_mask(miss, q, lp):
    """Which (query, probe) buckets come out empty: a run of them at a
    row's head, middle or tail (such a run shares one start), or all of
    query 0's."""
    mask = np.zeros((q, lp), bool)
    run = max(2, lp // 3)
    if miss == "head":
        mask[:, :run] = True
    elif miss == "middle":
        mask[:, (lp - run) // 2:(lp - run) // 2 + run] = True
    elif miss == "tail":
        mask[:, -run:] = True
    elif miss == "row":
        mask[0] = True
    return mask


@pytest.mark.parametrize("l,n,p,q,cap,c_cap,cbucket,miss", [
    (3, 60, 5, 4, 6, 6, 256, "head"),
    (3, 60, 5, 4, 6, 6, 256, "middle"),
    (3, 60, 5, 4, 6, 6, 256, "tail"),
    (2, 80, 6, 3, 8, 8, 5, "none"),        # cbucket below L*P
    (2, 80, 6, 3, 8, 8, 20, "middle"),     # cbucket below the total
    (3, 60, 5, 4, 8, 2, 256, "tail"),      # c_cap below cap
    (3, 60, 5, 4, 6, 3, 9, "head"),        # both truncations at once
    (3, 60, 5, 4, 6, 6, 64, "row"),        # an all-empty query row
    (2, 1, 4, 3, 4, 4, 16, "middle"),      # n = 1
], ids=["empty-head", "empty-middle", "empty-tail", "cbucket-below-lp",
        "cbucket-below-total", "c_cap-below-cap", "both-truncate",
        "empty-row", "n1"])
def test_extents_gather_matches_oracle(l, n, p, q, cap, c_cap, cbucket,
                                       miss):
    """The gather from phase-A extents maps every slot to its bucket the
    way the oracle appends them: runs of empty buckets sharing a start,
    a cbucket that truncates, a per-bucket ``c_cap`` under the extents'
    ``cap``."""
    rng = np.random.default_rng([l, n, p, q, cap, c_cap, cbucket, len(miss)])
    keys = np.sort(2 * rng.integers(0, max(1, n // 4) + 1, (l, n)),
                   axis=-1).astype(np.uint32)                    # even keys
    ids = np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
    hits = keys[np.arange(l)[None, :, None],
                rng.integers(0, n, (q, l, p))]
    # a miss is an odd key (falls between runs) or one past every key
    misses = np.where(rng.random((q, l, p)) < 0.5,
                      2 * rng.integers(0, n // 4 + 1, (q, l, p)) + 1,
                      2 * n + 7).astype(np.uint32)
    empty = _miss_mask(miss, q, l * p).reshape(q, l, p)
    pk = np.where(empty, misses, hits).astype(np.uint32)
    keys_j, ids_j, pk_j = map(jnp.asarray, (keys, ids, pk))
    lo, occ, _ = kops.probe_extents(keys_j, pk_j, cap)
    got_ids, got_cnt = kops.fused_probe(keys_j, ids_j, pk_j, c_cap, cbucket,
                                        extents=(lo, occ))
    want_ids, want_cnt = np_fused_probe(keys, ids, pk, c_cap, cbucket)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_array_equal(np.asarray(got_cnt), want_cnt)
    if miss == "row":
        assert want_cnt[0] == 0 and (want_ids[0] == n).all()
    if cbucket < l * p:
        assert want_cnt.max() > cbucket         # the case truncates


def test_compact_gather_one_slot_gather_at_serving_shape():
    """At the serving shape (64 queries, 8 x 801 probes, the 131,072 rung,
    1M rows) the compiled gather holds one gather over the slots: the
    ``sorted_ids`` take.  A per-slot search over the probe extents would
    add one per step."""
    q, l, p, n, cbucket = 64, 8, 801, 1_000_000, 131072
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    hlo = compact_gather_xla.lower(
        spec(l, n), spec(q, l * p), spec(q, l * p), p=p, cbucket=cbucket,
        cap=128).compile().as_text()
    slot_gathers = [
        dims for dims in re.findall(r"= s32\[([0-9,]+)\]\S* gather\(", hlo)
        if math.prod(int(d) for d in dims.split(",")) == q * cbucket]
    assert len(slot_gathers) == 1, slot_gathers


def test_occ_histogram_and_quantile():
    """The build-time histogram counts each distinct bucket once in its
    ceil-log2 occupancy bin; ``occupancy_quantile`` reads pow-2 caps off
    it (bucket-weighted, so hot buckets can't move low quantiles)."""
    from repro.core.index import OCC_HIST_BINS, _occ_histogram, _run_lengths
    keys = jnp.asarray(np.asarray([[1, 1, 1, 2, 3, 3, 3, 3]], np.uint32))
    hist = np.asarray(_occ_histogram(keys, _run_lengths(keys)))
    assert hist.shape == (1, OCC_HIST_BINS)
    assert hist.sum() == 3                  # three distinct buckets
    assert hist[0, 0] == 1                  # occ 1 -> bin 0
    assert hist[0, 2] == 2                  # occ 3, 4 -> bin 2 ((2, 4])
    assert pipe.occupancy_quantile(hist, 1.0) == 4
    assert pipe.occupancy_quantile(hist, 0.01) == 1
    assert pipe.occupancy_quantile(np.zeros((2, 32), np.int32), 0.999) == 1


def test_extents_occ_from_parity(cfg, small):
    """The build-time run-length shortcut (IndexState.occ_from) must
    produce bit-identical extents to the two-sided-search fallback —
    including misses, run starts, and the clamp."""
    data, queries = small
    state = build_index(cfg, KEY, data)
    bucket, x_neg = pipe.stage_hash(cfg, state.params, queries)
    pk = pipe.stage_probe_keys(
        cfg, state.params, state.template, bucket, x_neg)
    plain = pipe.stage_probe_extents(cfg, state.sorted_keys, pk)
    fast = pipe.stage_probe_extents(cfg, state.sorted_keys, pk,
                                    state.occ_from)
    for a, b in zip(plain, fast):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # occ_from's max IS the occupancy the oracle derives from raw keys
    assert (pipe.max_bucket_occupancy(state.sorted_keys)
            == pipe.max_bucket_occupancy(state.sorted_keys, state.occ_from))


def test_counts_match_stage_probe_counts():
    """``stage_probe_counts`` (the cheap phase-A counts) must equal the
    counts the fused gather reports — or a picked bucket could truncate."""
    rng = np.random.default_rng(3)
    l, n, p, cap = 4, 120, 7, 6
    keys = np.sort(rng.integers(0, 30, (l, n)).astype(np.uint32), axis=-1)
    ids = np.stack([rng.permutation(n) for _ in range(l)]).astype(np.int32)
    pk = rng.integers(0, 33, (6, l, p)).astype(np.uint32)
    cfg = IndexConfig(num_tables=l, num_probes=p - 1, candidate_cap=cap)
    counts = pipe.stage_probe_counts(
        cfg, jnp.asarray(keys), jnp.asarray(pk))
    _, kernel_counts = fused_probe_xla(
        jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(pk), cap, 64)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(kernel_counts))


# ---------------------------------------------------------------------------
# 2. pipeline parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    spec = ds.DatasetSpec("probe", n=2500, dim=16, universe=64,
                          num_clusters=8)
    data = ds.make_dataset(spec)
    queries = ds.make_queries(spec, data, 12)
    return jnp.asarray(data), jnp.asarray(queries)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=30,
                       candidate_cap=32, universe=64, k=8, rerank_chunk=128)


def _one_segment(cfg, state):
    """A one-segment index over ``state``, gids = rows."""
    n = state.dataset.shape[0]
    return SegmentedIndex.from_checkpoint(
        cfg, state, jnp.arange(n, dtype=jnp.int32), n)


@pytest.mark.parametrize("dataset_dtype", ["int32", "uint8"])
def test_query_index_probe_impls_bit_identical(cfg, small, dataset_dtype):
    """The full-slab ``query_index`` (phase A + phase B in one program)
    equals the frozen seed implementation, whatever the rows are stored
    as; the seed reads int32 rows."""
    data, queries = small
    seed = _seed_query_index(cfg, build_index(cfg, KEY, data), queries)
    cfg_s = dataclasses.replace(cfg, dataset_dtype=dataset_dtype)
    got = query_index(cfg_s, build_index(cfg_s, KEY, data), queries)
    np.testing.assert_array_equal(np.asarray(seed[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(seed[1]), np.asarray(got[1]))


def test_query_index_compact_bit_identical(cfg, small):
    """Phase B at the rung the counts pick gives the full slab's bits."""
    data, queries = small
    state = build_index(cfg, KEY, data)
    d0, i0 = query_index(cfg, state, queries)
    for floor in (16, 64, 4096):   # tiny, typical, bigger-than-worst-case
        d1, i1, used = _one_segment(cfg, state).query_compact(
            queries, floor=floor)
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_probe_candidates_same_set_after_dedup(cfg, small):
    """Phase A + the full-slab gather hand the rerank the oracle's
    candidates: the same set per query once duplicates are dropped."""
    data, queries = small
    state = build_index(cfg, KEY, data)
    n = data.shape[0]
    pk, lo, occ, counts = probe_index(cfg, state, queries)
    ids, got_counts = pipe.stage_fused_probe(
        cfg, state.sorted_keys, state.sorted_ids, pk, n,
        extents=(lo, occ))
    full = cfg.num_tables * cfg.probes_per_table * cfg.candidate_cap
    want, want_counts = np_fused_probe(
        np.asarray(state.sorted_keys), np.asarray(state.sorted_ids),
        np.asarray(pk), cfg.candidate_cap, full)
    np.testing.assert_array_equal(np.asarray(got_counts), want_counts)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    ids = np.asarray(ids)
    for row, want_row in zip(ids, want):
        np.testing.assert_array_equal(np.unique(row[row < n]),
                                      np.unique(want_row[want_row < n]))
    assert (want_counts > np.asarray([len(np.unique(r[r < n]))
                                      for r in ids])).any()  # dups exist


def test_segmented_query_compact_bit_identical(cfg, small):
    """Through sealed segments, the delta and deletes, every segment at
    the rung its counts pick gives the bits of every segment at its full
    non-truncating width (a single-level ladder, floor past the top)."""
    data, queries = small
    data_np = np.asarray(data)

    def mutated(cap_quantile):
        idx = SegmentedIndex.from_dataset(
            cfg, KEY, jnp.asarray(data_np[:1500]), delta_cap=256,
            cap_quantile=cap_quantile)
        idx.insert(data_np[1500:])             # seals segments + delta
        idx.delete([1, 2, 2000])
        return idx

    wide = mutated(1.0)
    d0, i0, used0 = wide.query_compact(queries, floor=1 << 30)
    assert all(cb == seg.ctot_cap
               for (_, cb, _), seg in zip(used0, wide.segments))
    idx = mutated(0.999)
    d1, i1, used = idx.query_compact(queries)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    full = cfg.num_tables * cfg.probes_per_table * cfg.candidate_cap
    assert used and all(cb <= full for _, cb, _ in used)
    ladders = idx.candidate_ladders()
    assert len(ladders) == idx.num_segments
    for (size, cb, cc), ladder in zip(used, ladders):
        assert (cb, cc) in ladder


def test_max_bucket_occupancy():
    keys = np.asarray([[1, 1, 1, 2, 3], [4, 5, 5, 6, 7]], np.uint32)
    assert pipe.max_bucket_occupancy(keys) == 3
    assert pipe.max_bucket_occupancy(np.zeros((2, 0), np.uint32)) == 1
    assert pipe.max_bucket_occupancy(np.asarray([[1, 2, 3]])) == 1
    cfg = IndexConfig(candidate_cap=2)
    assert pipe.oracle_candidate_cap(cfg, keys) == 3


def test_candidate_ladder_and_bucket():
    assert pipe.candidate_ladder(1000, floor=64) == (64, 128, 256, 512, 1000)
    assert pipe.candidate_ladder(64, floor=64) == (64,)
    assert pipe.candidate_ladder(40, floor=64) == (40,)
    assert pipe.candidate_bucket(0, 1000, 64) == 64
    assert pipe.candidate_bucket(129, 1000, 64) == 256
    assert pipe.candidate_bucket(900, 1000, 64) == 1000


def test_candidate_ladder_and_bucket_edges():
    """Degenerate ladders the batch-rung pick must survive: a cap below
    the floor, a cap of one, and counts landing exactly on a pow-2."""
    assert pipe.candidate_ladder(1, floor=64) == (1,)
    assert pipe.candidate_ladder(256, floor=64) == (64, 128, 256)
    assert pipe.candidate_bucket(0, 1, 64) == 1
    assert pipe.candidate_bucket(500, 1, 64) == 1       # count >> cap
    assert pipe.candidate_bucket(7, 40, 64) == 40       # floor >= cap
    assert pipe.candidate_bucket(64, 1000, 64) == 64    # exact pow-2
    assert pipe.candidate_bucket(128, 1000, 64) == 128
    assert pipe.candidate_bucket(1000, 1000, 64) == 1000


def test_rung_ladder_and_pick_rung():
    """Two-level ladder (§9): without a normal top it degenerates to the
    single-level ladder; with one, exactly one overflow rung is appended
    and every ``pick_rung`` result is a ladder member."""
    single = tuple((b, None) for b in pipe.candidate_ladder(1000, 64))
    assert pipe.rung_ladder(1000, floor=64) == single
    assert pipe.rung_ladder(1000, 64, ctot_norm=2048, c_cap=8) == single
    esc = pipe.rung_ladder(4096, 64, ctot_norm=512, c_cap=8,
                           overflow="escalate")
    assert esc == ((64, None), (128, None), (256, None), (512, None),
                   (4096, None))
    tr = pipe.rung_ladder(4096, 64, ctot_norm=512, c_cap=8,
                          overflow="truncate")
    assert tr == ((64, None), (128, None), (256, None), (512, None),
                  (512, 8))
    with pytest.raises(ValueError):
        pipe.rung_ladder(4096, 64, ctot_norm=512, c_cap=8, overflow="bogus")
    for count in (0, 63, 64, 500, 512, 513, 4000, 9999):
        for ovf, ladder in (("escalate", esc), ("truncate", tr)):
            cb, cc, over = pipe.pick_rung(count, 4096, 64, 512, 8, ovf)
            assert (cb, cc) in ladder
            assert over == (count > 512)
            assert cb >= min(count, 4096) or cc is not None


def test_segmented_truncate_overflow_stats(cfg, small):
    """Forcing every batch past the normal ladder: the truncate rung stays
    at ``ctot_norm`` width with the per-bucket ``c_norm`` applied, and the
    stats dict records the overflow hit + truncated-candidate count."""
    data, queries = small
    idx = SegmentedIndex.from_dataset(cfg, KEY, data)
    for seg in idx.segments:
        idx._ensure_caps(seg)
        seg.ctot_norm, seg.c_norm = 64, 1
    stats = {"overflow_hits": 0, "truncated_candidates": 0}
    d, i, used = idx.query_compact(queries, overflow="truncate",
                                   stats=stats)
    assert d.shape == i.shape == (queries.shape[0], cfg.k)
    assert stats["overflow_hits"] == len(used)
    assert stats["truncated_candidates"] > 0
    assert all(cb == 64 and cc == 1 for _, cb, cc in used)
    # escalate on the same forced caps falls back to the exact rung
    d0, i0 = query_index(cfg, idx.segments[0].state, queries)
    d1, i1, used_e = idx.query_compact(queries, overflow="escalate")
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    assert all(cc is None for _, _, cc in used_e)


def test_skewed_dataset_caps_below_full():
    """On duplicated-point data the histogram quantile must land far
    below the hot-bucket occupancy, and the derived ladder must carry the
    overflow rung (the whole point of two-level capping).  At test scale
    the hot buckets are a larger share of distinct buckets than in
    production, so the quantile is p99 rather than the serving-default
    p99.9."""
    spec = ds.DatasetSpec("skewtest", n=2000, dim=16, universe=256,
                          num_clusters=12)
    cfg = IndexConfig(num_tables=4, num_hashes=8, width=16, num_probes=30,
                      candidate_cap=256, universe=256, k=8,
                      rerank_chunk=128)
    data = jnp.asarray(ds.make_skewed_dataset(spec, zipf_s=0.5,
                                              dup_frac=0.3, num_hot=2))
    idx = SegmentedIndex.from_dataset(cfg, KEY, data, cap_quantile=0.99)
    seg = idx.segments[0]
    idx._ensure_caps(seg)
    occ_max = pipe.max_bucket_occupancy(seg.state.sorted_keys,
                                        seg.state.occ_from)
    assert occ_max >= 200                     # the dups really are hot
    assert seg.c_norm < occ_max
    assert seg.ctot_norm < seg.ctot_cap
    ladder = idx.candidate_ladders(overflow="truncate")[0]
    assert ladder[-1] == (seg.ctot_norm, seg.c_norm)
    summ = idx.skew_summary()[0]
    assert summ["occ_quantiles"]["max"] == occ_max
    assert summ["occ_quantiles"]["p50"] <= summ["occ_quantiles"]["p999"]


# ---------------------------------------------------------------------------
# 3. serving parity
# ---------------------------------------------------------------------------

def test_engine_compact_probe_smoke(cfg, small):
    from repro.serve.engine import AnnServingEngine, ServeConfig

    data, queries = small
    qn = np.asarray(queries)
    eng_c = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, bucket_min=2, delta_cap=64,
                         cand_bucket_min=64, persistent_cache=False), data)
    cold_after_warm = eng_c.stats["bucket_cold_hits"]
    eng_c.submit(qn[:3]); eng_c.submit(qn[3:])
    dc, ic = eng_c.drain()
    df, if_ = query_index(cfg, eng_c.index.segments[0].state, queries)
    np.testing.assert_array_equal(dc, np.asarray(df))
    np.testing.assert_array_equal(ic, np.asarray(if_))
    # the (batch-bucket x candidate-bucket) warmup grid covered every live
    # shape: no unplanned recompiles
    assert eng_c.stats["bucket_cold_hits"] == cold_after_warm
    s = eng_c.summary()
    assert s["cand_buckets"] and "compile_cache" in s
    # skew observability (§9): policy knobs + per-segment occupancy view
    sk = s["skew"]
    assert sk["cand_overflow"] == "escalate"
    assert sk["cand_cap_quantile"] == 0.999
    assert sk["overflow_hits"] == eng_c.stats["overflow_hits"]
    assert sk["truncated_candidates"] == 0     # escalate never truncates
    assert len(sk["segments"]) == eng_c.index.num_segments
    assert all("occ_quantiles" in e for e in sk["segments"] if e["size"])
