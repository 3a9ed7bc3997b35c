"""Tombstones checked after selection equal the per-slot mask before it.

The query paths rerank the k + T lex-(dist, id)-smallest unique candidates
(T the tombstone array's static length), map them to global ids, drop the
dead ones and keep the first k (``pipeline.rerank_candidates``).  The
reference here keeps the former order: every slot of the candidate slab is
looked up in the tombstone array and a dead one turned into the sentinel,
then the rerank selects k.  The two must agree bit for bit, ties included,
on the stage, on phase B at every rung of a ladder, on the delta scan and
on the served engine through mutations, compaction and a restore.
"""
import dataclasses
import math
import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import pipeline as pipe
from repro.core import segments
from repro.core.index import IndexConfig, build_index, probe_index
from repro.core.segments import SegmentedIndex
from repro.kernels.rows import store_rows
from repro.serve.engine import AnnServingEngine, ServeConfig

KEY = jax.random.PRNGKey(9)
N, DIM, U, K = 2000, 16, 510, 8
INT32_MAX = np.iinfo(np.int32).max
CFG = IndexConfig(num_tables=4, num_hashes=8, width=80, num_probes=30,
                  candidate_cap=32, universe=U, k=K, rerank_chunk=128)


# ---------------------------------------------------------------------------
# The former order: a per-slot tombstone mask ahead of a rerank at k.
# ---------------------------------------------------------------------------

def _old_mask(ids, gids, tombstones, n):
    """Dead candidates -> sentinel n, on every slot of the slab."""
    if n == 0:
        return ids
    gid = gids[jnp.clip(ids, 0, n - 1)]
    pos = jnp.searchsorted(tombstones, gid)
    hit = tombstones[jnp.clip(pos, 0, tombstones.shape[0] - 1)] == gid
    return jnp.where((ids < n) & hit, n, ids)


def _old_rerank(cfg, dataset, queries, ids, gids, tombstones):
    n = dataset.shape[0]
    ids = _old_mask(ids, gids, tombstones, n)
    d, i = pipe.stage_rerank(cfg, dataset, queries, ids)
    if n == 0:
        return d, i
    return d, jnp.where(i >= 0, gids[jnp.clip(i, 0, n - 1)], -1)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _old_finish_segment(cfg, cbucket, c_cap, state, gids, tombstones,
                        probe_keys, lo, occ, queries):
    n = state.dataset.shape[0]
    ids, _ = pipe.stage_fused_probe(
        cfg, state.sorted_keys, state.sorted_ids, probe_keys, n, cbucket,
        extents=(lo, occ), c_cap=c_cap)
    return _old_rerank(cfg, state.dataset, queries, ids, gids, tombstones)


@partial(jax.jit, static_argnums=0)
def _old_query_delta(cfg, buffer, gids, count, tombstones, queries):
    cap = buffer.shape[0]
    ids = jnp.broadcast_to(
        jnp.where(jnp.arange(cap, dtype=jnp.int32) < count,
                  jnp.arange(cap, dtype=jnp.int32), cap),
        (queries.shape[0], cap))
    return _old_rerank(cfg, buffer, queries, ids, gids, tombstones)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _rows(rng, count):
    return (rng.integers(0, U // 2 + 1, (count, DIM)) * 2).astype(np.int32)


def _tomb_array(dead):
    """As ``SegmentedIndex`` ships it: ascending, pow-2, INT32_MAX pad."""
    dead = sorted(set(int(g) for g in dead))
    cap = 1 << (len(dead) - 1).bit_length() if dead else 1
    out = np.full((cap,), INT32_MAX, np.int32)
    out[:len(dead)] = dead
    return jnp.asarray(out)


def _dedup(ids, n):
    """Sorted ids, repeats -> sentinel n: each candidate once."""
    ids = jnp.sort(ids, axis=-1)
    dup = jnp.concatenate([jnp.zeros((ids.shape[0], 1), bool),
                           ids[:, 1:] == ids[:, :-1]], axis=-1)
    return jnp.where(dup, n, ids)


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def _live_gids_only(got, dead):
    g = np.asarray(got[1])
    assert not np.isin(g[g >= 0], np.asarray(sorted(dead))).any()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(21)
    x = _rows(rng, N)
    q = x[rng.choice(N, 12, replace=False)] + 2 * rng.integers(
        -3, 4, (12, DIM)).astype(np.int32)
    return x, np.clip(q, 0, U).astype(np.int32), _rows(rng, 60)


# ---------------------------------------------------------------------------
# The stage, on candidate slabs built to stress each case
# ---------------------------------------------------------------------------

def _case(name, x, q, gids, rng):
    """(ids (Q, C) with sentinel N, dead gids) for one case."""
    qn = q.shape[0]
    base = rng.integers(0, N + 1, (qn, 500)).astype(np.int32)
    # every query's 3k true nearest rows are candidates
    d = np.abs(q[:, None, :].astype(np.int64) - x[None]).sum(-1)
    near = np.argsort(d, axis=1, kind="stable")[:, :3 * K].astype(np.int32)
    ids = np.concatenate([base, near], axis=1)
    if name == "none":
        return ids, []
    if name == "top_k":          # each query's top-1..top-k are dead
        return ids, gids[near[:, :K]].ravel()
    if name == "past_k":         # more dead candidates in a query than k
        return ids, np.concatenate([gids[near[0, :2 * K + 3]],
                                    gids[near[1:, :2]].ravel()])
    if name == "past_slab":      # k + T >= the slab's width
        narrow = np.concatenate([near[:, :6], np.full((qn, 6), N)], axis=1)
        return narrow.astype(np.int32), gids[near[:, :3]].ravel()
    if name == "repeated":       # a dead id repeated as if across tables
        rep = np.concatenate([np.repeat(near[:, :K], 4, axis=1), base[:, :64]],
                             axis=1)
        perm = rng.permutation(rep.shape[1])
        return rep[:, perm], gids[near[:, :K // 2]].ravel()
    raise ValueError(name)


CASES = ["none", "top_k", "past_k", "past_slab", "repeated"]


@pytest.mark.parametrize("slab", ["as_gathered", "deduplicated"])
@pytest.mark.parametrize("dtype", ["int32", "uint8"])
@pytest.mark.parametrize("case", CASES)
def test_select_then_tombstone_equals_mask_then_rerank(data, case, dtype,
                                                       slab):
    x, q, _ = data
    rng = np.random.default_rng(CASES.index(case))
    gids = (3 * np.arange(N) + 7).astype(np.int32)     # gid != local row
    ids, dead = _case(case, x, q, gids, rng)
    cfg = dataclasses.replace(CFG, dataset_dtype=dtype)
    rows = store_rows(jnp.asarray(x), dtype)
    ids = jnp.asarray(ids)
    if slab == "deduplicated":     # ids as phase B hands them, or unique
        ids = _dedup(ids, N)
    tomb = _tomb_array(dead)
    args = (cfg, rows, jnp.asarray(q), ids, jnp.asarray(gids), tomb)
    got = jax.jit(pipe.rerank_candidates, static_argnums=0)(*args)
    want = jax.jit(_old_rerank, static_argnums=0)(*args)
    _same(got, want)
    if case == "past_slab":
        assert K + tomb.shape[0] >= ids.shape[1]
    if len(dead):
        _live_gids_only(got, dead)
        alive = jax.jit(pipe.rerank_candidates, static_argnums=0)(
            *args[:-1], _tomb_array([]))
        assert not np.array_equal(np.asarray(alive[1]), np.asarray(got[1]))
    if case == "past_k":                 # query 0 still fills its k
        assert (np.asarray(got[1])[0] >= 0).all()


def test_tomb_select_bounds():
    assert pipe.tomb_select(10, 1, 131072) == 11
    assert pipe.tomb_select(10, 64, 40) == 40       # the whole slab
    assert pipe.tomb_select(10, 64, 4) == 10        # the rerank pads to k


# ---------------------------------------------------------------------------
# Phase B at every rung, the delta scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["int32", "uint8"])
def test_finish_segment_equals_old_order_at_every_rung(data, dtype):
    x, q, _ = data
    cfg = dataclasses.replace(CFG, dataset_dtype=dtype)
    state = build_index(cfg, KEY, jnp.asarray(x))
    gids = (3 * np.arange(N) + 7).astype(np.int32)
    idx = SegmentedIndex.from_checkpoint(cfg, state, gids, int(gids[-1]) + 1)
    # each query's eight nearest rows are dead, and some others
    d = np.abs(q[:, None, :].astype(np.int64) - x[None]).sum(-1)
    dead = gids[np.concatenate([np.argsort(d, axis=1, kind="stable")[:, :K]
                                .ravel(), np.arange(0, N, 37)])]
    idx.delete(dead)
    seg = idx.segments[0]
    tomb = idx._tombstone_array()
    queries = jnp.asarray(q)
    pk, lo, occ, counts = probe_index(cfg, state, queries)
    idx._ensure_caps(seg)
    # overflow rungs exist only below the worst case: put the normal top
    # there, with a per-bucket cap under the configured one
    norm = max(256, seg.ctot_cap // 4)
    rungs = set()
    for overflow in ("escalate", "truncate"):
        rungs |= set(pipe.rung_ladder(seg.ctot_cap, 256, norm,
                                      CFG.candidate_cap // 4, overflow))
    assert (seg.ctot_cap, None) in rungs                   # escalate
    assert any(c is not None for _, c in rungs)            # truncate
    assert int(counts.max()) > 256                         # rungs truncate
    for cb, c_cap in sorted(rungs, key=lambda r: (r[0], r[1] or 0)):
        args = (cfg, cb, c_cap, state, seg.gids, tomb, pk, lo, occ, queries)
        got = segments._finish_segment(*args)
        _same(got, _old_finish_segment(*args))
        _live_gids_only(got, dead)


@pytest.mark.parametrize("fill", ["part", "full"])
def test_query_delta_drops_dead_delta_rows(data, fill):
    x, _, fresh = data
    cap = {"part": 128, "full": len(fresh)}[fill]
    idx = SegmentedIndex(CFG, KEY, DIM, delta_cap=cap)
    new = idx.insert(fresh)                   # all in the delta buffer
    assert idx.num_segments == 0 and idx.delta_fill == len(fresh) / cap
    queries = jnp.asarray(fresh[:10])         # each one's top-1 is itself
    dead = np.concatenate([new[:10:2], new[30:50]])
    idx.delete(dead)
    pts, gids = idx._delta_arrays()
    args = (CFG, pts, gids, jnp.int32(len(new)), idx._tombstone_array(),
            queries)
    got = segments._query_delta(*args)
    _same(got, _old_query_delta(*args))
    _live_gids_only(got, dead)
    top1 = np.asarray(got[1])[:, 0]
    assert (top1[1::2] == new[1:10:2]).all()      # live rows find themselves


# ---------------------------------------------------------------------------
# The engine through insert, delete, compaction and a restore
# ---------------------------------------------------------------------------

def _serve(cfg, x, q, inserts, tmp_path):
    serve = ServeConfig(batch_size=16, bucket_min=16, warm_buckets=False,
                        cand_bucket_min=64, delta_cap=64,
                        compact_watermark=2.0, max_segments=8,
                        tombstone_watermark=2.0, hedge_ms=1e9)
    engine = AnnServingEngine(cfg, serve, dataset=jnp.asarray(x), key=KEY)
    out = {"sealed": engine.query_batch(q)}
    new = engine.insert(inserts)
    out["inserted"] = engine.query_batch(q)
    near = np.argsort(np.abs(q[:, None, :].astype(np.int64)
                             - x[None]).sum(-1), axis=1)[:, :3]
    dead = np.concatenate([near.ravel(), new[::3], np.arange(0, N, 11)])
    engine.delete(dead)
    out["deleted"] = engine.query_batch(q)
    engine.compact()
    out["compacted"] = engine.query_batch(q)
    payload = engine.checkpoint_payload()
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(1, payload)
    state, gids, next_gid = mgr.restore(1, payload)
    node = AnnServingEngine(
        cfg, serve, index=SegmentedIndex.from_checkpoint(
            cfg, state, gids, next_gid, delta_cap=serve.delta_cap))
    out["restored"] = node.query_batch(q)
    return out, dead


@pytest.mark.parametrize("dtype", ["int32", "uint8"])
def test_engine_equals_old_order_through_mutations_and_restore(
        data, dtype, monkeypatch, tmp_path):
    x, q, inserts = data
    cfg = dataclasses.replace(CFG, dataset_dtype=dtype)
    got, dead = _serve(cfg, x, q, inserts, tmp_path / "new")
    monkeypatch.setattr(segments, "_finish_segment", _old_finish_segment)
    monkeypatch.setattr(segments, "_query_delta", _old_query_delta)
    want, _ = _serve(cfg, x, q, inserts, tmp_path / "old")
    for stage in ("sealed", "inserted", "deleted", "compacted", "restored"):
        _same(got[stage], want[stage])
    for stage in ("deleted", "compacted", "restored"):
        _live_gids_only(got[stage], dead)
    assert not np.array_equal(np.asarray(got["inserted"][1]),
                              np.asarray(got["deleted"][1]))


# ---------------------------------------------------------------------------
# Structure at the serving shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 1024])
def test_phase_b_checks_tombstones_off_the_slab(t):
    """Phase B compiled at SIFT1M's serving shape (64 queries, the 131,072
    rung, 1M rows): outside the rerank's rows gather, one gather over the
    slab's slots (the compact gather's ``sorted_ids`` take), and no op of
    the ``tombstone`` or ``gid_map`` scopes, a bisection loop included, as
    wide as the slab."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke as smoke
    shape = smoke.SIFT1M
    cfg = smoke.index_config(shape)
    q, n, cbucket = 64, 1_000_000, 131072
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    state = jax.eval_shape(
        lambda d: build_index(cfg, jax.random.PRNGKey(0), d),
        spec(n, shape.dim))
    pk, lo, occ, _ = jax.eval_shape(
        lambda s, x: probe_index(cfg, s, x), state, spec(q, shape.dim))
    hlo = segments._finish_segment.lower(
        cfg, cbucket, None, state, spec(n), spec(t), pk, lo, occ,
        spec(q, shape.dim)).compile().as_text()
    slab = q * cbucket
    slot_gathers = [
        line for line in hlo.splitlines()
        if (m := re.search(r"= s32\[([0-9,]+)\]\S* gather\(", line))
        and math.prod(int(d) for d in m.group(1).split(",")) == slab
        and "/rerank/" not in line]
    assert len(slot_gathers) == 1, slot_gathers
    assert "compact_gather/" in slot_gathers[0]
    scoped = [line for line in hlo.splitlines()
              if re.search(r'op_name="[^"]*/(tombstone|gid_map)/', line)]
    assert scoped
    for line in scoped:
        out = line.split(" = ", 1)[1].split("metadata=")[0]
        for dims in re.findall(r"\[([0-9,]+)\]", out):
            assert math.prod(int(d) for d in dims.split(",")) < slab, line
