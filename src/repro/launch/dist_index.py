"""Distributed MP-RW-LSH: shard_map build + query over the production mesh.

Layout (DESIGN.md Sect. 4):
  * dataset rows sharded over the data axes ('pod','data') -> R row shards;
  * query batch sharded over 'model'                        -> 16 query shards;
  * every device runs the single-shard query (phase A and phase B of
    ``core.index``, at a static slab width) on its row shard for its
    query sub-batch;
  * per-shard top-k results are merged across row shards either by
    all-gather + local top-k (baseline) or by a ring or tree of
    collective-permutes with the ``topk_merge`` op (optimized — §Perf).

Hash params/walks are replicated (they are the paper's "fixed cost",
Sect. 3.2, ~MBs) so every shard buckets identically.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import hashes as hashes_lib
from repro.core import pipeline as pipe
from repro.core.index import (IndexConfig, IndexState, build_index,
                              finish_query, make_template, no_tombstones,
                              probe_index, row_gids)
from repro.kernels import ops as kops

__all__ = ["dist_build_fn", "dist_query_fn", "state_specs"]


def _row_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def state_specs(mesh: Mesh, cfg: IndexConfig) -> IndexState:
    """PartitionSpecs for a sharded IndexState (rows over data axes).

    family/width must match the target state's aux metadata (LshParams is a
    pytree with static fields)."""
    from repro.core.walks import WalkTable
    rows = _row_axes(mesh)
    params_spec = hashes_lib.LshParams(
        family=cfg.family, width=float(cfg.width),
        offsets=P(), mix_a=P(), mix_c=P(),
        walks=WalkTable(pairs=P(), prefix=P()) if cfg.family == "rw" else None,
        proj=None if cfg.family == "rw" else P(),
    )
    return IndexState(
        params=params_spec,
        sorted_keys=P(None, rows),
        sorted_ids=P(None, rows),
        dataset=P(rows, None),
        template=P(),
        row_offset=P(rows),
        occ_from=P(None, rows),
        occ_hist=P(),  # psum over row shards at build -> replicated
    )


def dist_build_fn(cfg: IndexConfig, mesh: Mesh):
    """Returns build(dataset, params) -> IndexState with sharded fields.

    dataset: (n_global, m) sharded P(rows, None); params: replicated
    LshParams built on host (shared by all shards).
    """
    rows = _row_axes(mesh)
    nshards = int(np.prod([mesh.shape[a] for a in rows]))

    def local_build(dataset, params):
        # row-shard id: flatten the data axes
        idx = jax.lax.axis_index(rows)
        n_local = dataset.shape[0]
        state = build_index(cfg, jax.random.PRNGKey(0), dataset,
                            row_offset=idx * n_local, params=params)
        # shard-local occupancy histograms are additive (each shard counts
        # its own buckets) — one psum yields the replicated global view the
        # two-level compaction policy reads (DESIGN.md §9)
        occ_hist = jax.lax.psum(state.occ_hist, rows)
        # row_offset out as (1,) so it shards over `rows`
        return (state.sorted_keys, state.sorted_ids,
                state.row_offset[None], state.occ_from, occ_hist)

    fn = shard_map(
        local_build, mesh=mesh,
        in_specs=(P(rows, None), P()),
        out_specs=(P(None, rows), P(None, rows), P(rows), P(None, rows),
                   P()),
        check_vma=False,
    )

    def build(dataset, params):
        (sorted_keys, sorted_ids, row_offset, occ_from,
         occ_hist) = fn(dataset, params)
        template = jnp.asarray(make_template(cfg))
        return IndexState(params=params, sorted_keys=sorted_keys,
                          sorted_ids=sorted_ids, dataset=dataset,
                          template=template, row_offset=row_offset,
                          occ_from=occ_from, occ_hist=occ_hist)

    return build


def dist_query_fn(cfg: IndexConfig, mesh: Mesh, merge: str = "allgather",
                  cand_bucket: int | None = None,
                  cand_cap: int | None = None):
    """Returns query(state, queries) -> (dists (Q, k), ids (Q, k)).

    queries: (Q_global, m) sharded over 'model'.  merge: 'allgather' | 'ring'.
    Each shard runs ``probe_index`` and ``finish_query``, the single-shard
    query's two phases, in one program at a static slab: the full
    ``L*P*C`` by default.  ``cand_bucket`` statically compacts each shard's
    candidate slab to that width (DESIGN.md §8) — shard_map bodies cannot
    take the two-phase host round-trip, but a caller that knows its
    shard occupancy (e.g. ``pipe.oracle_candidate_cap``-derived) passes the
    bound here and every shard gathers/reranks at it instead of the
    worst-case ``L*P*C``.  Results are bit-identical as long as the bucket
    covers the per-shard candidate counts.  ``cand_cap`` additionally
    tightens the per-bucket clamp below ``cfg.candidate_cap`` (the
    two-level truncate rung, DESIGN.md §9) — derive it from the sharded
    state's ``occ_hist`` via ``pipe.occupancy_quantile`` for a
    skew-bounded slab; deterministic sorted-prefix truncation, so results
    stay reproducible (but no longer exact when a bucket exceeds it).
    """
    rows = _row_axes(mesh)
    nshards = int(np.prod([mesh.shape[a] for a in rows]))
    k = cfg.k

    def local_query(sorted_keys, sorted_ids, dataset, row_offset, occ_from,
                    params, template, queries):
        # The single-shard query over the shard's slices: phase A, then
        # phase B at the static slab, rows mapped to global ids.
        state = IndexState(params=params, sorted_keys=sorted_keys,
                           sorted_ids=sorted_ids, dataset=dataset,
                           template=template, row_offset=row_offset[0],
                           occ_from=occ_from)
        probe_keys, lo, occ, _ = probe_index(cfg, state, queries)
        d, i = finish_query(cfg, cand_bucket, cand_cap, state,
                            row_gids(state), no_tombstones(), probe_keys,
                            lo, occ, queries)                  # local top-k
        if merge == "allgather":
            dg = jax.lax.all_gather(d, rows)               # (R, Qloc, k)
            ig = jax.lax.all_gather(i, rows)
            dg = jnp.moveaxis(dg, 0, 1).reshape(d.shape[0], nshards * k)
            ig = jnp.moveaxis(ig, 0, 1).reshape(d.shape[0], nshards * k)
            return pipe.stage_merge_concat(dg, ig, k)
        size = nshards
        if merge == "ring":
            # R-1 collective-permute steps; each shard's original list
            # travels the ring and is folded into the local accumulator.
            perm = [(j, (j + 1) % size) for j in range(size)]
            trav_d, trav_i = d, i
            acc_d, acc_i = d, i
            for _ in range(size - 1):
                trav_d = jax.lax.ppermute(trav_d, rows, perm)
                trav_i = jax.lax.ppermute(trav_i, rows, perm)
                acc_d, acc_i = kops.topk_merge(acc_d, acc_i, trav_d, trav_i)
            return acc_d, acc_i
        # 'tree': recursive-doubling butterfly — log2(R) exchange+merge
        # steps; every rank ends with the global top-k.  Collective bytes
        # log2(R)/(R-1) of the ring (§Perf ANN iteration C2).
        assert size & (size - 1) == 0, "tree merge needs power-of-two shards"
        acc_d, acc_i = d, i
        bit = 1
        while bit < size:
            perm = [(j, j ^ bit) for j in range(size)]
            pd = jax.lax.ppermute(acc_d, rows, perm)
            pi = jax.lax.ppermute(acc_i, rows, perm)
            acc_d, acc_i = kops.topk_merge(acc_d, acc_i, pd, pi)
            bit <<= 1
        return acc_d, acc_i

    in_specs = (
        P(None, rows), P(None, rows), P(rows, None), P(rows), P(None, rows),
        P(), P(), P("model", None),
    )
    fn = shard_map(local_query, mesh=mesh, in_specs=in_specs,
                   out_specs=(P("model", None), P("model", None)),
                   check_vma=False)

    def query(state: IndexState, queries):
        return fn(state.sorted_keys, state.sorted_ids, state.dataset,
                  state.row_offset, state.occ_from, state.params,
                  state.template, queries)

    return query
