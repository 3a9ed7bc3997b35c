"""Jit'd public wrappers for the kernels.

Each query-path stage has one executor, the same on every backend: the
probe extents, the compacted gather, the rerank and the top-k merge run in
XLA ops (``fused_probe``, ``fused_rerank``, ``topk_merge``), so the CPU
suite runs what the chip runs.  Kernels for the chip built on ANY-space
refs with DMA are ROADMAP B1 (DESIGN.md §5.4).  ``l1_distance`` and
``rw_hash`` are Pallas kernels of the baseline and hashing paths; off TPU
they run under ``interpret=True`` (the kernel body runs in Python per grid
step with identical semantics), never on TPU.
"""
from __future__ import annotations

import jax

from .fused_probe import (compact_gather_xla, fused_probe_xla,
                          probe_extents_xla)
from .fused_rerank import fused_rerank_xla
from .l1_distance import l1_distance_pallas, l1_distance_rows_pallas
from .rw_hash import rw_hash_pallas
from .topk_merge import topk_merge_xla

__all__ = ["l1_distance", "l1_distance_rows", "rw_hash", "topk_merge",
           "fused_rerank", "fused_probe", "probe_extents", "use_interpret"]


def use_interpret() -> bool:
    """Pallas interpret mode: on for every backend except TPU."""
    return jax.default_backend() != "tpu"


def l1_distance(queries, points, **kw):
    return l1_distance_pallas(queries, points, interpret=use_interpret(), **kw)


def l1_distance_rows(queries, rows, **kw):
    return l1_distance_rows_pallas(queries, rows, interpret=use_interpret(), **kw)


def rw_hash(pairs, points, **kw):
    return rw_hash_pallas(pairs, points, interpret=use_interpret(), **kw)


def topk_merge(da, ia, db, ib):
    """Merge two ascending (Q, k) top-k lists, lexicographic on (dist, id)."""
    return topk_merge_xla(da, ia, db, ib)


def fused_rerank(dataset, queries, ids, k, chunk=512):
    """Gather + L1 + top-k rerank (DESIGN.md §Perf): a chunked distance
    scan and one lexicographic selection sort; duplicate ids allowed."""
    return fused_rerank_xla(dataset, queries, ids, k, chunk=chunk)


def probe_extents(sorted_keys, probe_keys, cap, occ_from=None):
    """Raw (lo, occ, counts) bucket extents — the probe's phase A.

    A searchsorted sweep + gathers + a reduce.  The (lo, occ) pair is what
    the two-phase query hands back to ``fused_probe(extents=...)`` so the
    gather phase does not repeat the search; ``occ`` is the *unclamped*
    occupancy, so the gather may apply any per-bucket cap <= the counts'
    cap (two-level compaction, DESIGN.md §9).  ``occ_from`` (the build-time
    run-length table) drops the right-side search — pass it whenever the
    index carries one.
    """
    return probe_extents_xla(sorted_keys, probe_keys, cap, occ_from=occ_from)


def fused_probe(sorted_keys, sorted_ids, probe_keys, cap, cbucket,
                extents=None):
    """Bucket lookup + compacted candidate gather (DESIGN.md §8).

    ``extents`` — a precomputed ``probe_extents`` (lo, occ) pair — skips
    the search (the two-phase query computes it in phase A anyway).
    Because ``occ`` is raw, ``cap`` here may differ from the cap the
    extents were computed at — the overflow rung passes a tighter one.
    """
    if extents is not None:
        return compact_gather_xla(sorted_ids, extents[0], extents[1],
                                  probe_keys.shape[2], cbucket, cap)
    return fused_probe_xla(sorted_keys, sorted_ids, probe_keys, cap, cbucket)
