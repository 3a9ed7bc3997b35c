"""Jit'd public wrappers for the kernels, and the executor each query-path
stage runs on each backend.

The executor is chosen in code from the backend (``executors``), never
from the environment.  On TPU every query-path stage runs an XLA executor:
the Pallas probe, rerank and merge kernels do not lower for the chip at
serving shapes yet (DESIGN.md §5.4, ROADMAP B1), and a kernel that quietly
gives way to another executor at compile time is not a choice anyone can
see.  Off TPU the Pallas kernels run under ``interpret=True`` — the kernel
body runs in Python per grid step with identical semantics — which is how
the CPU parity tests pin them; interpret mode is never on for TPU.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from .fused_probe import (compact_gather_xla, fused_probe_xla,
                          probe_extents_xla)
from .fused_rerank import fused_rerank_xla
from .l1_distance import l1_distance_pallas, l1_distance_rows_pallas
from .rw_hash import rw_hash_pallas
from .topk_merge import topk_merge_pallas, topk_merge_xla

__all__ = ["l1_distance", "l1_distance_rows", "rw_hash", "topk_merge",
           "fused_rerank", "fused_probe", "probe_extents", "use_interpret",
           "executors"]


def executors(backend: Optional[str] = None) -> Dict[str, str]:
    """Executor per query-path stage on ``backend`` (default: JAX's).

    * ``probe`` — bucket extents + compacted candidate gather.  XLA on every
      backend: the Pallas ``fused_probe`` kernel's flattened-ref gathers do
      not lower on TPU and it maps all ``(L, n)`` keys into VMEM.
    * ``rerank`` — gather + L1 + top-k.  XLA on every backend: the Pallas
      ``fused_rerank`` kernel uses ``dynamic_slice`` inside the kernel,
      which Mosaic rejects, and maps the whole dataset into VMEM.
    * ``merge`` — two-way top-k merge.  The bitonic Pallas kernel off TPU
      (interpreted; the CPU tests pin it against the XLA merge); on TPU
      its lane reversal (``rev``) does not lower, so the chip runs the
      lexicographic sort merge.

    Every pair is bit-identical by the parity tests, so the choice moves
    time, never results.
    """
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return {"probe": "xla", "rerank": "xla",
            "merge": "xla" if on_tpu else "pallas"}


def use_interpret() -> bool:
    """Pallas interpret mode: on for every backend except TPU."""
    return jax.default_backend() != "tpu"


def l1_distance(queries, points, **kw):
    return l1_distance_pallas(queries, points, interpret=use_interpret(), **kw)


def l1_distance_rows(queries, rows, **kw):
    return l1_distance_rows_pallas(queries, rows, interpret=use_interpret(), **kw)


def rw_hash(pairs, points, **kw):
    return rw_hash_pallas(pairs, points, interpret=use_interpret(), **kw)


def topk_merge(da, ia, db, ib, **kw):
    """Merge two ascending (Q, k) top-k lists; ``kw`` tiles the Pallas kernel."""
    if executors()["merge"] == "xla":
        return topk_merge_xla(da, ia, db, ib)
    return topk_merge_pallas(da, ia, db, ib, interpret=use_interpret(), **kw)


def fused_rerank(dataset, queries, ids, k, chunk=512):
    """Fused gather+L1+running-top-k rerank (DESIGN.md §Perf).

    Runs the XLA executor (chunked scan + one lexicographic sort) on every
    backend, see ``executors``; the Pallas kernel ``fused_rerank_pallas``
    is bit-identical and pinned against it by the parity tests.
    """
    return fused_rerank_xla(dataset, queries, ids, k, chunk=chunk)


def probe_extents(sorted_keys, probe_keys, cap, occ_from=None):
    """Raw (lo, occ, counts) bucket extents — fused-probe phase A.

    Plain XLA on every backend (a searchsorted sweep + gathers + a reduce;
    there is no big gather to fuse).  The (lo, occ) pair is what the
    two-phase serving path hands back to ``fused_probe(extents=...)`` so
    the gather phase does not repeat the search; ``occ`` is the
    *unclamped* occupancy, so the gather may apply any per-bucket cap <=
    the counts' cap (two-level compaction, DESIGN.md §9).  ``occ_from``
    (the build-time run-length table) drops the right-side search — pass
    it whenever the index carries one.
    """
    return probe_extents_xla(sorted_keys, probe_keys, cap, occ_from=occ_from)


def fused_probe(sorted_keys, sorted_ids, probe_keys, cap, cbucket,
                extents=None):
    """Fused bucket-lookup + compacted candidate gather (DESIGN.md §8).

    Runs the XLA executor on every backend, see ``executors``; the Pallas
    kernel ``fused_probe_pallas`` is bit-identical and pinned against it
    by the parity tests.  ``extents`` — a precomputed ``probe_extents``
    (lo, occ) pair — skips the search (the two-phase serving path computes
    it in phase A anyway).  Because ``occ`` is raw, ``cap`` here may
    differ from the cap the extents were computed at — the overflow rung
    passes a tighter one.
    """
    if extents is not None:
        return compact_gather_xla(sorted_ids, extents[0], extents[1],
                                  probe_keys.shape[2], cbucket, cap)
    return fused_probe_xla(sorted_keys, sorted_ids, probe_keys, cap, cbucket)
