"""Baselines the paper compares against (Sect. 5):

  * brute-force exact L1 k-NN (ground truth for recall / overall ratio)
  * RW-LSH single-probe (the paper's own baseline: MP-RW-LSH with T=0)
  * CP-LSH (Cauchy projection, single-probe — state of the art for ANNS-L1)
  * MP-CP-LSH (the multi-probe extension the paper shows is "top-light")
  * SRS (Cauchy projection to M dims + exact t-NN in projection space +
    exact L1 rerank).  The paper's SRS uses a cover tree; pointer machines
    don't map to TPUs, so we use a brute-force projected t-NN (an accuracy
    *upper bound* for SRS at equal t) — see DESIGN.md Sect. 2.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rows import widen_rows

from . import hashes as hashes_lib
from .index import IndexConfig
from .pipeline import BIG_DIST

__all__ = [
    "l1_distance_chunked",
    "brute_force_l1",
    "single_probe_config",
    "cp_lsh_config",
    "mp_cp_lsh_config",
    "SrsState",
    "build_srs",
    "query_srs",
    "recall",
    "overall_ratio",
]


def l1_distance_chunked(
    dataset: jax.Array, queries: jax.Array, ids: jax.Array, k: int,
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Exact L1 scan of given candidates: a chunked ``lax.top_k`` running best.

    The reference's plain scan (``brute_force_l1``, ``query_srs``), not a
    serving executor: the query path reranks with ``kops.fused_rerank``.
    dataset (n, m) stored rows (``kernels.rows``); queries (Q, m) int; ids
    (Q, Ctot) int32 with sentinel n marking invalid, **unique** (a
    duplicate would take a top-k slot of its own).  Returns (dists (Q,k)
    int32, ids (Q,k) int32) sorted ascending; invalid entries have dist =
    INT32_MAX/2 and id = -1.
    """
    n = dataset.shape[0]
    q, ctot = ids.shape
    big = jnp.int32(BIG_DIST)
    pad = (-ctot) % chunk
    if pad:
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=n)
    steps = ids.shape[1] // chunk
    ids_steps = ids.reshape(q, steps, chunk).transpose(1, 0, 2)     # (S,Q,c)

    def body(carry, step_ids):
        best_d, best_i = carry                                      # (Q,k)
        sl = jnp.clip(step_ids, 0, n - 1)                           # (Q,c)
        rows = dataset[sl]                                          # (Q,c,m)
        # HBM gather stays at the stored dtype (int16 or uint8); the rows
        # are widened to int32 coordinates in registers.
        diff = widen_rows(rows) - queries[:, None, :].astype(jnp.int32)
        d = jnp.abs(diff).sum(axis=-1).astype(jnp.int32)
        d = jnp.where(step_ids >= n, big, d)
        cd = jnp.concatenate([best_d, d], axis=-1)
        ci = jnp.concatenate([best_i, step_ids], axis=-1)
        nd, sel = jax.lax.top_k(-cd, k)
        return (-nd, jnp.take_along_axis(ci, sel, axis=-1)), None

    init = (jnp.full((q, k), big, jnp.int32), jnp.full((q, k), n, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(body, init, ids_steps)
    best_i = jnp.where(best_d >= big, -1, best_i)
    return best_d, best_i


@partial(jax.jit, static_argnums=(2, 3))
def brute_force_l1(dataset: jax.Array, queries: jax.Array, k: int, chunk: int = 2048):
    """Exact k-NN in L1.  Chunked over dataset rows; O(n*m) per query."""
    n = dataset.shape[0]
    q = queries.shape[0]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (q, n))
    return l1_distance_chunked(dataset, queries, ids, k, chunk)


def single_probe_config(cfg: IndexConfig) -> IndexConfig:
    """RW-LSH baseline = the same index probed only at the epicenter."""
    return dataclasses.replace(cfg, num_probes=0)


def cp_lsh_config(cfg: IndexConfig, width: int) -> IndexConfig:
    return dataclasses.replace(cfg, family="cauchy", width=width, num_probes=0,
                               hash_impl="gather")


def mp_cp_lsh_config(cfg: IndexConfig, width: int) -> IndexConfig:
    return dataclasses.replace(cfg, family="cauchy", width=width,
                               hash_impl="gather")


# --------------------------------------------------------------------------
# SRS
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SrsState:
    proj: jax.Array       # (M, m) Cauchy projection
    projected: jax.Array  # (n, M) f(D)
    dataset: jax.Array    # (n, m)

    def tree_flatten(self):
        return (self.proj, self.projected, self.dataset), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def build_srs(key: jax.Array, dataset: jax.Array, num_proj: int = 10) -> SrsState:
    proj = jax.random.cauchy(key, (num_proj, dataset.shape[1]), jnp.float32)
    projected = dataset.astype(jnp.float32) @ proj.T
    return SrsState(proj=proj, projected=projected, dataset=dataset)


@partial(jax.jit, static_argnums=(2, 3))
def query_srs(state: SrsState, queries: jax.Array, t: int, k: int):
    """t-NN in projection space (L2), exact L1 rerank of those t."""
    fq = queries.astype(jnp.float32) @ state.proj.T                 # (Q, M)
    d2 = jnp.sum((state.projected[None, :, :] - fq[:, None, :]) ** 2, axis=-1)
    _, cand = jax.lax.top_k(-d2, t)                                 # (Q, t)
    return l1_distance_chunked(state.dataset, queries, cand.astype(jnp.int32),
                               k, chunk=min(t, 512))


# --------------------------------------------------------------------------
# Quality metrics (paper Sect. 5.1)
# --------------------------------------------------------------------------

def recall(result_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Recall@k: |R ∩ R*| / |R*| averaged over queries (paper Sect. 5.1).

    The denominator is the ground-truth set R* — the exact k-NN ids — which
    is what the paper reports (a padded or truncated result row must not be
    able to inflate its own score).  Robust to ragged inputs: ``-1``/negative
    padding is dropped from both rows, duplicate ids count once (set
    semantics), result rows may carry more or fewer than |R*| entries, and
    degenerate inputs (no queries, or an all-padding truth row) score 0
    instead of dividing by zero.
    """
    result_ids = np.atleast_2d(np.asarray(result_ids))
    true_ids = np.atleast_2d(np.asarray(true_ids))
    if result_ids.shape[0] != true_ids.shape[0]:
        # zip would silently truncate and the mean would quietly use the
        # wrong query count — a caller bug, not a raggedness to absorb.
        raise ValueError(
            f"row count mismatch: {result_ids.shape[0]} result rows vs "
            f"{true_ids.shape[0]} ground-truth rows")
    if result_ids.shape[0] == 0:
        return 0.0
    r = 0.0
    for a, b in zip(result_ids, true_ids):
        truth = set(b[b >= 0].tolist())
        if truth:
            r += len(set(a[a >= 0].tolist()) & truth) / len(truth)
    return r / len(result_ids)


def overall_ratio(result_d: np.ndarray, true_d: np.ndarray) -> float:
    """(1/k) sum_i ||q - o_i|| / ||q - o_i*||, averaged over queries.
    Missing results (dist sentinel) are excluded defensively."""
    rd = np.asarray(result_d, np.float64)
    td = np.asarray(true_d, np.float64)
    ok = rd < np.iinfo(np.int32).max // 4
    ratio = np.where(ok, rd / np.maximum(td, 1e-9), np.nan)
    return float(np.nanmean(ratio))
