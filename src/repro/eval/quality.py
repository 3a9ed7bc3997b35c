"""Shared-ground-truth quality harness (paper Sect. 5 / Tables 3-4 protocol).

One :class:`QualityRun` owns a dataset + query set + *one* exact L1 ground
truth (``brute_force_l1``), and every scheme is scored against it — the
evaluation discipline of Cai's "revisit" benchmark (recall-vs-cost curves
under a shared exact-GT protocol):

  * schemes: MP-RW-LSH, RW-LSH (single-probe, the paper's own baseline),
    CP-LSH, MP-CP-LSH, and SRS (projected brute-force upper bound);
  * sweeps ``num_tables`` x ``num_probes`` per scheme, recording recall@k
    and overall ratio per point, and derives the paper's headline
    statistic: **tables needed to reach recall R** per scheme, plus the
    CP/MP table-count ratio;
  * doubles as a **cross-layer consistency oracle**: the same config is
    pushed through ``query_index`` (flat), ``SegmentedIndex.query_compact``
    (fresh, mutated, and mutated-then-compacted), the ``dist_query_fn``
    all-gather path, and the sharded+replicated ``ClusterRouter``
    (including after a replica kill + WAL-replay recovery), asserting the
    quality the curves report is the quality every serving layer actually
    delivers.

``benchmarks/quality_bench.py`` drives this module and persists
``BENCH_quality.json``; DESIGN.md §6 documents the protocol.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as bl
from repro.core import pipeline as pipe
from repro.core.index import (IndexConfig, build_index, make_params,
                              probe_index, query_index)
from repro.core.segments import SegmentedIndex

__all__ = ["SCHEMES", "QualitySpec", "QualityRun", "tables_needed"]

# Sweep behavior per scheme: single-probe schemes pin T=0; 'srs' is special
# (no hash tables at all — a projected brute-force accuracy upper bound).
SCHEMES = ("mp-rw-lsh", "rw-lsh", "cp-lsh", "mp-cp-lsh", "srs")
_MULTIPROBE = {"mp-rw-lsh": True, "rw-lsh": False,
               "cp-lsh": False, "mp-cp-lsh": True}


@dataclasses.dataclass(frozen=True)
class QualitySpec:
    """Static sweep parameters (widths are tuned per dataset, see below)."""

    k: int = 10
    table_sweep: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    # single-probe schemes burn tables much faster (that IS the paper's
    # claim), so their sweep may extend further; None = same as table_sweep
    table_sweep_single: Optional[Tuple[int, ...]] = None
    probe_sweep: Tuple[int, ...] = (100,)     # T values for multiprobe schemes
    candidate_cap: int = 64
    num_hashes_rw: int = 12
    num_hashes_cp: int = 8
    rerank_chunk: int = 1024
    srs_proj: int = 10
    srs_t: int = 1024                          # projected t-NN candidates
    target_recall: float = 0.9
    seed: int = 0


def tables_needed(records: Sequence[dict], scheme: str,
                  target: float) -> Optional[int]:
    """Smallest num_tables at which ``scheme`` reaches ``target`` recall
    (any probe count); None when the sweep never gets there."""
    hits = [r["num_tables"] for r in records
            if r["scheme"] == scheme and r["recall"] >= target]
    return min(hits) if hits else None


class QualityRun:
    """One dataset + one exact ground truth; every scheme scored against it."""

    def __init__(self, data, queries, universe: int,
                 spec: QualitySpec = QualitySpec()):
        self.spec = spec
        self.universe = int(universe)
        self.data = jnp.asarray(data)
        self.queries = jnp.asarray(queries)
        self.key = jax.random.PRNGKey(spec.seed)
        td, ti = bl.brute_force_l1(self.data, self.queries, spec.k)
        self.true_d = np.asarray(td)
        self.true_i = np.asarray(ti)
        # Per-dataset width tuning, exactly as benchmarks/table4 does it:
        # the RW raw-hash spread at the near radius is sqrt(d1); the Cauchy
        # scale IS d1.  dbar comes from the shared ground truth for free.
        dbar = float(self.true_d.mean())
        self.dbar = dbar
        self.w_rw = max(8, int(3.0 * np.sqrt(dbar)) & ~1)
        self.w_cp = max(8, int(4.0 * dbar))

    # -- configs -----------------------------------------------------------

    def scheme_config(self, scheme: str, num_tables: int,
                      num_probes: Optional[int] = None) -> IndexConfig:
        s = self.spec
        if scheme not in _MULTIPROBE:
            raise ValueError(f"no IndexConfig for scheme {scheme!r}")
        if not _MULTIPROBE[scheme]:
            num_probes = 0
        elif num_probes is None:
            num_probes = s.probe_sweep[-1]
        rw = scheme in ("mp-rw-lsh", "rw-lsh")
        return IndexConfig(
            num_tables=num_tables,
            num_hashes=s.num_hashes_rw if rw else s.num_hashes_cp,
            width=self.w_rw if rw else self.w_cp,
            num_probes=num_probes,
            candidate_cap=s.candidate_cap,
            universe=self.universe,
            family="rw" if rw else "cauchy",
            k=s.k,
            rerank_chunk=s.rerank_chunk)

    # -- query layers (the cross-layer oracle's subjects) ------------------

    def query_flat(self, cfg: IndexConfig):
        state = build_index(cfg, self.key, self.data)
        return query_index(cfg, state, self.queries)

    def query_segmented(self, cfg: IndexConfig):
        idx = SegmentedIndex.from_dataset(cfg, self.key, self.data)
        return idx.query_compact(self.queries)[:2]

    def query_dist(self, cfg: IndexConfig, merge: str = "allgather"):
        """All-gather shard_map path on a (1, n_devices) mesh.

        One row shard keeps the candidate set identical to the flat path
        (per-shard candidate_cap never truncates differently), so the
        result must be bit-for-bit equal to ``query_index`` — which is
        exactly what makes this a consistency oracle rather than an
        approximate comparison.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch import dist_index as di
        n_dev = len(jax.devices())
        if self.queries.shape[0] % n_dev:
            n_dev = 1
        mesh = jax.make_mesh((1, n_dev), ("data", "model"))
        params = make_params(cfg, self.key, int(self.data.shape[1]))
        with mesh:
            dj = jax.device_put(self.data, NamedSharding(mesh, P("data", None)))
            qj = jax.device_put(self.queries,
                                NamedSharding(mesh, P("model", None)))
            state = di.dist_build_fn(cfg, mesh)(dj, params)
            d, i = di.dist_query_fn(cfg, mesh, merge=merge)(state, qj)
            return jnp.asarray(d), jnp.asarray(i)

    # -- scoring -----------------------------------------------------------

    def _score(self, d, i, ms_per_query: Optional[float] = None) -> dict:
        rec = {"recall": float(bl.recall(np.asarray(i), self.true_i)),
               "ratio": float(bl.overall_ratio(np.asarray(d), self.true_d))}
        if ms_per_query is not None:
            rec["ms_per_query"] = ms_per_query
        return rec

    def eval_config(self, cfg: IndexConfig, timed: bool = False) -> dict:
        state = build_index(cfg, self.key, self.data)
        d, i = query_index(cfg, state, self.queries)  # compile + result
        ms = None
        if timed:
            jax.tree.leaves((d, i))[0].block_until_ready()
            t0 = time.perf_counter()
            d, i = query_index(cfg, state, self.queries)
            jax.tree.leaves((d, i))[0].block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / self.queries.shape[0]
        return self._score(d, i, ms)

    def eval_srs(self, timed: bool = False) -> dict:
        s = self.spec
        t = min(s.srs_t, int(self.data.shape[0]))
        srs = bl.build_srs(jax.random.fold_in(self.key, 1), self.data,
                           s.srs_proj)
        d, i = bl.query_srs(srs, self.queries, t, s.k)
        ms = None
        if timed:
            d.block_until_ready()
            t0 = time.perf_counter()
            d, i = bl.query_srs(srs, self.queries, t, s.k)
            d.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / self.queries.shape[0]
        return self._score(d, i, ms)

    # -- sweeps + derived statistics ---------------------------------------

    def sweep(self, schemes: Sequence[str] = SCHEMES,
              timed: bool = False) -> List[dict]:
        """recall@k / ratio over num_tables x num_probes for every scheme,
        all against the one shared ground truth."""
        records: List[dict] = []
        for scheme in schemes:
            if scheme == "srs":
                rec = self.eval_srs(timed)
                rec.update(scheme="srs", num_tables=0, num_probes=0)
                records.append(rec)
                continue
            multi = _MULTIPROBE[scheme]
            probes = self.spec.probe_sweep if multi else (0,)
            tables = (self.spec.table_sweep if multi else
                      self.spec.table_sweep_single or self.spec.table_sweep)
            for t_probes in probes:
                for l_tables in tables:
                    cfg = self.scheme_config(scheme, l_tables, t_probes)
                    rec = self.eval_config(cfg, timed)
                    rec.update(scheme=scheme, num_tables=l_tables,
                               num_probes=t_probes)
                    records.append(rec)
        return records

    def table_claim(self, records: Sequence[dict],
                    target: Optional[float] = None) -> dict:
        """The paper's headline: tables needed at recall R, per scheme, and
        the baseline/MP-RW ratios (paper Sect. 5: 15-53x for CP-LSH)."""
        target = self.spec.target_recall if target is None else target
        needed = {s: tables_needed(records, s, target)
                  for s in ("mp-rw-lsh", "rw-lsh", "cp-lsh", "mp-cp-lsh")
                  if any(r["scheme"] == s for r in records)}
        l_mp = needed.get("mp-rw-lsh")
        ratios = {}
        for s, l in needed.items():
            if s == "mp-rw-lsh" or l_mp is None:
                continue
            # None = "more than the sweep maximum": still strictly more
            # tables than MP-RW, reported as a lower bound on the ratio.
            ratios[s] = (None if l is None else round(l / l_mp, 2))
        max_l = max(self.spec.table_sweep
                    + (self.spec.table_sweep_single or ()))
        return {"target_recall": target, "tables_needed": needed,
                "ratio_vs_mp_rw": ratios, "sweep_max_tables": max_l}

    # -- cross-layer consistency oracle ------------------------------------

    def check_segmented(self, cfg: IndexConfig, split: float = 0.5,
                        delta_cap: Optional[int] = None, flat=None) -> dict:
        """Mutation-path oracle: build half, insert the rest, query while
        fragmented, compact, query again.

        Invariants checked (DESIGN.md Sect. 3 + §6):
          * fresh single-segment == flat ``query_index`` bit-for-bit;
          * fragmented (multi-segment + delta) recall never regresses below
            the compacted recall — each source contributes its own
            candidate_cap, so the fragmented index examines a superset;
          * after ``compact()`` the result is bit-identical to the fresh
            build (insertion order and gids are preserved), so the
            *recall matches exactly*.
        """
        data_np = np.asarray(self.data)
        n = data_np.shape[0]
        n0 = max(1, int(n * split))
        fd, fi = self.query_flat(cfg) if flat is None else flat
        fresh = self._score(fd, fi)

        frag = SegmentedIndex.from_dataset(
            cfg, self.key, jnp.asarray(data_np[:n0]),
            delta_cap=delta_cap or max(64, (n - n0) // 3))
        frag.insert(data_np[n0:])                  # seals segments + delta
        md, mi, _ = frag.query_compact(self.queries)
        mutated = self._score(md, mi)
        segments_while_fragmented = frag.num_segments
        frag.compact()
        cd, ci, _ = frag.query_compact(self.queries)
        compacted = self._score(cd, ci)

        idx = SegmentedIndex.from_dataset(cfg, self.key, self.data)
        sd, si, _ = idx.query_compact(self.queries)
        return {
            "fresh_recall": fresh["recall"],
            "mutated_recall": mutated["recall"],
            "compacted_recall": compacted["recall"],
            "segments_while_fragmented": segments_while_fragmented,
            "segmented_matches_flat": bool(
                np.array_equal(np.asarray(sd), np.asarray(fd))
                and np.array_equal(np.asarray(si), np.asarray(fi))),
            "compacted_matches_fresh": bool(
                np.array_equal(np.asarray(cd), np.asarray(fd))
                and np.array_equal(np.asarray(ci), np.asarray(fi))),
            "mutated_no_regression":
                mutated["recall"] >= compacted["recall"],
        }

    def check_cluster(self, cfg: IndexConfig,
                      num_shards: int = 2, num_replicas: int = 2,
                      root_dir: Optional[str] = None,
                      transport: str = "inproc") -> dict:
        """Cluster-path oracle (DESIGN.md §7): the sharded+replicated
        ``ClusterRouter`` == flat ``query_index``, bit-for-bit — before AND
        after a replica kill + WAL-replay recovery (the recovered replica
        is forced to serve by killing its peer).

        ``transport='process'`` runs the identical oracle against worker
        *subprocesses* behind the RPC transport (DESIGN.md §10) — the
        bit-identity and kill/recovery claims must survive the wire, and
        the kill becomes a real SIGKILL.

        Bit-identity between a sharded and a flat index requires the
        candidate gather to be non-truncating (a shard examines its own
        ``candidate_cap`` per probed bucket, so a binding cap makes the
        cluster examine a *superset* — recall can only improve, but bits
        may differ).  The oracle therefore raises the cap to the max
        bucket occupancy of the built index, where per-shard candidate
        sets union to exactly the flat set and the ``topk_merge`` fold
        must reproduce the flat top-k bits.
        """
        from repro.cluster import ClusterConfig, ClusterRouter
        from repro.serve.engine import ServeConfig

        state = build_index(cfg, self.key, self.data)
        # the occupancy a non-truncating gather must cover — the SAME
        # derivation the candidate-compaction ladder builds on
        # (pipeline.max_bucket_occupancy via segments._seg_ctot_cap), so
        # oracle exactness and compaction bounds cannot drift (cap is not a
        # build parameter, so the state is reusable under the raised cap)
        cfg = dataclasses.replace(
            cfg, candidate_cap=pipe.oracle_candidate_cap(
                cfg, state.sorted_keys, state.occ_from))
        fd, fi = map(np.asarray, query_index(cfg, state, self.queries))
        with tempfile.TemporaryDirectory(dir=root_dir) as root:
            router = ClusterRouter(
                cfg, ServeConfig(batch_size=32),
                ClusterConfig(num_shards=num_shards,
                              num_replicas=num_replicas,
                              hedge_ms=60000.0,  # oracle: never hedge on a
                              wal_fsync=False,   # cold compile
                              transport=transport),
                np.asarray(self.data), root, key=self.key)
            cd, ci = router.query(np.asarray(self.queries))
            matches = bool(np.array_equal(cd, fd) and np.array_equal(ci, fi))
            # WAL some mutations through, kill a replica, recover it, then
            # make it serve (peer killed): still flat-identical on the
            # original points (inserted probes are deleted again before the
            # check, exercising insert+delete+replay in one pass).
            probes = np.asarray(self.queries[:4], np.int32)
            gids = router.insert(probes)
            router.kill_replica(0, 0)
            router.delete(gids)
            router.recover_replica(0, 0)
            router.kill_replica(0, min(1, num_replicas - 1))
            rd, ri = router.query(np.asarray(self.queries))
            recovered = bool(np.array_equal(rd, fd)
                             and np.array_equal(ri, fi))
            summary = router.summary()
            router.close()
        return {
            "cluster_matches_flat": matches,
            "cluster_recovery_matches_flat": recovered,
            "cluster_shards": num_shards,
            "cluster_replicas": num_replicas,
            "cluster_recoveries": summary["recoveries"],
            "cluster_oracle_cap": cfg.candidate_cap,
            "cluster_transport": transport,
        }

    def check_compact(self, cfg: IndexConfig, flat=None) -> dict:
        """Candidate-rung oracle (DESIGN.md §8): the segmented
        ``query_compact``, phase B at the pow-2 candidate rung its counts
        pick, must reproduce the flat full-slab result bit-for-bit, while
        actually shrinking the slab (the reported buckets show by how
        much)."""
        fd, fi = self.query_flat(cfg) if flat is None else flat
        fd, fi = np.asarray(fd), np.asarray(fi)
        idx = SegmentedIndex.from_dataset(cfg, self.key, self.data)
        sd, si, used = idx.query_compact(self.queries)
        return {
            "compact_segmented_matches_flat": bool(
                np.array_equal(np.asarray(sd), fd)
                and np.array_equal(np.asarray(si), fi)),
            "compact_cand_buckets": [cb for _, cb, _ in used],
            "compact_full_slab": (cfg.num_tables * cfg.probes_per_table
                                  * cfg.candidate_cap),
        }

    def check_skew_cap(self, cfg: IndexConfig, quantile: float = 0.999,
                       floor: int = 64, flat=None) -> dict:
        """Skew-aware two-level compaction oracle (DESIGN.md §9).

        Derives the two-level caps the serving policy would (per-bucket
        ``c_norm`` from the build-time occupancy-histogram quantile,
        normal-ladder top ``ctot_norm`` from realized capped totals) and
        checks both overflow policies against the uncapped flat query:

        * ``escalate`` must stay **bit-identical** — the exact worst-case
          rung is still exact;
        * ``truncate`` (per-bucket sorted-prefix truncation) must cost
          < 0.5% recall vs the uncapped result at paper-shaped configs —
          the bounded-latency knob's advertised price.

        On skew-free data the caps degenerate (``c_norm == full cap``) and
        both paths are trivially exact; feed it
        ``data.ann_synthetic.make_skewed_dataset`` output to actually
        exercise hot buckets.
        """
        fd, fi = self.query_flat(cfg) if flat is None else flat
        fd, fi = np.asarray(fd), np.asarray(fi)
        state = build_index(cfg, self.key, self.data)
        lp = cfg.num_tables * cfg.probes_per_table
        occ_max = pipe.max_bucket_occupancy(state.sorted_keys,
                                            state.occ_from)
        c_full = min(cfg.candidate_cap, occ_max)
        ctot_cap = lp * c_full
        c_norm = max(1, min(c_full, pipe.occupancy_quantile(
            state.occ_hist, quantile)))
        # p90 of realized capped totals over the dataset's own rows — same
        # derivation as SegmentedIndex._ensure_caps (per-bucket cap tames
        # depth outliers, p90 tames breadth outliers; the overflow rung
        # absorbs the tail past both)
        sample = self.data[:: max(1, self.data.shape[0] // 64)][:64]
        _, _, occ, _ = probe_index(cfg, state, jnp.asarray(sample,
                                                          jnp.int32))
        totals = np.minimum(np.asarray(occ), c_norm).sum(axis=-1)
        realized = int(np.percentile(totals, 90))
        ctot_norm = min(lp * c_norm,
                        1 << max(0, 2 * realized - 1).bit_length())
        ctot_norm = max(1, min(ctot_norm, ctot_cap))
        n = int(self.data.shape[0])
        idx = SegmentedIndex.from_checkpoint(
            cfg, state, jnp.arange(n, dtype=jnp.int32), n)
        seg = idx.segments[0]
        seg.ctot_cap, seg.ctot_norm, seg.c_norm = ctot_cap, ctot_norm, c_norm
        ed, ei, _ = idx.query_compact(self.queries, floor=floor,
                                      overflow="escalate")
        td, ti, _ = idx.query_compact(self.queries, floor=floor,
                                      overflow="truncate")
        uncapped = self._score(fd, fi)
        capped = self._score(np.asarray(td), np.asarray(ti))
        drop = uncapped["recall"] - capped["recall"]
        return {
            "skew_c_norm": c_norm,
            "skew_c_full": c_full,
            "skew_ctot_norm": ctot_norm,
            "skew_ctot_cap": ctot_cap,
            "skew_escalate_matches_flat": bool(
                np.array_equal(np.asarray(ed), fd)
                and np.array_equal(np.asarray(ei), fi)),
            "skew_uncapped_recall": uncapped["recall"],
            "skew_capped_recall": capped["recall"],
            "skew_recall_drop": drop,
            "skew_recall_within_half_pct": bool(drop < 0.005),
        }

    def check_distributed(self, cfg: IndexConfig, flat=None) -> dict:
        """Distributed-path oracle: all-gather shard_map == flat, bit-for-bit
        (single row shard; queries sharded over 'model').  ``flat`` may pass
        a precomputed ``query_flat(cfg)`` result to skip the rebuild."""
        fd, fi = self.query_flat(cfg) if flat is None else flat
        dd, di_ = self.query_dist(cfg)
        return {
            "devices": len(jax.devices()),
            "dist_matches_flat": bool(
                np.array_equal(np.asarray(dd), np.asarray(fd))
                and np.array_equal(np.asarray(di_), np.asarray(fi))),
        }

    def check_cross_layer(self, cfg: IndexConfig,
                          cluster: bool = True) -> dict:
        """All oracle layers for one config; every flag must be True/hold."""
        flat = self.query_flat(cfg)  # shared by all checks (one build)
        out = self.check_segmented(cfg, flat=flat)
        out.update(self.check_compact(cfg, flat=flat))
        out.update(self.check_distributed(cfg, flat=flat))
        if cluster:
            out.update(self.check_cluster(cfg))
        return out
