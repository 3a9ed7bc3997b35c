#!/usr/bin/env python3
"""Run the MP-RW-LSH serving path once on a TPU and check what it returns.

    python chip_smoke.py              # one chip: the serving engine
    python chip_smoke.py --chips 4    # four chips: sharded build + query

One chip: builds an ``AnnServingEngine`` over SIFT1M-shaped data
(n = 1,000,000, dim = 128, U = 510; the ``sift50m`` shape of
``repro.data.ann_synthetic`` at SIFT1M's n, made from ``--seed``), warms
it, serves 256 in-distribution queries in batches of 64, then inserts
2,500 rows (sealing a second segment, with the rest in the delta buffer),
deletes 300 and serves again.  It checks that

  * recall@10 against brute-force L1 on the chip is at least 0.9;
  * before mutation, served results equal the flat full-slab
    ``query_index`` on the same chip, bit for bit;
  * deleted gids never come back;
  * every inserted row that is still live finds itself.

Four chips (``--chips 4``, and nothing else): builds and queries the
``launch.dist_index`` shard_map index over n = 4,000,000 on a (1, 4)
all-gather mesh, which must equal the flat one-chip query bit for bit, and
on a (4, 1) row-sharded mesh, whose distances must be <= flat row by row;
it prints the device every shard sits on.

Timings printed here are smoke timings of one run, not benchmark numbers.
With no TPU the script exits non-zero and prints no result.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REFUSED_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_RERANK_EXECUTOR",
               "REPRO_PROBE_EXECUTOR")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Shape:
    """Data and traffic sizes of one smoke run."""

    n: int = 1_000_000
    dim: int = 128
    universe: int = 510
    queries: int = 256
    batch: int = 64
    inserts: int = 2500
    deletes: int = 300
    delta_cap: int = 2048          # < inserts: the insert seals a segment


SIFT1M = Shape()
SIFT4M = dataclasses.replace(SIFT1M, n=4_000_000)
RECALL_MIN = 0.9                   # eval/quality.py's table-count target

# Index parameters for recall@10 >= 0.9 at SIFT1M's shape (L = 8 tables).
# Swept on a TPU v5e over seed 0's data: M = 20 hashes gave 0.865 at 400
# probes and 0.93 at 800; M = 22 gives 0.904 at 800 probes with about 60%
# of M = 20's candidates, so a batch takes about half as long.
INDEX = dict(num_tables=8, num_hashes=22, width=200, num_probes=800,
             candidate_cap=128, k=10, rerank_chunk=1024)
# Smallest candidate rung the engine warms: a query here has tens of
# thousands of candidates, so smaller rungs would only add compiles.
CAND_BUCKET_MIN = 8192


def index_config(shape: Shape, **over):
    from repro.core.index import IndexConfig
    return IndexConfig(universe=shape.universe, **{**INDEX, **over})


def serve_config(shape: Shape, cand_bucket_min: int = CAND_BUCKET_MIN):
    """One batch shape; compaction never folds the sealed segment back."""
    from repro.serve.engine import ServeConfig
    return ServeConfig(batch_size=shape.batch, bucket_min=shape.batch,
                       warm_buckets=False, cand_bucket_min=cand_bucket_min,
                       delta_cap=shape.delta_cap, compact_watermark=2.0,
                       max_segments=4, hedge_ms=1e9)


def make_data(shape: Shape, seed: int):
    """(data, queries, inserts) as host int32 arrays, all made from seed."""
    from repro.data import ann_synthetic as ds
    spec = dataclasses.replace(ds.PAPER_DATASETS["sift50m"], n=shape.n,
                               dim=shape.dim, universe=shape.universe,
                               seed=seed)
    data = ds.make_dataset(spec)
    queries = ds.make_queries(spec, data, shape.queries, stray_frac=0.0)
    inserts = ds.make_queries(spec, data, shape.inserts, seed=2,
                              stray_frac=0.0)
    return data, queries, inserts


def _cache_delta(before: dict, after: dict) -> dict:
    return {"hits": after["hits"] - before["hits"],
            "misses": after["misses"] - before["misses"]}


def _batches(x, b):
    return [x[lo:lo + b] for lo in range(0, x.shape[0], b)]


def run_one_chip(shape: Shape, cfg, serve_cfg, seed: int, log) -> dict:
    """The engine path; returns {check name: passed}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import baselines as bl
    from repro.core.index import query_index
    from repro.serve.engine import (AnnServingEngine,
                                    compilation_cache_stats,
                                    enable_compilation_cache)

    enable_compilation_cache()
    log("compile cache", {"dir": compilation_cache_stats()["dir"]})
    t0 = time.perf_counter()
    data, queries, inserts = make_data(shape, seed)
    dj = jnp.asarray(data)
    dj.block_until_ready()
    log("data", {"n": shape.n, "dim": shape.dim, "universe": shape.universe,
                 "seconds": time.perf_counter() - t0})

    c0 = compilation_cache_stats()
    t0 = time.perf_counter()
    engine = AnnServingEngine(cfg, serve_cfg, dj,
                              key=jax.random.PRNGKey(seed))
    build_s = time.perf_counter() - t0
    c1 = compilation_cache_stats()
    engine.warmup()
    warm_s = time.perf_counter() - t0 - build_s
    c2 = compilation_cache_stats()
    log("build", {"seconds": build_s, "cache": _cache_delta(c0, c1),
                  "segments": engine.index.num_segments})
    log("warmup", {"seconds": warm_s, "cache": _cache_delta(c1, c2),
                   "rungs": [list(r) for r in
                             engine.index.candidate_ladders(
                                 serve_cfg.cand_bucket_min)[0]]})

    def serve(x):
        ds_, is_, ms = [], [], []
        for chunk in _batches(x, shape.batch):
            t = time.perf_counter()
            d, i = engine.query_batch(chunk)
            ms.append((time.perf_counter() - t) * 1e3)
            ds_.append(d)
            is_.append(i)
        return np.concatenate(ds_), np.concatenate(is_), ms

    checks = {}
    d1, i1, ms1 = serve(queries)
    log("serve", {"queries": int(queries.shape[0]),
                  "smoke_batch_ms_p50": float(np.percentile(ms1, 50)),
                  "smoke_batch_ms_p99": float(np.percentile(ms1, 99))})

    seg = engine.index.segments[0]
    flat = [query_index(cfg, seg.state, jnp.asarray(c))
            for c in _batches(queries, shape.batch)]
    fd = np.concatenate([np.asarray(f[0]) for f in flat])
    fi = np.concatenate([np.asarray(f[1]) for f in flat])
    checks["served_equals_flat"] = bool(
        np.array_equal(d1, fd) and np.array_equal(i1, fi))

    truth = [bl.brute_force_l1(dj, jnp.asarray(c), cfg.k)[1]
             for c in _batches(queries, shape.batch)]
    ti = np.concatenate([np.asarray(t) for t in truth])
    rec = bl.recall(i1, ti)
    log("recall", {"recall_at_k": rec, "k": cfg.k, "min": RECALL_MIN})
    checks["recall"] = rec >= RECALL_MIN

    # Mutations: the insert overflows the delta buffer, which seals a second
    # segment (minor compaction) and leaves the rest in the delta buffer.
    gids = engine.insert(inserts)
    n_ins = int(gids.shape[0])
    rng = np.random.default_rng(seed)
    hit = np.unique(i1[:, 0][i1[:, 0] >= 0])
    n_old = min(shape.deletes // 2, hit.shape[0])
    dead = np.concatenate([
        rng.choice(hit, n_old, replace=False),
        rng.choice(gids, shape.deletes - n_old, replace=False)])
    engine.delete(dead)
    idx = engine.index
    checks["two_segments_and_delta"] = (idx.num_segments == 2
                                        and idx.delta_fill > 0)
    c3 = compilation_cache_stats()
    t0 = time.perf_counter()
    engine.warmup()
    log("mutate", {"inserted": n_ins, "deleted": int(dead.shape[0]),
                   "segments": [s.size for s in idx.segments],
                   "delta_rows": int(round(idx.delta_fill * idx.delta_cap)),
                   "rewarm_seconds": time.perf_counter() - t0,
                   "rewarm_cache": _cache_delta(c3,
                                                compilation_cache_stats())})

    d2, i2, ms2 = serve(queries)
    ds_, is_, _ = serve(inserts)
    log("serve after mutation", {
        "queries": int(queries.shape[0] + inserts.shape[0]),
        "smoke_batch_ms_p50": float(np.percentile(ms2, 50)),
        "smoke_batch_ms_p99": float(np.percentile(ms2, 99))})
    dead_set = set(dead.tolist())
    checks["deleted_never_return"] = not (
        dead_set & set(i2.ravel().tolist())
        or dead_set & set(is_.ravel().tolist()))
    live = ~np.isin(gids, dead)
    found = (is_ == gids[:, None]).any(axis=1)
    checks["inserts_find_themselves"] = bool(found[live].all())
    checks["no_unwarmed_shapes"] = engine.stats["bucket_cold_hits"] == 0
    return checks


def run_four_chips(shape: Shape, cfg, seed: int, devices, log) -> dict:
    """The shard_map path on (1, 4) and (4, 1) meshes vs the flat query."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.index import build_index, make_params, query_index
    from repro.launch import dist_index as di

    t0 = time.perf_counter()
    data, queries, _ = make_data(shape, seed)
    log("data", {"n": shape.n, "dim": shape.dim, "universe": shape.universe,
                 "seconds": time.perf_counter() - t0})
    key = jax.random.PRNGKey(seed)
    params = make_params(cfg, key, shape.dim)

    # Each build runs as one jit program: op by op, the 4M-row build's
    # intermediates overflow a chip's 16 GiB; compiled whole for a v5e, it
    # needs about 13.3 GiB.
    t0 = time.perf_counter()
    one = jax.device_put(data, devices[0])
    state = jax.jit(lambda d, p: build_index(cfg, None, d, params=p))(
        one, params)
    flat = [query_index(cfg, state, jax.device_put(c, devices[0]))
            for c in _batches(queries, shape.batch)]
    fd = np.concatenate([np.asarray(f[0]) for f in flat])
    fi = np.concatenate([np.asarray(f[1]) for f in flat])
    log("flat", {"device": str(devices[0]),
                 "seconds": time.perf_counter() - t0})
    del one, state, flat

    checks = {}
    for rows, cols in ((1, len(devices)), (len(devices), 1)):
        mesh = Mesh(np.array(devices).reshape(rows, cols),
                    ("data", "model"))
        t0 = time.perf_counter()
        with mesh:
            dj = jax.device_put(data, NamedSharding(mesh, P("data", None)))
            qj = jax.device_put(queries,
                                NamedSharding(mesh, P("model", None)))
            st = jax.jit(di.dist_build_fn(cfg, mesh))(dj, params)
            d, i = jax.jit(di.dist_query_fn(cfg, mesh,
                                            merge="allgather"))(st, qj)
            d, i = np.asarray(d), np.asarray(i)
        placed = sorted({(s.device.id, str(s.index[0]))
                         for s in st.dataset.addressable_shards})
        devs = {s.device.id for s in st.sorted_keys.addressable_shards}
        log(f"mesh {rows}x{cols}", {
            "seconds": time.perf_counter() - t0,
            "dataset_shards": [f"device {dev}: rows {sl}"
                               for dev, sl in placed],
            "query_shards": sorted(
                f"device {s.device.id}: rows {s.index[0]}"
                for s in qj.addressable_shards)})
        checks[f"mesh_{rows}x{cols}_on_{len(devices)}_devices"] = (
            len(devs) == len(devices))
        if rows == 1:
            checks["mesh_1x4_equals_flat"] = bool(
                np.array_equal(d, fd) and np.array_equal(i, fi))
        else:
            checks["mesh_4x1_le_flat"] = bool((d <= fd).all())
        del dj, qj, st
    return checks


def _log(stage: str, fields: dict) -> None:
    print(f"{stage}: {json.dumps(fields)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    bad = [v for v in REFUSED_ENV if v in os.environ]
    if bad:
        print(f"refusing to run with {bad} set: executors and interpret "
              f"mode are chosen in code", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"needs {args.chips} TPU chip(s); JAX found {dev}",
              file=sys.stderr)
        return 2
    _log("device", dev)

    if args.chips == 4:
        checks = run_four_chips(SIFT4M, index_config(SIFT4M), args.seed,
                                devices[:4], _log)
    else:
        checks = run_one_chip(SIFT1M, index_config(SIFT1M),
                              serve_config(SIFT1M), args.seed, _log)
    for name, ok in checks.items():
        _log("check", {"name": name, "passed": bool(ok)})
    if not all(checks.values()):
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
