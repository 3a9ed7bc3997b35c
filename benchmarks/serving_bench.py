"""Serving-engine shape-bucket benchmark (ISSUE 2 acceptance).

Streams mixed batch sizes through ``AnnServingEngine`` and demonstrates the
shape-bucket policy (DESIGN.md §Perf): after ``warmup()`` compiles every
power-of-two bucket, live traffic with arbitrary batch sizes triggers
**zero recompiles** (``bucket_cold_hits`` stays 0), and small batches stop
paying full-batch padding FLOPs.  The legacy pad-to-batch_size policy is
measured side by side.  Emits machine-readable ``BENCH_serving.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core.index import IndexConfig
from repro.data import ann_synthetic as ds
from repro.obs import MetricsRegistry
from repro.obs import trace as obs_trace
from repro.serve.engine import (AnnServingEngine, ServeConfig,
                                compilation_cache_stats)


def run_engine(cfg, serve_cfg, data, bursts):
    cache_before = compilation_cache_stats()
    t0 = time.perf_counter()
    engine = AnnServingEngine(cfg, serve_cfg, data)
    init_ms = (time.perf_counter() - t0) * 1e3
    cold_after_warmup = engine.stats["bucket_cold_hits"]
    rng = np.random.default_rng(7)
    dim = data.shape[1]
    t0 = time.perf_counter()
    for burst in bursts:
        engine.submit((rng.integers(0, 32, (burst, dim)) * 2).astype(np.int32))
        engine.drain()
    serve_ms = (time.perf_counter() - t0) * 1e3
    s = engine.summary()
    cache_after = compilation_cache_stats()
    return {
        "init_ms": round(init_ms, 1),
        "warmup_ms": round(s["warmup_ms"], 1),
        "serve_ms": round(serve_ms, 1),
        "buckets": s["buckets"],
        "cand_buckets": s["cand_buckets"],
        "batches": s["batches"],
        "recompiles_after_warmup": s["bucket_cold_hits"] - cold_after_warmup,
        "cache_hits": cache_after["hits"] - cache_before["hits"],
        "cache_misses": cache_after["misses"] - cache_before["misses"],
        "p50_batch_ms": round(s["p50_batch_ms"], 3),
        "p99_batch_ms": round(s["p99_batch_ms"], 3),
        "queries_per_s": round(s["queries_per_s"], 1),
    }


# -- persistent-cache warm-start probe (DESIGN.md §8) -----------------------
# Engine start is compile-dominated (init + warmup >> serve).  The JAX
# persistent compilation cache makes every restart after the first read its
# executables off disk; since jit's in-memory cache would mask that inside
# one process, the demonstration runs this same script twice as a CPU
# subprocess against a shared JAX_COMPILATION_CACHE_DIR and compares
# init+warmup.

def _inner_probe() -> None:
    spec = ds.DatasetSpec("warm", n=400, dim=8, universe=32, num_clusters=4)
    cfg = IndexConfig(num_tables=2, num_hashes=6, width=16, num_probes=10,
                      candidate_cap=8, universe=32, k=4, rerank_chunk=64)
    data = np.asarray(ds.make_dataset(spec))
    t0 = time.perf_counter()
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, bucket_min=8, delta_cap=64), data)
    init_ms = (time.perf_counter() - t0) * 1e3
    s = engine.summary()
    print(json.dumps({
        "init_ms": round(init_ms, 1),
        "warmup_ms": round(s["warmup_ms"], 1),
        "cache": s["compile_cache"],
    }))


def warm_start_demo() -> dict:
    with tempfile.TemporaryDirectory() as cache_dir:
        runs = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--inner-probe"],
                capture_output=True, text=True, check=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "JAX_COMPILATION_CACHE_DIR": cache_dir})
            runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    cold_total = cold["init_ms"]
    warm_total = warm["init_ms"]
    return {
        "platform": "cpu",
        "cold": cold,
        "warm": warm,
        "startup_speedup": round(cold_total / max(warm_total, 1e-9), 2),
        "warm_start_effective": bool(
            warm["cache"]["hits"] > 0 and warm_total < cold_total),
    }


# -- tracing-off overhead gate (DESIGN.md §12) ------------------------------
# The ISSUE 9 budget: observability must cost <=1% of batch p50 when
# REPRO_TRACE is off.  The off-path cost is a fixed set of primitives — a
# no-op span (env check + shared null context manager), a histogram record
# (two int adds), a counter bump through the registry facade — so the gate
# microbenchmarks each primitive, multiplies by a GENEROUS per-batch call
# count (several x what the engine + router hot paths actually execute),
# and compares against the measured serving p50.  Deterministic and
# noise-free where an A/B of two full serving runs would flap in CI.

# per-batch ceilings at ~2-3x the real counts of the path the denominator
# measures: the bench p50 is the ENGINE batch p50, and an engine batch
# executes exactly capture_begin + the engine_batch span (2 span-path
# calls), ~6 counter bumps, and 1 histogram record.  The router's own
# span/counter calls run in the router process against its multi-ms
# dispatch latency — they never sit on an engine batch, so they are not
# multiplied against the engine p50 here.
_SPANS_PER_BATCH = 6
_COUNTERS_PER_BATCH = 12
_HISTS_PER_BATCH = 2


def trace_off_overhead(p50_ms: float, iters: int = 50_000) -> dict:
    saved = os.environ.pop("REPRO_TRACE", None)
    try:
        reg = MetricsRegistry("bench")
        hist = reg.histogram("h")
        t0 = time.perf_counter()
        for _ in range(iters):
            with obs_trace.span("x", attr=1):
                pass
        span_ns = (time.perf_counter() - t0) / iters * 1e9
        t0 = time.perf_counter()
        for _ in range(iters):
            reg["c"] += 1
        counter_ns = (time.perf_counter() - t0) / iters * 1e9
        t0 = time.perf_counter()
        for _ in range(iters):
            hist.record_ms(0.123)
        hist_ns = (time.perf_counter() - t0) / iters * 1e9
    finally:
        if saved is not None:
            os.environ["REPRO_TRACE"] = saved
    per_batch_ms = (_SPANS_PER_BATCH * span_ns
                    + _COUNTERS_PER_BATCH * counter_ns
                    + _HISTS_PER_BATCH * hist_ns) / 1e6
    frac = per_batch_ms / max(p50_ms, 1e-9)
    return {
        "null_span_ns": round(span_ns, 1),
        "counter_inc_ns": round(counter_ns, 1),
        "hist_record_ns": round(hist_ns, 1),
        "per_batch_ms": round(per_batch_ms, 6),
        "p50_batch_ms": p50_ms,
        "frac_of_p50": round(frac, 6),
        "budget": 0.01,
        "ok": bool(frac <= 0.01),
    }


def main(smoke: bool = False, json_out: str = "BENCH_serving.json",
         skip_warm_start: bool = False):
    if smoke:
        spec = ds.DatasetSpec("srv", n=1500, dim=16, universe=64,
                              num_clusters=6)
        cfg = IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=20,
                          candidate_cap=16, universe=64, k=8, rerank_chunk=128)
        batch, rounds = 32, 2
    else:
        spec = ds.DatasetSpec("srv", n=20000, dim=32, universe=64,
                              num_clusters=16)
        cfg = IndexConfig(num_tables=6, num_hashes=10, width=32, num_probes=50,
                          candidate_cap=32, universe=64, k=10,
                          rerank_chunk=512)
        batch, rounds = 64, 4
    data = np.asarray(ds.make_dataset(spec))
    # mixed live traffic: every size class appears, repeated across rounds
    rng = np.random.default_rng(0)
    sizes = [1, 3, 7, 8, 13, 17, batch // 2, batch - 1, batch]
    bursts = [int(s) for _ in range(rounds) for s in rng.permutation(sizes)]

    result = {
        "bench": "serving_shape_buckets",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "config": {"n": spec.n, "dim": spec.dim, "batch_size": batch,
                   "bursts": len(bursts)},
        "bucketed": run_engine(
            cfg, ServeConfig(batch_size=batch, delta_cap=256,
                             shape_buckets=True), data, bursts),
        "legacy_fixed": run_engine(
            cfg, ServeConfig(batch_size=batch, delta_cap=256,
                             shape_buckets=False), data, bursts),
        "full_slab": run_engine(
            cfg, ServeConfig(batch_size=batch, delta_cap=256,
                             compact_probe=False), data, bursts),
        "compilation_cache": compilation_cache_stats(),
    }
    if not skip_warm_start:
        result["warm_start"] = warm_start_demo()
    result["trace_off_overhead"] = trace_off_overhead(
        result["bucketed"]["p50_batch_ms"])
    ok = result["bucketed"]["recompiles_after_warmup"] == 0
    result["zero_recompiles_after_warmup"] = ok
    with open(json_out, "w") as f:
        json.dump(result, f, indent=1)
    b, l = result["bucketed"], result["legacy_fixed"]
    ws = result.get("warm_start", {})
    print(f"serving buckets={b['buckets']} cand_buckets={b['cand_buckets']} "
          f"recompiles_after_warmup={b['recompiles_after_warmup']} "
          f"p50={b['p50_batch_ms']}ms (legacy p50={l['p50_batch_ms']}ms, "
          f"full-slab p50={result['full_slab']['p50_batch_ms']}ms) "
          f"warm_start x{ws.get('startup_speedup', 'skipped')} "
          f"obs_overhead={result['trace_off_overhead']['frac_of_p50']:.4%} "
          f"of p50 -> {json_out}")
    if not ok:
        raise SystemExit("shape buckets recompiled after warm-up")
    if not result["trace_off_overhead"]["ok"]:
        raise SystemExit(
            "tracing-off observability overhead exceeds 1% of batch p50: "
            f"{result['trace_off_overhead']}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json-out", default="BENCH_serving.json")
    ap.add_argument("--skip-warm-start", action="store_true",
                    help="skip the 2-subprocess persistent-cache demo")
    ap.add_argument("--inner-probe", action="store_true",
                    help=argparse.SUPPRESS)  # warm_start_demo child mode
    args = ap.parse_args()
    if args.inner_probe:
        _inner_probe()
    else:
        main(smoke=args.smoke, json_out=args.json_out,
             skip_warm_start=args.skip_warm_start)
