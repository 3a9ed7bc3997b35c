"""Kernel micro-bench: Pallas (interpret on CPU / Mosaic on TPU) vs jnp ref.

On CPU the absolute numbers measure the interpreter, NOT the TPU kernel —
the structural quantity we report is the roofline-relevant arithmetic
intensity per kernel (FLOPs or bytes per output element), which is
hardware-independent, plus wall time of the jnp reference for regression
tracking.

``--rerank-json BENCH_rerank.json`` (default on) additionally runs the
rerank-stage benchmark — the served rerank (id dedup + chunked L1 + one
selection sort) vs a sort-dedup + chunked scan + lax.top_k vs a naive full
materialize + sort — and emits a machine-readable JSON so the perf
trajectory is tracked from ISSUE 2 onward.  ``--smoke`` shrinks every shape
for CPU-only CI runners.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as bl
from repro.core import walks as wl
from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def rerank_bench(smoke: bool = False, json_out: str = "BENCH_rerank.json"):
    """Rerank-stage shootout; returns the result dict and writes ``json_out``.

    All three variants consume the RAW (non-deduplicated) candidate gather,
    i.e. each timing includes that path's duplicate-suppression cost — that
    is the pipeline-level comparison (dedup is part of the rerank contract).
    """
    if smoke:
        cfg = dict(n=1500, m=32, q=8, ctot=512, k=10, chunk=128, reps=3)
    else:
        cfg = dict(n=20000, m=64, q=32, ctot=4096, k=50, chunk=256, reps=5)
    rng = np.random.default_rng(0)
    n, m, q, ctot, k, chunk = (cfg[x] for x in
                               ("n", "m", "q", "ctot", "k", "chunk"))
    dataset = jnp.asarray(rng.integers(0, 200, (n, m)).astype(np.int32))
    queries = jnp.asarray(rng.integers(0, 200, (q, m)).astype(np.int32))
    # probe-shaped candidates: clustered ids with duplicates + ~10% sentinel
    ids_np = rng.integers(0, n, (q, ctot)).astype(np.int32)
    ids_np[rng.random((q, ctot)) < 0.1] = n
    ids = jnp.asarray(ids_np)

    @jax.jit
    def scan_path(ds, qs, cand):   # sort-dedup + chunked scan+top_k
        srt = jnp.sort(cand, axis=-1)
        dup = jnp.concatenate([jnp.zeros((q, 1), bool),
                               srt[:, 1:] == srt[:, :-1]], axis=-1)
        return bl.l1_distance_chunked(
            ds, qs, jnp.where(dup, n, srt), k, chunk)

    @jax.jit
    def fused_path(ds, qs, cand):  # the served rerank
        return ops.fused_rerank(ds, qs, cand, k, chunk=chunk)

    @jax.jit
    def naive_path(ds, qs, cand):  # full (Q, Ctot, m) materialize + sort
        return ref.fused_rerank(ds, qs, cand, k)

    variants = {"scan_topk": scan_path, "fused": fused_path,
                "naive": naive_path}
    us, outs = {}, {}
    for name, fn in variants.items():
        us[name] = _time(fn, dataset, queries, ids, reps=cfg["reps"])
        outs[name] = tuple(np.asarray(x) for x in fn(dataset, queries, ids))
    for name in ("fused", "naive"):   # all paths must agree bit-for-bit
        np.testing.assert_array_equal(outs["scan_topk"][0], outs[name][0])
        np.testing.assert_array_equal(outs["scan_topk"][1], outs[name][1])
    result = {
        "bench": "rerank_stage",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "config": {x: cfg[x] for x in ("n", "m", "q", "ctot", "k", "chunk")},
        "us_per_call": {name: round(v, 1) for name, v in us.items()},
        "fused_speedup_vs_scan": round(us["scan_topk"] / us["fused"], 3),
        "fused_speedup_vs_naive": round(us["naive"] / us["fused"], 3),
        "outputs_bit_identical": True,
    }
    with open(json_out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"rerank_stage: fused {us['fused']:.0f}us  "
          f"scan+top_k {us['scan_topk']:.0f}us  naive {us['naive']:.0f}us  "
          f"-> {result['fused_speedup_vs_scan']:.2f}x vs scan "
          f"({json_out})")
    return result


def main(smoke: bool = False, rerank_json: str = "BENCH_rerank.json"):
    rng = np.random.default_rng(0)
    rows = []

    q = jnp.asarray(rng.integers(0, 200, (64, 128)).astype(np.int32))
    x = jnp.asarray(rng.integers(0, 200, (4096, 128)).astype(np.int32))
    us_ref = _time(lambda a, b: ref.l1_distance(a, b).block_until_ready()
                   if False else ref.l1_distance(a, b), q, x)
    # arithmetic intensity: 2*m ops per output, 2*m*4B streamed naive
    rows.append(("l1_distance_ref_64x4096x128", us_ref,
                 f"ops_per_out={2*128};bytes_per_out~{8*128/64:.0f}"))

    wt = wl.make_walks(jax.random.PRNGKey(0), 128, 128, 256)
    pts = jnp.asarray((rng.integers(0, 129, (256, 128)) * 2).astype(np.int32))
    us_g = _time(lambda w, p: wl.eval_prefix(w, p), wt, pts)
    us_t = _time(lambda pr, p: ref.rw_hash(pr, p), wt.pairs, pts)
    rows.append(("rw_hash_gather_256x128x128f", us_g, "paper_lookup_path"))
    rows.append(("rw_hash_thermo_ref_256x128x128f", us_t,
                 "mxu_path_flops_per_hash=%d" % (2 * 128 * 128)))

    da = jnp.sort(jnp.asarray(rng.integers(0, 1000, (256, 64)).astype(np.int32)), -1)
    db = jnp.sort(jnp.asarray(rng.integers(0, 1000, (256, 64)).astype(np.int32)), -1)
    ia = jnp.zeros((256, 64), jnp.int32); ib = ia + 1
    us_m = _time(lambda *a: ref.topk_merge(*a)[0], da, ia, db, ib)
    rows.append(("topk_merge_ref_256x64", us_m,
                 "ring_step_bytes=%d" % (256 * 64 * 8)))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    rerank_bench(smoke=smoke, json_out=rerank_json)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CPU-only CI runners")
    ap.add_argument("--rerank-json", default="BENCH_rerank.json")
    args = ap.parse_args()
    main(smoke=args.smoke, rerank_json=args.rerank_json)
