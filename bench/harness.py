"""Find a cell's parts by name, run it once, and build its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* a configuration: its ``file`` (``bench/configs/<name>.json``);
* a traffic mix: ``<dir>/traffic/<name>.json``, its parameters; its
  ``loop`` names the client that sends it, ``<dir>/loops/<loop>.py``, a
  module whose ``drive(engine, pool, mix, seconds, span, rng, closed)``
  runs the window (``bench/loops/closed.py`` says what it returns);
* a per-layer metric: ``<dir>/metrics/<name>.py``, a module whose
  ``read(run, trace)`` returns the value or None when it finds nothing.

``<dir>`` is ``bench/`` unless the caller passes other directories to look
in first, as the tests do for a cell that exists only as a fixture.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from functools import partial
from typing import Callable, Sequence

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")

__all__ = ["load_spec", "resolve", "run_cell", "read_peaks", "BENCH", "ROOT"]


def load_spec(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}")


def _find(dirs: Sequence[str], *parts: str) -> str:
    for d in dirs:
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"{os.path.join(*parts)} in none of {list(dirs)}")


def _load(path: str, kind: str, attr: str):
    name = f"bench_{kind}_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, attr)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, root: str = ROOT,
            dirs: Sequence[str] = ()) -> dict:
    """Everything one cell needs, loaded: its entry, configuration, traffic
    mix with the loop that sends it, end-to-end metrics and per-layer
    metrics with their readers."""
    dirs = list(dirs) + [BENCH]
    cell = _named(spec["workloads"], workload, "workload")
    config = _named(spec["configs"], cell["config"], "configuration")
    with open(os.path.join(root, config["file"])) as f:
        conf = json.load(f)
    with open(_find(dirs, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return {
        "cell": cell,
        "config": conf,
        "traffic": mix,
        "drive": _load(_find(dirs, "loops", mix["loop"] + ".py"), "loop",
                       "drive"),
        "end_to_end": [m for m in spec["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": per_layer,
        "readers": {m["name"]: _load(
            _find(dirs, "metrics", m["name"] + ".py"), "metric", "read")
            for m in per_layer},
    }


def read_peaks(kind: str) -> dict:
    """Published peaks of ``kind``; an unknown device is an error."""
    path = os.path.join(BENCH, "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _span(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _build_fn():
    import jax
    from repro.core.index import build_index

    @partial(jax.jit, static_argnums=0)
    def build(cfg, key, data):
        """The program's ``build_index`` as one jit program.

        Where the rows pass through unchanged (stored dtype == given dtype)
        they are not returned, so the build does not copy them; the caller
        puts the same array back into the state."""
        state = build_index(cfg, key, data)
        if state.dataset.dtype == data.dtype:
            state = dataclasses.replace(state, dataset=None)
        return state

    return build


def _counters(engine) -> dict:
    """The engine's unplanned compiles, the persistent-cache lookups (one
    per program compiled or loaded in this process), and the batches served
    on each candidate rung."""
    from repro.serve.engine import compilation_cache_stats
    cache = compilation_cache_stats()
    return {"bucket_cold_hits": engine.stats["bucket_cold_hits"],
            "cache_lookups": cache["hits"] + cache["misses"],
            "rungs": dict(engine.stats["cand_buckets"])}


def _real_candidates(cfg, index, queries, batch: int) -> int:
    """Candidates ``queries`` really have: phase A's own per-query counts
    under the per-bucket cap (``core.index.probe_index``), summed over the
    index's segments, read back after the window."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.index import probe_index
    pad = -queries.shape[0] % batch
    queries = np.concatenate([queries, queries[:pad]])
    total = 0
    for seg in index.segments:
        if not seg.size:
            continue
        counts = np.concatenate([
            np.asarray(probe_index(cfg, seg.state, jnp.asarray(q))[3])
            for q in queries.reshape(-1, batch, queries.shape[1])])
        total += int(counts[:counts.shape[0] - pad].sum())
    return total


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = _log) -> dict:
    """One run of one cell: set-up, window, check.  Returns the result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import data as bdata
    from bench import reference, trace_reduce
    from repro.core.index import IndexConfig
    from repro.core.segments import SegmentedIndex
    from repro.serve.engine import (AnnServingEngine, ServeConfig,
                                    enable_compilation_cache)

    conf, mix = cell["config"], cell["traffic"]
    law = conf["data"]
    enable_compilation_cache()
    # The deployment (rows, query pool, hash functions) and the order the
    # queries are sent in all come from the run's seed.
    keys = bdata.seed_keys(seed)
    data = bdata.make_data(keys["data"], n=law["n"], dim=law["dim"],
                           universe=law["universe"],
                           num_clusters=law["num_clusters"],
                           cluster_spread=law["cluster_spread"])
    pool = np.asarray(bdata.make_queries(
        keys["queries"], data, count=law["queries"],
        universe=law["universe"], perturb_frac=law["perturb_frac"]))
    log(f"data: {law['n']} x {law['dim']} rows, {pool.shape[0]} queries, "
        f"{time.perf_counter() - t_start:.2f} s since start")

    cfg = IndexConfig(universe=law["universe"], **conf["index"])
    serve_cfg = ServeConfig(**conf["serve"])
    t = time.perf_counter()
    state = _build_fn()(cfg, keys["hashes"], data)
    if state.dataset is None:
        state = dataclasses.replace(state, dataset=data)
    jax.block_until_ready(state)
    build_s = time.perf_counter() - t
    n = law["n"]
    t = time.perf_counter()
    index = SegmentedIndex.from_checkpoint(
        cfg, state, jnp.arange(n, dtype=jnp.int32), n,
        delta_cap=serve_cfg.delta_cap,
        cap_quantile=serve_cfg.cand_cap_quantile,
        cap_sample=serve_cfg.cand_cap_sample)
    engine = AnnServingEngine(cfg, serve_cfg, index=index)  # warms up
    # One batch through the served path: the engine's warmup compiles the
    # query programs but not what its first served batch adds (the flight
    # recorder's slow-batch preview), which would otherwise compile in the
    # window.
    engine.query_batch(pool[:serve_cfg.batch_size])
    warmup_s = time.perf_counter() - t
    ladders = [[list(r) for r in ladder] for ladder
               in index.candidate_ladders(serve_cfg.cand_bucket_min)]
    log(f"build {build_s:.3f} s, warmup {warmup_s:.3f} s, rungs {ladders}")

    before = _counters(engine)
    after = {}

    def closed():
        """The window's end: its counters, and the trace stops."""
        after.update(_counters(engine))
        if trace:
            jax.profiler.stop_trace()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    setup_s = time.perf_counter() - t_start
    window = cell["drive"](engine, pool, mix, seconds, _span(trace),
                           np.random.default_rng(seed % (1 << 64)), closed)
    compiles = sum(after[k] - before[k]
                   for k in ("bucket_cold_hits", "cache_lookups"))
    rungs = {int(cb): after["rungs"].get(cb, 0) - before["rungs"].get(cb, 0)
             for cb in after["rungs"]}
    rungs = {cb: c for cb, c in sorted(rungs.items()) if c}
    served = window["attempted"]
    log(f"window: {served} queries in {window['batches']} batches, "
        f"{window['metrics']}, batches by candidate rung {rungs}, "
        f"compiles {compiles}")

    run = {"build_s": build_s, "warmup_s": warmup_s, "setup_s": setup_s,
           **window["metrics"], "attempted": served,
           "batches": window["batches"], "rungs": rungs, "dim": law["dim"],
           "itemsize": np.dtype(cfg.dataset_dtype).itemsize}
    if trace:
        run["candidates"] = _real_candidates(
            cfg, index, window["queries"][:served], serve_cfg.batch_size)
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del engine, index, state

    t = time.perf_counter()
    readings = reference.compare(data, window["queries"], window["dists"],
                                 window["ids"], window["recall_queries"],
                                 cfg.k)
    log(f"reference: {time.perf_counter() - t:.3f} s over "
        f"{window['queries'].shape[0]} answered queries")
    run["recall_at_10"] = readings["recall_at_10"]

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics = {}
    summary = None
    if trace:
        path = trace_reduce.find_trace(TRACE_DIR)
        summary = trace_reduce.reduce_file(path) if path else None
        if summary is None:
            raise RuntimeError("the traced run left no trace with a window")
        run["peaks"] = read_peaks(device["kind"])
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        for m in cell["per_layer"]:
            value = cell["readers"][m["name"]](run, summary)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": run[m["name"]], "unit": m["unit"]}

    checks = {
        "recall_at_10": {"value": readings["recall_at_10"],
                         "at_least": conf["guarantees"]["recall_at_10"]},
        "wrong_answers": {"value": readings["wrong_answers"], "at_most": 0},
        "window_compiles": {"value": compiles, "at_most": 0},
    }
    correct = all(c["value"] >= c["at_least"] if "at_least" in c
                  else c["value"] <= c["at_most"] for c in checks.values())
    result = {"correct": correct, "attempted": served,
              "failed": readings["failed_queries"], "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["checks"] = checks
    return result
