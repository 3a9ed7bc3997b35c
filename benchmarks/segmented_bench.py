"""Segmented-index serving benchmark: mutation + query cost vs fragmentation.

Measures (a) query latency as the index fragments (1 segment -> sealed
segments + delta), (b) insert throughput into the delta buffer, and
(c) major-compaction cost — the knobs DESIGN.md Sect. 3 exposes for tuning
candidate generation vs rerank per workload.

Emits ``name,us_per_call,derived`` CSV rows like the other benchmarks.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import IndexConfig, build_index, query_index
from repro.core.segments import SegmentedIndex
from repro.data import ann_synthetic as ds


def _timeit(fn, reps: int = 5) -> float:
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def main() -> None:
    spec = ds.DatasetSpec("segbench", n=16_384, dim=64, universe=128,
                          num_clusters=32)
    data = jnp.asarray(ds.make_dataset(spec))
    queries = jnp.asarray(ds.make_queries(spec, np.asarray(data), 64))
    cfg = IndexConfig(num_tables=6, num_hashes=10, width=48, num_probes=60,
                      candidate_cap=32, universe=spec.universe, k=10)
    key = jax.random.PRNGKey(0)

    mono = build_index(cfg, key, data)
    us = _timeit(lambda: query_index(cfg, mono, queries)[0].block_until_ready())
    print(f"monolithic_query,{us:.1f},n={spec.n}")

    idx = SegmentedIndex.from_dataset(cfg, key, data, delta_cap=1024)

    def served():
        return idx.query_compact(queries)[0].block_until_ready()

    us = _timeit(served)
    print(f"segmented_query_1seg,{us:.1f},segments=1")

    rng = np.random.default_rng(0)
    batch = (rng.integers(0, spec.universe // 2, (256, spec.dim)) * 2
             ).astype(np.int32)
    t0 = time.perf_counter()
    total = 0
    while idx.num_segments < 4:       # fragment: seal several segments
        idx.insert(batch)
        total += batch.shape[0]
    ins_us = (time.perf_counter() - t0) / total * 1e6
    print(f"insert_per_point,{ins_us:.2f},points={total}")

    us = _timeit(served)
    print(f"segmented_query_{idx.num_segments}seg,{us:.1f},"
          f"segments={idx.num_segments} delta_fill={idx.delta_fill:.2f}")

    idx.delete(np.arange(0, 512, dtype=np.int32))
    us = _timeit(served)
    print(f"segmented_query_tombstoned,{us:.1f},tombstones={idx.num_tombstones}")

    t0 = time.perf_counter()
    idx.compact()
    print(f"compact,{(time.perf_counter() - t0) * 1e6:.0f},live={idx.num_live}")

    us = _timeit(served)
    print(f"segmented_query_postcompact,{us:.1f},segments={idx.num_segments}")


if __name__ == "__main__":
    main()
