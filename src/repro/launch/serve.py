"""ANN serving launcher (deliverable b: serve a small index with batched
requests — the paper's kind of system).

  PYTHONPATH=src python -m repro.launch.serve --n 20000 --dim 64 --queries 256
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from repro.core.baselines import brute_force_l1, recall
from repro.core.index import IndexConfig
from repro.data import ann_synthetic as ds
from repro.serve.engine import AnnServingEngine, ServeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--universe", type=int, default=128,
                    help="max (even) coordinate U; 510 is SIFT's shape")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--tables", type=int, default=8)
    ap.add_argument("--width", type=int, default=56)
    ap.add_argument("--probes", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--target-recall", type=float, default=None,
                    help="autotune (tables, probes, cap) for this recall@k "
                         "instead of serving --tables/--probes as given")
    args = ap.parse_args(argv)

    spec = ds.DatasetSpec("serve", n=args.n, dim=args.dim, universe=args.universe,
                          num_clusters=32)
    data = ds.make_dataset(spec)
    queries = ds.make_queries(spec, data, args.queries)

    cfg = IndexConfig(num_tables=args.tables, num_hashes=12, width=args.width,
                      num_probes=args.probes, candidate_cap=128,
                      universe=spec.universe, k=args.k, rerank_chunk=1024)
    engine = AnnServingEngine(
        cfg, ServeConfig(batch_size=args.batch,
                         target_recall=args.target_recall),
        jnp.asarray(data))
    engine.submit(queries)
    d, i = engine.drain()

    td, ti = brute_force_l1(jnp.asarray(data), jnp.asarray(queries), args.k)
    r = recall(i, np.asarray(ti))
    print(json.dumps({"recall": round(r, 4), **engine.summary()}, indent=1))


if __name__ == "__main__":
    main()
