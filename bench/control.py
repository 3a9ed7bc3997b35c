#!/usr/bin/env python3
"""Run the control of a cell at the cell's own size and print its readings.

    python3 bench/control.py --workload sift1m.batch64 --seeds 1 2 3

The control stands where the program stands: the cell's own loop sends
the queries a run with that seed sends (its data, pool and order), and
brute force whose distances are computed and kept in bfloat16 answers
them, batch by batch, until the recall's set is answered; the run's own
comparison judges the answers.  It has to come out not correct; its
readings are the upper ends the limits in PERF.md were set below.  One
JSON line per seed.  The benchmark's runs do not run it.
"""
import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Control:
    """The reference in the program's place: ``query_batch`` answers with
    bfloat16 brute force over ``data``."""

    def __init__(self, data, k: int):
        self.data, self.k = data, k

    def query_batch(self, queries):
        from bench import reference
        return reference.knn_bf16(self.data, queries, self.k)


def readings(cell: dict, seed: int) -> dict:
    """The control's readings for one seed of a resolved cell."""
    import numpy as np

    from bench import data as bd
    from bench import reference

    law = cell["config"]["data"]
    k = cell["config"]["index"]["k"]
    keys = bd.seed_keys(seed)
    data = bd.make_data(keys["data"], n=law["n"], dim=law["dim"],
                        universe=law["universe"],
                        num_clusters=law["num_clusters"],
                        cluster_spread=law["cluster_spread"])
    pool = np.asarray(bd.make_queries(
        keys["queries"], data, count=law["queries"],
        universe=law["universe"], perturb_frac=law["perturb_frac"]))
    window = cell["drive"](Control(data, k), pool, cell["traffic"], 0.0,
                           lambda name: contextlib.nullcontext(),
                           np.random.default_rng(seed % (1 << 64)),
                           lambda: None)
    out = reference.compare(data, window["queries"], window["dists"],
                            window["ids"], window["recall_queries"], k)
    out["correct"] = (out["wrong_answers"] == 0 and out["recall_at_10"]
                      >= cell["config"]["guarantees"]["recall_at_10"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import jax

    from bench import harness

    cell = harness.resolve(harness.load_spec(), args.workload)
    device = jax.devices()[0]
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": device.device_kind,
                          **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
