"""Paper Sect. 5 quality protocol (ISSUE 3 acceptance): recall@k curves for
all five schemes on one shared exact ground truth, the "tables needed to hit
recall R" headline statistic, the cross-layer consistency oracle (flat vs
segmented-mutated-compacted vs distributed all-gather vs the sharded
cluster runtime, incl. kill + WAL-replay recovery), and an autotuner
demonstration — persisted as machine-readable ``BENCH_quality.json``.

The smoke config must show MP-RW-LSH reaching recall >= 0.9 with strictly
fewer hash tables than CP-LSH (the paper's 15-53x claim, scaled to CI), and
the mutated-then-compacted ``SegmentedIndex`` matching the fresh-build
recall exactly.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.data import ann_synthetic as ds
from repro.eval import QualityRun, QualitySpec, tune_for_recall

TARGET = 0.9


def main(smoke: bool = False, json_out: str = "BENCH_quality.json"):
    t_start = time.time()
    if smoke:
        dspec = ds.DatasetSpec("quality-smoke", n=4096, dim=32, universe=128,
                               num_clusters=16, seed=3)
        qspec = QualitySpec(k=10, table_sweep=(1, 2, 4, 8, 16),
                            table_sweep_single=(4, 8, 16, 32, 64),
                            probe_sweep=(60,), candidate_cap=32,
                            num_hashes_rw=10, num_hashes_cp=8,
                            rerank_chunk=512, srs_t=512, target_recall=TARGET)
        n_queries, table_ladder = 32, (1, 2, 4, 8, 16)
    else:
        dspec = ds.DatasetSpec("quality-glove", n=32768, dim=100, universe=512,
                               num_clusters=48, seed=2)
        qspec = QualitySpec(k=10, table_sweep=(1, 2, 4, 8, 16, 32),
                            table_sweep_single=(8, 16, 32, 64, 128),
                            probe_sweep=(50, 150), candidate_cap=64,
                            num_hashes_rw=12, num_hashes_cp=8,
                            rerank_chunk=1024, srs_t=1024,
                            target_recall=TARGET)
        n_queries, table_ladder = 64, (1, 2, 4, 8, 16, 32)

    data = ds.make_dataset(dspec)
    queries = ds.make_queries(dspec, data, n_queries)
    run = QualityRun(data, queries, dspec.universe, qspec)

    records = run.sweep(timed=True)
    claim = run.table_claim(records)
    l_mp = claim["tables_needed"].get("mp-rw-lsh")
    l_cp = claim["tables_needed"].get("cp-lsh")

    # Cross-layer oracle at the claim config (the smallest MP-RW config that
    # meets the target — the one whose quality number the claim rests on).
    oracle_cfg = run.scheme_config(
        "mp-rw-lsh", l_mp or max(qspec.table_sweep), qspec.probe_sweep[-1])
    consistency = run.check_cross_layer(oracle_cfg)

    # Autotuner demonstration: derive (L, T, cap) for the target from the
    # analytical success model, then validate on a calibration split.
    base_cfg = run.scheme_config("mp-rw-lsh", 4, qspec.probe_sweep[-1])
    tuned = tune_for_recall(base_cfg, data, TARGET, num_calib=24,
                            table_ladder=table_ladder, mc_runs=32)

    # best recall over probe counts at l_mp: tables_needed picks l_mp over
    # ANY probe count, so the claim must be checked against the same max
    mp_rec = [r["recall"] for r in records if r["scheme"] == "mp-rw-lsh"
              and r["num_tables"] == l_mp] if l_mp else []
    acceptance = {
        "schemes_on_shared_gt": len({r["scheme"] for r in records}),
        "mp_recall_ge_target": bool(mp_rec and max(mp_rec) >= TARGET),
        # l_cp None means CP-LSH never reached the target within its (wider)
        # sweep — still strictly more tables than MP-RW needed.
        "mp_fewer_tables_than_cp": bool(
            l_mp is not None and (l_cp is None or l_mp < l_cp)),
        "compacted_matches_fresh": consistency["compacted_matches_fresh"],
        "segmented_matches_flat": consistency["segmented_matches_flat"],
        "compact_segmented_matches_flat":
            consistency["compact_segmented_matches_flat"],
        "mutated_no_regression": consistency["mutated_no_regression"],
        "dist_matches_flat": consistency["dist_matches_flat"],
        "cluster_matches_flat": consistency["cluster_matches_flat"],
        "cluster_recovery_matches_flat":
            consistency["cluster_recovery_matches_flat"],
        "autotune_met_target": tuned.met_target,
    }
    acceptance["ok"] = all(v for k, v in acceptance.items()
                           if k != "schemes_on_shared_gt") \
        and acceptance["schemes_on_shared_gt"] >= 4

    result = {
        "bench": "quality_protocol",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "config": {"dataset": dspec.name, "n": dspec.n, "dim": dspec.dim,
                   "universe": dspec.universe, "queries": n_queries,
                   "k": qspec.k, "target_recall": TARGET,
                   "w_rw": run.w_rw, "w_cp": run.w_cp,
                   "dbar_knn": round(run.dbar, 1)},
        "records": [{k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in r.items()} for r in records],
        "table_claim": claim,
        "consistency": {k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in consistency.items()},
        "autotune": {
            "target_recall": tuned.target_recall,
            "num_tables": tuned.cfg.num_tables,
            "num_probes": tuned.cfg.num_probes,
            "candidate_cap": tuned.cfg.candidate_cap,
            "predicted_recall": round(tuned.predicted_recall, 4),
            "validated_recall": round(tuned.validated_recall, 4),
            "met_target": tuned.met_target,
            "rounds": tuned.rounds,
        },
        "acceptance": acceptance,
        "wall_s": round(time.time() - t_start, 1),
    }
    with open(json_out, "w") as f:
        json.dump(result, f, indent=1)

    cp_str = ("never within "
              f"L<={claim['sweep_max_tables']}" if l_cp is None else str(l_cp))
    print(f"quality target={TARGET} tables_needed: mp-rw={l_mp} "
          f"cp={cp_str} | compacted==fresh:"
          f"{acceptance['compacted_matches_fresh']} dist==flat:"
          f"{acceptance['dist_matches_flat']} | autotune L="
          f"{tuned.cfg.num_tables} validated={tuned.validated_recall:.3f} "
          f"-> {json_out} ({result['wall_s']}s)")
    for r in records:
        print(f"#  {r['scheme']:10s} L={r['num_tables']:3d} "
              f"T={r['num_probes']:3d} recall={r['recall']:.4f} "
              f"ratio={r['ratio']:.4f}")
    if not acceptance["ok"]:
        raise SystemExit(f"quality acceptance failed: {acceptance}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json-out", default="BENCH_quality.json")
    main(**vars(ap.parse_args()))
