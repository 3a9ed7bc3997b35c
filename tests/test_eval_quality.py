"""Quality-evaluation subsystem (ISSUE 3): shared-GT harness, table-count
claim machinery, recall metric robustness, autotuner, and the cross-layer
consistency oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as bl
from repro.core.index import IndexConfig
from repro.data import ann_synthetic as ds
from repro.eval import (QualityRun, QualitySpec, predicted_recall,
                        tables_needed, tune_for_recall)
from repro.serve.engine import AnnServingEngine, ServeConfig

SPEC = ds.DatasetSpec("evalq", n=2048, dim=16, universe=64, num_clusters=8,
                      seed=5)
QSPEC = QualitySpec(k=8, table_sweep=(1, 2, 4), probe_sweep=(30,),
                    candidate_cap=32, num_hashes_rw=8, num_hashes_cp=8,
                    rerank_chunk=256, srs_t=256, target_recall=0.8)


@pytest.fixture(scope="module")
def run():
    data = ds.make_dataset(SPEC)
    queries = ds.make_queries(SPEC, data, 16)
    return QualityRun(data, queries, SPEC.universe, QSPEC)


# ---------------------------------------------------------------------------
# recall metric (satellite: docstring/denominator fix + robustness)
# ---------------------------------------------------------------------------

def test_recall_denominator_is_ground_truth():
    # result row holds 2 of the 4 true ids -> 0.5, regardless of result size
    res = np.array([[1, 2, 99, 98, 97, 96, 95, 94]])
    true = np.array([[1, 2, 3, 4]])
    assert bl.recall(res, true) == pytest.approx(0.5)


def test_recall_duplicate_ids_count_once():
    res = np.array([[1, 1, 1, 1]])
    true = np.array([[1, 2, 3, 4]])
    assert bl.recall(res, true) == pytest.approx(0.25)


def test_recall_ignores_negative_padding():
    res = np.array([[1, -1, -1, -1]])
    true = np.array([[1, 2]])
    assert bl.recall(res, true) == pytest.approx(0.5)
    # padding in the truth row is dropped from the denominator too
    assert bl.recall(np.array([[1, 2]]), np.array([[1, -1]])) == 1.0


def test_recall_k_mismatched_rows():
    # result row shorter than truth row and vice versa
    assert bl.recall(np.array([[1]]), np.array([[1, 2, 3, 4]])) == 0.25
    assert bl.recall(np.array([[1, 2, 3, 4]]), np.array([[1]])) == 1.0


def test_recall_empty_inputs_do_not_divide_by_zero():
    assert bl.recall(np.zeros((0, 4), np.int32), np.zeros((0, 4))) == 0.0
    assert bl.recall(np.array([[-1, -1]]), np.array([[-1, -1]])) == 0.0


def test_recall_row_count_mismatch_raises():
    # zip would silently truncate; that is a caller bug, not raggedness
    with pytest.raises(ValueError, match="row count"):
        bl.recall(np.ones((2, 4), np.int32), np.ones((5, 4), np.int32))
    with pytest.raises(ValueError, match="row count"):
        bl.recall(np.ones((5, 4), np.int32), np.ones((2, 4), np.int32))


def test_recall_perfect_and_averaged():
    res = np.array([[1, 2], [5, 6]])
    true = np.array([[1, 2], [7, 8]])
    assert bl.recall(res, true) == pytest.approx(0.5)  # (1.0 + 0.0) / 2


# ---------------------------------------------------------------------------
# QualityRun harness
# ---------------------------------------------------------------------------

def test_sweep_shared_ground_truth_and_curves(run):
    records = run.sweep(schemes=("mp-rw-lsh", "cp-lsh", "srs"))
    schemes = {r["scheme"] for r in records}
    assert schemes == {"mp-rw-lsh", "cp-lsh", "srs"}
    for r in records:
        assert 0.0 <= r["recall"] <= 1.0
        assert r["ratio"] >= 1.0 - 1e-9  # exact rerank: never beats truth
    mp = sorted([r for r in records if r["scheme"] == "mp-rw-lsh"],
                key=lambda r: r["num_tables"])
    # more tables -> recall curve ends above where it starts
    assert mp[-1]["recall"] >= mp[0]["recall"]
    # multiprobe beats single-probe of the same family budget-for-budget
    cp = {r["num_tables"]: r["recall"] for r in records
          if r["scheme"] == "cp-lsh"}
    assert mp[-1]["recall"] > cp[max(cp)] - 1e-9


def test_tables_needed_and_claim(run):
    records = [
        {"scheme": "mp-rw-lsh", "num_tables": 2, "num_probes": 30,
         "recall": 0.95, "ratio": 1.0},
        {"scheme": "mp-rw-lsh", "num_tables": 1, "num_probes": 30,
         "recall": 0.7, "ratio": 1.1},
        {"scheme": "cp-lsh", "num_tables": 16, "num_probes": 0,
         "recall": 0.92, "ratio": 1.0},
        {"scheme": "rw-lsh", "num_tables": 8, "num_probes": 0,
         "recall": 0.5, "ratio": 1.2},
    ]
    assert tables_needed(records, "mp-rw-lsh", 0.9) == 2
    assert tables_needed(records, "cp-lsh", 0.9) == 16
    assert tables_needed(records, "rw-lsh", 0.9) is None
    claim = run.table_claim(records, target=0.9)
    assert claim["tables_needed"]["mp-rw-lsh"] == 2
    assert claim["ratio_vs_mp_rw"]["cp-lsh"] == 8.0
    assert claim["ratio_vs_mp_rw"]["rw-lsh"] is None  # > sweep max


# ---------------------------------------------------------------------------
# Cross-layer consistency oracle
# ---------------------------------------------------------------------------

def test_segmented_oracle_exact_match_after_compaction(run):
    cfg = run.scheme_config("mp-rw-lsh", 2, 30)
    out = run.check_segmented(cfg)
    assert out["segments_while_fragmented"] > 1  # mutation really fragmented
    assert out["segmented_matches_flat"]
    assert out["compacted_matches_fresh"]
    assert out["compacted_recall"] == out["fresh_recall"]
    assert out["mutated_no_regression"]


def test_skew_cap_oracle():
    """ISSUE 6 acceptance (eval side): on duplicated-point data the
    escalate overflow rung stays bit-identical to the flat query while
    the truncate rung costs < 0.5% recall."""
    data = ds.make_skewed_dataset(SPEC, zipf_s=0.5, dup_frac=0.25,
                                  num_hot=2)
    queries = ds.make_queries(SPEC, data, 16)
    srun = QualityRun(data, queries, SPEC.universe, QSPEC)
    cfg = srun.scheme_config("mp-rw-lsh", 2, 30)
    out = srun.check_skew_cap(cfg)
    assert out["skew_escalate_matches_flat"]
    assert out["skew_recall_within_half_pct"]
    assert out["skew_c_norm"] <= out["skew_c_full"]
    assert out["skew_ctot_norm"] <= out["skew_ctot_cap"]


def test_distributed_oracle_bit_identical(run):
    cfg = run.scheme_config("mp-rw-lsh", 2, 30)
    out = run.check_distributed(cfg)
    assert out["dist_matches_flat"]


def test_cluster_oracle_bit_identical_incl_recovery(run, tmp_path):
    """ISSUE 4 acceptance: ClusterRouter (S=2, R=2) == flat query_index
    bit-for-bit, including after a replica kill + WAL-replay recovery."""
    cfg = run.scheme_config("mp-rw-lsh", 2, 30)
    out = run.check_cluster(cfg, root_dir=str(tmp_path))
    assert out["cluster_matches_flat"]
    assert out["cluster_recovery_matches_flat"]
    assert out["cluster_recoveries"] == 1
    # the oracle's cap raise really is non-truncating (>= the sweep cap)
    assert out["cluster_oracle_cap"] >= cfg.candidate_cap


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

def test_predicted_recall_monotone_in_tables_and_probes(run):
    cfg = run.scheme_config("mp-rw-lsh", 1, 20)
    d_values = (16.0, 32.0, 64.0)
    by_l = [predicted_recall(dataclasses.replace(cfg, num_tables=l),
                             d_values, mc_runs=16) for l in (1, 2, 4, 8)]
    assert all(b >= a - 1e-12 for a, b in zip(by_l, by_l[1:]))
    by_t = [predicted_recall(dataclasses.replace(cfg, num_probes=t),
                             d_values, mc_runs=16) for t in (0, 10, 40)]
    assert all(b >= a - 1e-12 for a, b in zip(by_t, by_t[1:]))
    assert all(0.0 <= p <= 1.0 for p in by_l + by_t)


def test_autotune_meets_target_and_validates(run):
    base = run.scheme_config("mp-rw-lsh", 2, 30)
    res = tune_for_recall(base, np.asarray(run.data), 0.8, num_calib=16,
                          table_ladder=(1, 2, 4, 8), mc_runs=16)
    assert res.met_target
    assert res.validated_recall >= 0.8
    assert res.cfg.num_tables in (1, 2, 4, 8)
    assert res.rounds == len(res.history) >= 1
    # history records the escalation path faithfully
    assert res.history[-1]["validated"] == pytest.approx(
        res.validated_recall, abs=1e-4)


def test_autotune_empty_dataset_raises(run):
    base = run.scheme_config("mp-rw-lsh", 1, 10)
    with pytest.raises(ValueError, match="empty"):
        tune_for_recall(base, np.zeros((0, 16), np.int32), 0.5)


# ---------------------------------------------------------------------------
# ServeConfig.target_recall: quality as a first-class serving config input
# ---------------------------------------------------------------------------

def test_engine_target_recall_autotunes_and_reports(run):
    cfg = run.scheme_config("mp-rw-lsh", 1, 30)  # deliberately too weak
    eng = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, delta_cap=64, target_recall=0.8,
                         autotune_calib=16),
        run.data)
    assert eng.autotune is not None
    q = eng.summary()["quality"]
    assert q["target_recall"] == 0.8
    assert q["met_target"]
    assert q["num_tables"] == eng.cfg.num_tables
    # startup reuses the tuner's validated index instead of rebuilding
    assert eng.autotune.state is not None
    assert eng.index.segments[0].state is eng.autotune.state
    # the engine serves with the tuned config end to end, identically to a
    # from-scratch segmented build of the tuned config
    eng.submit(np.asarray(run.queries)[:4])
    d, i = eng.drain()
    assert d.shape == (4, cfg.k) and d.dtype == np.int32
    from repro.core.segments import SegmentedIndex
    ref = SegmentedIndex.from_dataset(eng.cfg, jax.random.PRNGKey(0),
                                      run.data)
    rd, ri, _ = ref.query_compact(run.queries[:4])
    np.testing.assert_array_equal(d, np.asarray(rd))
    np.testing.assert_array_equal(i, np.asarray(ri))


def test_engine_target_recall_empty_dataset_serves_best_effort(run):
    """Cold start with a quality target but no data must not crash: there
    is nothing to calibrate against, so the engine serves as configured."""
    cfg = run.scheme_config("mp-rw-lsh", 1, 10)
    eng = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, target_recall=0.9),
        jnp.zeros((0, 16), jnp.int32))
    assert eng.autotune is None
    assert eng.summary()["quality"] is None
    eng.submit(np.zeros((2, 16), np.int32))
    d, i = eng.drain()
    assert (i == -1).all() and d.dtype == np.int32
