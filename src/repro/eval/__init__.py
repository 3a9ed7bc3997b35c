"""Quality-evaluation subsystem (DESIGN.md §6).

The paper's headline claim is scientific, not just fast: MP-RW-LSH needs
15-53x fewer hash tables than CP-LSH at equal recall (Sect. 5).  This
package measures that axis:

  * ``quality``  — the :class:`QualityRun` harness: every scheme over one
    shared exact ground truth, ``num_tables`` x ``num_probes`` sweeps,
    recall@k / overall-ratio curves, the derived "tables needed to hit
    recall R" statistic, and the cross-layer consistency oracle
    (``query_index`` vs ``SegmentedIndex.query_compact`` vs
    ``dist_query_fn``).
  * ``autotune`` — the recall-target autotuner: the analytical success
    model of ``core.multiprobe`` inverted into a (L, T, candidate_cap)
    proposal, validated on a calibration split.  ``ServeConfig.target_recall``
    feeds it, making quality a first-class serving config input.
"""
from .autotune import AutotuneResult, predicted_recall, tune_for_recall
from .quality import SCHEMES, QualityRun, QualitySpec, tables_needed

__all__ = [
    "AutotuneResult",
    "predicted_recall",
    "tune_for_recall",
    "SCHEMES",
    "QualityRun",
    "QualitySpec",
    "tables_needed",
]
