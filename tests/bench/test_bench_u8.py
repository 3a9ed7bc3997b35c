"""A whole run, on the CPU, of a fixture cell whose rows are stored as
uint8 bytes: the bigann10m-u8 path at a size the CPU runs in seconds.

The reference judges the int32 rows the run made, so ``correct`` says the
program's answers from the stored bytes are the exact L1 answers.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

CELL = "tiny.u8batch16"


def _spec():
    """The fixture benchmark with the uint8 configuration and its cell."""
    spec = harness.load_spec(os.path.join(FIX, "BENCHMARK.json"))
    spec["configs"].append(
        {"name": "tiny-u8", "source": "test fixture",
         "file": "configs/tiny-u8.json", "reduced": ["n"],
         "why": "the uint8 row store at a CPU size"})
    spec["workloads"].append(
        {"name": CELL, "config": "tiny-u8", "traffic": "tiny_closed",
         "chips": 1, "why": "closed loop of 16-query batches over 4096 "
                            "uint8 rows"})
    return spec


def test_uint8_fixture_cell_runs_correct():
    cell = harness.resolve(_spec(), CELL, root=FIX, dirs=[FIX])
    assert cell["config"]["index"]["dataset_dtype"] == "uint8"
    logged = []
    result = harness.run_cell(cell, 2 ** 40 + 15, 0.5, False,
                              time.perf_counter(), log=logged.append)
    assert result["correct"], (result["checks"], logged)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["wrong_answers"]["value"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "recall_at_10",
                                      "setup_s"}
    json.dumps(result)
