"""The trace reduction on committed traces, so that every change computes
device time, busy and idle time and the idle gaps' host spans alike."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402

NS = 1e-9


def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(FIX, name)) as f:
        return ProfileData.from_text_proto(f.read())


def _readers():
    spec = harness.load_spec()
    cell = harness.resolve(spec, spec["workloads"][0]["name"])
    return cell["readers"]


def test_hand_made_trace_reduces_to_its_hand_count():
    s = trace_reduce.reduce_profile(_profile("trace_synthetic.pbtxt"))
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(10000 * NS)
    # ops [500,1500] (two overlapping), [1600,3900], [4400,5400],
    # [5500,9400] and [9900,10000] of one that runs past the window
    assert s["busy_s"] == pytest.approx(8300 * NS)
    assert s["programs"] == {
        "jit_probe_index": {"seconds": pytest.approx(2000 * NS), "count": 2},
        "jit__finish_segment": {"seconds": pytest.approx(6200 * NS),
                                "count": 2}}
    assert s["ops"] == pytest.approx({
        "jit_probe_index/fusion.1": 1500 * NS,
        "jit_probe_index/fusion.2": 600 * NS,
        "jit__finish_segment/sort.3": 6200 * NS,
        "fusion.9": 100 * NS})
    # gaps [0,500], [1500,1600], [5400,5500] lie in bench.query_batch;
    # [3900,4400] and [9400,9900] in bench.handle_result
    assert s["gaps"] == pytest.approx({"bench.query_batch": 700 * NS,
                                       "bench.handle_result": 1000 * NS})
    b = trace_reduce.breakdown(s)
    assert b["device_ops"][0] == ["jit__finish_segment/sort.3",
                                  pytest.approx(6200 * NS)]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.handle_result",
                                              "bench.query_batch"]


def test_metric_readers_on_the_hand_made_trace():
    s = trace_reduce.reduce_profile(_profile("trace_synthetic.pbtxt"))
    read = _readers()
    run = {"batches": 2, "attempted": 128, "dim": 128, "itemsize": 4,
           "candidates": 1000, "build_s": 3.0, "warmup_s": 4.0,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read["phase_a_ms"](run, s) == pytest.approx(1000 * NS * 1e3)
    assert read["phase_b_ms"](run, s) == pytest.approx(3100 * NS * 1e3)
    assert read["device_idle_share"](run, s) == pytest.approx(17.0)
    share = read["phase_b_roofline"](run, s)
    assert share == pytest.approx(
        100 * (1000 * 516 + 128 * 512) / 819e9 / (6200 * NS))
    assert read["build_s"](run, s) == 3.0
    assert read["warmup_s"](run, s) == 4.0


@pytest.mark.parametrize("metric", ["phase_a_ms", "phase_b_ms",
                                    "phase_b_roofline", "device_idle_share"])
def test_device_readers_find_nothing_without_a_trace(metric):
    run = {"batches": 2, "attempted": 128, "dim": 128, "itemsize": 4,
           "candidates": 10, "peaks": {"hbm_bytes_per_s": 819e9}}
    read = _readers()[metric]
    assert read(run, None) is None
    empty = {"window_s": 1.0, "busy_s": 0.0, "devices": 0, "programs": {},
             "ops": {}, "gaps": {}}
    assert read(run, empty) is None


def test_trace_without_a_window_span_reduces_to_nothing():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(
        'planes { id: 1 name: "/device:TPU:0" }')
    assert trace_reduce.reduce_profile(profile) is None


def test_recorded_chip_trace_reduces_to_its_numbers():
    """One batch of sift1m.batch64 cut from a TPU v5e trace."""
    s = trace_reduce.reduce_profile(_profile("trace_sift1m_batch.pbtxt"))
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(1.712335296)
    assert s["busy_s"] == pytest.approx(1.692634993)
    progs = s["programs"]
    assert progs["jit__finish_segment"]["count"] == 1
    assert progs["jit__finish_segment"]["seconds"] == pytest.approx(
        1.619719265)
    assert progs["jit_probe_index"]["seconds"] == pytest.approx(0.072917531)
    assert set(progs) >= {"jit__reduce_max", "jit_dynamic_slice"}
    assert sum(s["gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert max(s["gaps"], key=s["gaps"].get) == "bench.query_batch"
    top = trace_reduce.breakdown(s)["device_ops"]
    assert len(top) == 10
    assert top[0][0] == "jit__finish_segment/while.12 (tuple)"
    assert all(name.startswith("jit__finish_segment/") for name, _ in top)
