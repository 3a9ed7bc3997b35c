"""The program's spans in a trace (``bench/program_trace.py``) and the
per-layer metrics that read them, on committed traces."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, program_trace, trace_reduce  # noqa: E402

NS = 1e-9
IDLE = ("rung_pick_idle_ms", "engine_idle_ms", "client_idle_ms")


def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(FIX, name)) as f:
        return ProfileData.from_text_proto(f.read())


def _readers():
    spec = harness.load_spec()
    cell = harness.resolve(spec, spec["workloads"][0]["name"])
    return cell["readers"]


def test_each_idle_gap_goes_to_the_innermost_program_span():
    """Two batches: the gaps lie under repro.engine_batch (before phase A's
    dispatch), repro.rung_pick (between phase A and the rung pick's read),
    repro.phase_b_rerank (phase B's dispatch), repro.engine.record, and
    bench.handle_result, where no program span is open."""
    s = program_trace.reduce_profile(_profile("trace_program_spans.pbtxt"))
    assert s["window_s"] == pytest.approx(10000 * NS)
    assert s["idle"] == pytest.approx({
        "repro.engine_batch": (1000 + 540) * NS,
        "repro.rung_pick": 2 * 50 * NS,
        "repro.phase_b_rerank": 2 * 120 * NS,
        "repro.engine.record": 500 * NS,
        "bench.handle_result": 940 * NS})
    whole = trace_reduce.reduce_profile(_profile("trace_program_spans.pbtxt"))
    assert sum(s["idle"].values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"])


def test_readers_split_the_window_idle(monkeypatch):
    found = program_trace.reduce_profile(
        _profile("trace_program_spans.pbtxt"))
    monkeypatch.setattr(program_trace, "newest", lambda: found)
    whole = trace_reduce.reduce_profile(_profile("trace_program_spans.pbtxt"))
    read = _readers()
    run = {"batches": 2}
    ms = {m: read[m](run, whole) for m in IDLE}
    assert ms == pytest.approx({
        "rung_pick_idle_ms": 50 * NS * 1e3,
        "engine_idle_ms": (1540 + 240 + 500) / 2 * NS * 1e3,
        "client_idle_ms": 940 / 2 * NS * 1e3})
    idle_s = (read["device_idle_share"](run, whole) / 100
              * whole["window_s"])
    assert sum(ms.values()) * run["batches"] / 1e3 == pytest.approx(idle_s)


@pytest.mark.parametrize("metric", IDLE)
def test_readers_find_nothing_in_a_trace_without_program_spans(
        monkeypatch, metric):
    """The committed chip trace predates the program's spans, as a parent
    commit's trace does: the readers report nothing."""
    found = program_trace.reduce_profile(
        _profile("trace_sift1m_batch.pbtxt"))
    assert found["idle"] is None
    monkeypatch.setattr(program_trace, "newest", lambda: found)
    whole = trace_reduce.reduce_profile(_profile("trace_sift1m_batch.pbtxt"))
    read = _readers()[metric]
    assert read({"batches": 1}, whole) is None
    assert read({"batches": 1}, None) is None
    monkeypatch.setattr(program_trace, "newest", lambda: None)
    assert read({"batches": 1}, whole) is None


def test_newest_reads_the_newest_trace_once(monkeypatch, tmp_path):
    """A CPU trace with a window and one program span: no device plane, so
    no idle gap, and the reading is kept for the next reader."""
    import jax
    from repro.obs import trace as obs_trace
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert program_trace.newest() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            with obs_trace.span("engine_batch"):
                pass
    finally:
        jax.profiler.stop_trace()
    first = program_trace.newest()
    assert first["window_s"] > 0 and first["idle"] == {}
    assert program_trace.newest() is first


def test_recorded_chip_batch_puts_its_gaps_under_program_spans():
    """One batch of sift1m.batch64 on the 262,144 rung, cut from a TPU v5e
    trace with the program's spans: the device waits for the rung pick's
    read between phase A and phase B, and, between phase B's end and the
    next batch's phase A, for the host to wake from the result wait; at
    the cut's start the host copies the previous batch's result."""
    s = program_trace.reduce_profile(_profile("trace_sift1m_spans.pbtxt"))
    assert s["window_s"] == pytest.approx(3.568101652)
    big = {k: v for k, v in s["idle"].items() if v > 1e-6}
    assert big == pytest.approx({
        "repro.engine.result_fetch": 0.002177977,
        "repro.rung_pick": 0.001428287,
        "repro.engine.result_wait": 0.003464323})
    whole = trace_reduce.reduce_profile(_profile("trace_sift1m_spans.pbtxt"))
    assert whole["programs"]["jit__finish_segment"]["count"] == 1
    assert sum(s["idle"].values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"])
