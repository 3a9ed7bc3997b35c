"""repro.obs (DESIGN.md §12): metrics registry, tracing, flight recorder.

The load-bearing claims pinned here:
  * histogram quantiles are EXACT BOUNDS: the true quantile of everything
    recorded provably lies in ``quantile_bounds(q)`` and the bucket is
    ≤12.5% wide, at O(1) memory regardless of sample count;
  * snapshot merge is commutative + associative with the empty snapshot
    as identity — including after a JSON round trip (the wire stringifies
    int bucket keys), so the router's cluster roll-up cannot depend on
    replica order or transport;
  * the registry's dict-style facade keeps legacy ``stats[...]`` sites
    working verbatim;
  * with ``REPRO_TRACE`` unset, ``span()`` returns the shared null
    singleton (no allocation) and emits nothing; with it set, spans nest
    on one thread, cross threads/processes via explicit parent handoff,
    and export as schema-valid Chrome trace JSON;
  * the flight recorder stays bounded and captures slow exemplars;
  * ``router.summary()`` survives a dead-but-unmarked replica and an
    empty shard, and its cluster roll-up is order-independent;
  * the engine's latency percentiles come from the histogram (no
    unbounded per-batch sample list anywhere).
"""
import json
import os
import tempfile
import threading

import numpy as np
import pytest

from repro.obs import (FlightRecorder, Histogram, MetricsRegistry,
                       merge_snapshots, summarize_snapshot)
from repro.obs import trace as obs_trace
from repro.obs.metrics import _NBUCKETS, _bucket_bounds_us, _bucket_of
from repro.obs.render import check_spans, load_spans, to_chrome


# ------------------------------------------------------------- histogram


def test_bucket_of_roundtrip_and_width():
    for us in [0, 1, 7, 8, 9, 100, 1023, 1024, 5000, 10**6, 10**9]:
        b = _bucket_of(us)
        lo, hi = _bucket_bounds_us(b)
        assert lo <= us < hi, (us, b, lo, hi)
        if lo >= 8:
            # log-linear guarantee: bucket width <= 12.5% of its lower edge
            assert (hi - lo) <= lo / 8


def test_histogram_quantile_bounds_contain_truth():
    rng = np.random.default_rng(0)
    samples = np.concatenate([rng.uniform(0.5, 5.0, 900),
                              rng.uniform(50.0, 80.0, 100)])
    h = Histogram()
    for s in samples:
        h.record_ms(float(s))
    for q in (0.5, 0.9, 0.99, 0.999):
        true_q = float(np.quantile(samples, q, method="inverted_cdf"))
        lo, hi = h.quantile_bounds(q)
        assert lo <= true_q * 1.001 and true_q <= hi + 1e-3, \
            (q, true_q, lo, hi)
        assert h.quantile_ms(q) == hi
    assert h.count == 1000
    assert abs(h.mean_ms - samples.mean()) < 1e-6


def test_histogram_memory_is_bounded():
    h = Histogram()
    rng = np.random.default_rng(1)
    for ms in rng.uniform(0.001, 10_000.0, 20_000):
        h.record_ms(float(ms))
    assert len(h.snapshot()["buckets"]) <= _NBUCKETS
    # huge values saturate the top bucket instead of growing the table
    h.record_ms(1e15)
    assert max(h.snapshot()["buckets"]) <= _NBUCKETS - 1


# ------------------------------------------------------------- registry


def test_registry_dict_facade():
    reg = MetricsRegistry("t")
    reg["batches"] = 0
    reg["batches"] += 1
    reg["batches"] += 2
    assert reg["batches"] == 3
    assert reg["never_set"] == 0        # unknown counter reads as 0
    assert reg.get("batches") == 3
    assert reg.get("nope", None) is None
    assert "batches" in reg and "nope" not in reg
    fam = reg.family("cand_buckets")
    fam[128] += 2
    assert reg["cand_buckets"][128] == 2
    reg.gauge_set("queue_depth", 7)
    assert reg.gauge("queue_depth") == 7
    d = reg.as_dict()
    assert d["batches"] == 3 and d["cand_buckets"] == {128: 2}


def _snap(counters=(), fam=(), hist=()):
    reg = MetricsRegistry()
    for k, v in counters:
        reg[k] = v
    for label, n in fam:
        reg.family("f")[label] += n
    h = reg.histogram("lat")
    for ms in hist:
        h.record_ms(ms)
    return reg.snapshot()


def test_merge_commutative_associative_identity():
    a = _snap([("x", 1), ("y", 2)], [(8, 1)], [1.0, 2.0])
    b = _snap([("x", 10)], [(8, 2), (16, 1)], [100.0])
    c = _snap([("z", 5)], [], [0.5, 0.5, 7.0])
    assert merge_snapshots(a, b) == merge_snapshots(b, a)
    assert (merge_snapshots(merge_snapshots(a, b), c)
            == merge_snapshots(a, merge_snapshots(b, c)))
    # empty/None is the identity
    assert merge_snapshots(a, None)["counters"] == a["counters"]
    assert merge_snapshots(None, a)["histograms"] == \
        merge_snapshots(a, {})["histograms"]


def test_merge_survives_json_roundtrip():
    # the RPC meta stringifies int keys; merging a wire copy with a local
    # snapshot must agree with merging two local snapshots
    a = _snap([("x", 1)], [(8, 3)], [1.0, 64.0])
    b = _snap([("x", 2)], [(16, 1)], [2.0])
    wire_b = json.loads(json.dumps(b))
    assert merge_snapshots(a, wire_b) == merge_snapshots(a, b)
    merged = merge_snapshots(json.loads(json.dumps(a)), wire_b)
    summ = summarize_snapshot(merged)
    assert summ["histograms"]["lat"]["count"] == 3
    assert summ["families"]["f"] == {8: 3, 16: 1}


def test_summarize_snapshot_quantiles():
    s = _snap(hist=[1.0] * 99 + [500.0])
    out = summarize_snapshot(s)["histograms"]["lat"]
    assert out["count"] == 100
    assert out["p50_ms"] < 2.0
    assert out["p99_ms"] < 2.0          # rank 99 of 100 is still a 1ms sample
    assert out["p999_ms"] >= 500.0
    assert summarize_snapshot(None) is None


# ------------------------------------------------------------- tracing


def test_span_is_shared_null_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    s1 = obs_trace.span("a", x=1)
    s2 = obs_trace.span("b")
    assert s1 is s2                     # one shared singleton, no allocation
    with s1:
        assert obs_trace.current() is None
        assert obs_trace.wire_context() is None
    obs_trace.record_span("q", dur_ms=5.0)
    obs_trace.event("e")
    assert obs_trace.capture_end() == []


def test_spans_nest_flush_and_render(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
    obs_trace.set_process_label("test-root")
    with obs_trace.span("root", kind="batch") as root:
        ctx = obs_trace.current()
        assert ctx == (root.trace_id, root.span_id)
        with obs_trace.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        obs_trace.record_span("queue_wait", dur_ms=3.0)
        obs_trace.event("mark", n=1)

        # cross-thread: context does NOT follow; explicit parent= does
        seen = {}

        def worker():
            assert obs_trace.current() is None
            with obs_trace.span("pool_child", parent=ctx) as sp:
                seen["tid"], seen["psid"] = sp.trace_id, sp.parent_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert seen == {"tid": root.trace_id, "psid": root.span_id}
    obs_trace.flush()
    spans = load_spans(str(tmp_path))
    assert {r["name"] for r in spans} >= {"root", "child", "queue_wait",
                                          "mark", "pool_child"}
    assert len({r["tid"] for r in spans}) == 1
    report = check_spans(spans)
    assert report["ok"], report
    chrome = to_chrome(spans)
    names = {e["name"] for e in chrome["traceEvents"]}
    assert "process_name" in names and "root" in names
    json.dumps(chrome)                  # chrome export must be JSON-able


def test_wire_context_and_capture(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
    obs_trace.capture_begin()
    with obs_trace.span("engine_batch"):
        wc = obs_trace.wire_context()
        assert set(wc) == {"tid", "sid"}
        assert isinstance(wc["tid"], str) and isinstance(wc["sid"], int)
    captured = obs_trace.capture_end()
    assert [r["name"] for r in captured] == ["engine_batch"]
    json.dumps({"trace": wc})           # meta-safe: scalars only


def _profiled_events(log_dir, body):
    """Run ``body`` under a JAX profiler session; return the host events
    named ``repro.*`` in its trace as ``{name: {stat: value}}``."""
    import glob
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs_trace.PROFILER_PREFIX):
                    events[ev.name] = dict(ev.stats)
    return events


def test_span_writes_a_profiler_event_while_a_session_records(
        monkeypatch, tmp_path):
    """REPRO_TRACE off: only the profiler's trace gets the span, with its
    scalar attributes, those given and those set later, as stats."""
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path / "jsonl"))

    def body():
        with obs_trace.span("rung_pick", segment=4096, c_cap=None,
                            policy="escalate", extra=[1]) as sp:
            assert sp is not obs_trace.span("other")
            sp.set(rung=8192, max_count=5000)

    events = _profiled_events(tmp_path / "prof", body)
    assert events == {"repro.rung_pick": {
        "segment": 4096, "policy": "escalate", "rung": 8192,
        "max_count": 5000}}
    assert not (tmp_path / "jsonl").exists()        # no JSONL sink


def test_span_writes_both_sinks_when_traced_and_profiled(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path / "jsonl"))

    def body():
        with obs_trace.span("engine_batch", bucket=64):
            with obs_trace.span("rung_pick") as sp:
                sp.set(rung=8192)

    events = _profiled_events(tmp_path / "prof", body)
    assert events == {"repro.engine_batch": {"bucket": 64},
                      "repro.rung_pick": {"rung": 8192}}
    obs_trace.flush()
    spans = load_spans(str(tmp_path / "jsonl"))
    assert {r["name"]: r["args"] for r in spans} == {
        "engine_batch": {"bucket": 64}, "rung_pick": {"rung": 8192}}
    assert check_spans(spans)["ok"]


def test_span_writes_no_profiler_event_without_a_session(monkeypatch,
                                                         tmp_path):
    """With jax loaded but no session recording, span() is the shared
    no-op, and a session started afterwards holds none of its spans."""
    import jax  # noqa: F401  (the profiler check looks for it)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    sp = obs_trace.span("phase_a", segment=1)
    assert sp is obs_trace.span("phase_b_rerank")
    with sp:
        sp.set(rung=1)
    assert _profiled_events(tmp_path / "prof", lambda: None) == {}


def test_check_spans_rejects_bad_records():
    assert not check_spans([])["ok"]
    bad = [{"ph": "X", "name": "a"}]
    assert not check_spans(bad)["ok"]
    one_proc = [{"ph": "X", "name": "a", "tid": "t1", "sid": 1, "psid": None,
                 "ts": 0, "dur": 5, "proc": "p0", "thread": 1, "args": {}}]
    assert check_spans(one_proc)["ok"]
    assert not check_spans(one_proc, require_cross_process=True)["ok"]
    assert not check_spans(one_proc, require_hedge=True)["ok"]
    two_proc = one_proc + [
        {"ph": "X", "name": "b", "tid": "t1", "sid": 2, "psid": 1,
         "ts": 1, "dur": 3, "proc": "p1", "thread": 2, "args": {}}]
    rep = check_spans(two_proc, require_cross_process=True)
    assert rep["ok"] and rep["cross_process_pairs"] == 1


# ------------------------------------------------------- flight recorder


def test_flight_recorder_bounds_and_exemplars():
    fr = FlightRecorder(capacity=4, slow_ms=10.0, exemplar_capacity=2)
    for n in range(8):
        fr.record(1.0, {"n": n})
    assert len(fr.entries()) == 4       # ring stays bounded
    assert [e[2]["n"] for e in fr.entries()] == [4, 5, 6, 7]
    assert fr.exemplars() == []
    ex = fr.record(25.0, {"n": 8}, spans=[{"name": "s"}])
    assert ex["ms"] == 25.0 and ex["spans"] == [{"name": "s"}]
    fr.record(30.0, {"n": 9})
    fr.record(40.0, {"n": 10})
    assert len(fr.exemplars()) == 2     # exemplar ring bounded too
    assert [e["n"] for e in fr.exemplars()] == [9, 10]
    s = fr.summary()
    assert s["recorded"] == 11 and s["slow_batches"] == 3
    assert s["exemplar_count"] == 2


# ------------------------------------------------- engine / router wiring

jax = pytest.importorskip("jax")

from repro.cluster import ClusterConfig, ClusterRouter       # noqa: E402
from repro.cluster.replica import ReplicaKilled              # noqa: E402
from repro.core.index import IndexConfig                     # noqa: E402
from repro.data import ann_synthetic as ds                   # noqa: E402
from repro.serve.engine import AnnServingEngine, ServeConfig  # noqa: E402

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(num_tables=2, num_hashes=6, width=16, num_probes=10,
                       candidate_cap=16, universe=32, k=4, rerank_chunk=64)


@pytest.fixture(scope="module")
def small():
    spec = ds.DatasetSpec("obs-t", n=600, dim=8, universe=32, num_clusters=4)
    data = np.asarray(ds.make_dataset(spec))
    queries = np.asarray(ds.make_queries(spec, data, 12))
    return data, queries


def make_router(cfg, data, root, shards=2, replicas=2, **ckw):
    ckw.setdefault("hedge_ms", 30000)
    ckw.setdefault("wal_fsync", False)
    return ClusterRouter(
        cfg, ServeConfig(batch_size=8, bucket_min=4, delta_cap=32),
        ClusterConfig(num_shards=shards, num_replicas=replicas, **ckw),
        data, str(root), key=KEY)


def test_compile_cache_writes_are_atomic(tmp_path, monkeypatch):
    # a worker SIGKILL'd mid-cache-write (the §10 chaos drills) must not
    # leave a torn entry for another process to segfault on: entries land
    # via temp-file + os.replace, so readers see whole files or a miss
    import os

    from jax._src import lru_cache as _lru

    from repro.serve import engine as engine_mod

    engine_mod._install_atomic_cache_writes()
    assert getattr(_lru.LRUCache.put, "_repro_atomic", False)

    cache = _lru.LRUCache(str(tmp_path), max_size=-1)
    replaced = []
    real_replace = os.replace

    def recording_replace(src, dst):
        replaced.append(str(dst))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", recording_replace)
    cache.put("k1", b"x" * 1024)
    assert cache.get("k1") == b"x" * 1024
    assert replaced and replaced[0].endswith("k1" + _lru._CACHE_SUFFIX)
    cache.put("k1", b"y" * 1024)     # existing entries are never rewritten
    assert cache.get("k1") == b"x" * 1024
    assert not list(tmp_path.glob("*.tmp"))


def test_compile_cache_dir_is_placed_from_outside(tmp_path, monkeypatch):
    # $JAX_COMPILATION_CACHE_DIR set (conftest sets one outside the
    # checkout): the engine leaves jax's directory there.  Unset: the one
    # fixed path inside the checkout, <repo>/.jax_cache.
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.serve import engine as engine_mod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert engine_mod.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    outside = os.environ["JAX_COMPILATION_CACHE_DIR"]
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setitem(engine_mod._CACHE_STATS, "enabled", False)
    monkeypatch.setitem(engine_mod._CACHE_STATS, "dir", None)
    try:
        assert engine_mod.enable_compilation_cache()["dir"] == outside
        assert jax.config.jax_compilation_cache_dir == outside
        inside = str(tmp_path / ".jax_cache")
        monkeypatch.setattr(engine_mod, "REPO_CACHE_DIR", inside)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        engine_mod._CACHE_STATS["enabled"] = False
        assert engine_mod.enable_compilation_cache()["dir"] == inside
        assert jax.config.jax_compilation_cache_dir == inside
        assert os.path.isdir(inside)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_engine_summary_is_histogram_backed(cfg, small):
    data, queries = small
    eng = AnnServingEngine(
        cfg, ServeConfig(batch_size=8, bucket_min=4, delta_cap=32), data,
        key=KEY)
    eng.query_batch(queries)
    s = eng.summary()
    assert s["p50_batch_ms"] > 0 and s["p999_batch_ms"] >= s["p99_batch_ms"]
    assert s["flight"]["recorded"] == s["batches"]
    snap = eng.metrics.snapshot()
    assert snap["histograms"]["batch_ms"]["count"] == s["batches"]
    # the old unbounded per-batch list is gone: memory is the bucket table
    assert "batch_ms" not in vars(eng)
    assert not any(isinstance(v, list) and len(v) == s["batches"]
                   for v in vars(eng).values())


def test_router_summary_dead_unmarked_replica(cfg, small, tmp_path,
                                              monkeypatch):
    """A replica that died without being marked (alive=True but telemetry
    raises) must degrade that replica's row, not break summary()."""
    data, _ = small
    router = make_router(cfg, data, tmp_path)
    try:
        victim = router.replicas[1][0]

        def boom():
            raise ReplicaKilled("worker vanished")

        monkeypatch.setattr(victim, "telemetry", boom)
        assert victim.alive
        s = router.summary()
        rows = {(sh["shard"], r["replica"]): r
                for sh in s["shards"] for r in sh["replicas"]}
        assert rows[(1, 0)]["num_live"] is None        # degraded, present
        assert rows[(0, 0)]["num_live"] is not None
        # the roll-up still merged the 3 reachable engines
        assert s["cluster_metrics"] is not None
    finally:
        router.close()


def test_router_summary_empty_shard_merge(cfg, tmp_path):
    """1 row across 2 shards: shard 1 is EMPTY; query + summary + roll-up
    must all survive a shard with nothing in it."""
    rng = np.random.default_rng(3)
    data = rng.integers(0, 32, (1, 8)).astype(np.int32)
    router = make_router(cfg, data, tmp_path, shards=2, replicas=1)
    try:
        d, i = router.query(data)
        assert i[0, 0] == 0                            # the one real row
        assert (i[0, 1:] == -1).all()                  # empty-shard padding
        s = router.summary()
        assert s["cluster_metrics"]["histograms"]["batch_ms"]["count"] >= 2
    finally:
        router.close()


def test_router_cluster_rollup_is_order_independent(cfg, small, tmp_path):
    data, queries = small
    router = make_router(cfg, data, tmp_path)
    try:
        router.query(queries)
        snaps = [rep.telemetry()["metrics"]
                 for group in router.replicas for rep in group]
        fwd = snaps[0]
        for s in snaps[1:]:
            fwd = merge_snapshots(fwd, s)
        rev = snaps[-1]
        for s in reversed(snaps[:-1]):
            rev = merge_snapshots(rev, s)
        assert fwd == rev
        summ = router.summary()
        assert (summ["cluster_metrics"]["counters"]["batches"]
                == fwd["counters"]["batches"])
        # dispatch latency landed in the router's own histogram
        assert summ["dispatch_ms"]["count"] == summ["batches"]
        assert summ["flight"]["recorded"] == summ["batches"]
    finally:
        router.close()


def test_router_traced_query_exports_hedge_pair(cfg, small, tmp_path,
                                                monkeypatch):
    """In-proc end-to-end: traced hedged query -> valid span files with the
    primary/reissue pair and a hedge_win mark on one trace."""
    data, queries = small
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path / "tr"))
    router = make_router(cfg, data, tmp_path / "root", hedge_ms=150)
    try:
        router.query(queries)                          # warm + compile
        for rep in router.replicas[0]:                 # slow ALL shard-0
            rep.slow_ms = 500.0                        # replicas: rotation
        router.clear_cache()                           # can't dodge it
        router.query(queries[:8])
        assert router.stats["hedged_batches"] >= 1
    finally:
        for rep in router.replicas[0]:
            rep.slow_ms = 0.0
        router.close()
    obs_trace.flush()
    spans = load_spans(str(tmp_path / "tr"))
    report = check_spans(spans, require_hedge=True)
    assert report["ok"], report
    names = {r["name"] for r in spans}
    assert {"cluster_batch", "fanout", "shard_query", "replica_query",
            "engine_batch", "merge"} <= names
