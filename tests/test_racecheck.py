"""Runtime race sanitizer (repro.analysis.racecheck, DESIGN.md §11).

The centerpiece is the seeded-violation regression: a cluster router
whose straggler quiesce is disabled MUST trip ``RaceViolation`` when a
mutation lands while a hedged straggler's query is still in flight — and
the stock router (quiesce intact) must run the same sequence clean.
That is the §7 contract checked dynamically instead of by source shape.
"""
import threading

import jax
import numpy as np
import pytest

from repro.analysis import racecheck
from repro.analysis.racecheck import RaceViolation, StateToken
from repro.cluster import ClusterConfig, ClusterRouter
from repro.cluster.transport import error_meta, raise_remote_error
from repro.core.index import IndexConfig
from repro.data import ann_synthetic as ds
from repro.serve.engine import ServeConfig

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- token unit


def test_token_same_thread_nesting_is_legal():
    tok = StateToken("t")
    e = tok.enter_query()
    tok.enter_mutation()        # drain() -> compact() style reentrancy
    tok.exit_mutation()
    tok.exit_query(e)           # epoch advanced, but by this thread


def test_token_cross_thread_mutation_during_query_raises():
    tok = StateToken("t")
    in_query = threading.Event()
    release = threading.Event()

    def long_query():
        e = tok.enter_query()
        in_query.set()
        release.wait(5)
        tok.exit_query(e)

    t = threading.Thread(target=long_query)
    t.start()
    try:
        assert in_query.wait(5)
        with pytest.raises(RaceViolation):
            tok.enter_mutation()
    finally:
        release.set()
        t.join()


def test_token_query_detects_epoch_advanced_by_unwrapped_mutator():
    # defense in depth: if a mutation dodged enter_mutation entirely
    # (uninstrumented path, monkeypatched method), the query still
    # notices the epoch moved under it at exit
    tok = StateToken("t")
    e = tok.enter_query()
    tok.epoch += 1
    tok.last_mutator = -2       # some other thread
    with pytest.raises(RaceViolation):
        tok.exit_query(e)


def test_token_concurrent_cross_thread_mutations_raise():
    tok = StateToken("t")
    in_mut = threading.Event()
    release = threading.Event()

    def long_mutation():
        tok.enter_mutation()
        in_mut.set()
        release.wait(5)
        tok.exit_mutation()

    t = threading.Thread(target=long_mutation)
    t.start()
    try:
        assert in_mut.wait(5)
        with pytest.raises(RaceViolation):
            tok.enter_mutation()
        with pytest.raises(RaceViolation):
            tok.enter_query()
    finally:
        release.set()
        t.join()


# --------------------------------------------------- instrumentation seam


def test_instrument_is_noop_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)

    class Obj:
        def q(self):
            return 1

    o = Obj()
    racecheck.maybe_instrument(o, "x", queries=("q",))
    assert not hasattr(o, "__repro_race_token__")
    assert o.q() == 1


def test_instrument_wraps_and_is_idempotent(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")

    class Obj:
        def q(self):
            return 41

        def m(self):
            return 42

    o = Obj()
    racecheck.maybe_instrument(o, "x", queries=("q",), mutations=("m",))
    assert o.q.__repro_sanitized__ == "query"
    assert o.m.__repro_sanitized__ == "mutation"
    first = o.q
    racecheck.maybe_instrument(o, "x", queries=("q",))  # no double wrap
    assert o.q is first
    assert (o.q(), o.m()) == (41, 42)
    assert o.__repro_race_token__.epoch == 1            # one mutation ran


def test_raceviolation_crosses_the_wire_unmapped_to_remote_error():
    meta = error_meta(RaceViolation("boom"))
    assert meta["etype"] == "RaceViolation"
    with pytest.raises(RaceViolation, match="boom"):
        raise_remote_error(meta)


# ------------------------------------------------- seeded cluster race


@pytest.fixture(scope="module")
def race_setup():
    cfg = IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=20,
                      candidate_cap=256, universe=64, k=8, rerank_chunk=128)
    spec = ds.DatasetSpec("race-t", n=600, dim=16, universe=64,
                          num_clusters=8)
    data = np.asarray(ds.make_dataset(spec))
    queries = np.asarray(ds.make_queries(spec, data, 16))
    return cfg, data, queries


def test_seeded_race_caught_without_quiesce_clean_with_it(
        race_setup, tmp_path, monkeypatch):
    """The regression pin the ISSUE asks for, both directions:

    1. stock router, straggler in flight, mutation -> quiesce waits, no
       violation, mutation lands;
    2. same sequence with ``_quiesce`` disabled -> ``RaceViolation`` from
       the straggler replica's token, BEFORE any WAL append.

    The straggler is the victim replica's engine call held on an event
    the test sets, so it is in flight for as long as the sequence needs,
    whatever the machine's speed: the router's hedge deadline only decides
    when the peer is asked, not whether the victim is still busy.
    """
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    cfg, data, queries = race_setup
    router = ClusterRouter(
        cfg, ServeConfig(batch_size=16, delta_cap=128),
        ClusterConfig(num_shards=2, num_replicas=2, hedge_ms=150,
                      wal_fsync=False, cache_capacity=0),
        data, str(tmp_path), key=KEY)
    victim = router.replicas[0][0]
    assert hasattr(victim, "__repro_race_token__")      # ctor instrumented
    pts = data[:4].astype(np.int32)
    release = threading.Event()
    serve = victim.engine.run_padded

    def straggle(*args, **kw):
        release.wait()
        return serve(*args, **kw)

    victim.engine.run_padded = straggle
    quiesce = ClusterRouter._quiesce

    def release_then_quiesce(self):
        release.set()                       # the straggler is in flight
        quiesce(self)                       # until here; quiesce drains it

    try:
        # phase 1: quiesce intact — hedged straggler, then a mutation
        router._rr[0] = 0                   # pin the victim as primary
        router.query(queries)               # peer wins; straggler in flight
        assert not release.is_set()
        with monkeypatch.context() as m:
            m.setattr(ClusterRouter, "_quiesce", release_then_quiesce)
            router.insert(pts)              # _quiesce drains it first
        s = router.summary()
        assert s["hedged_batches"] >= 1 and s["hedge_wins"] >= 1, s

        # phase 2: identical sequence, quiesce disabled.  Pin the rotation
        # again: the hedged re-issue must land on the held victim so its
        # query is still in flight when the mutation arrives.
        release.clear()
        router._rr[0] = 0
        router.clear_cache()                # force real dispatches
        router.query(queries)
        tok = victim.__repro_race_token__
        assert any(d > 0 for d in tok._queries.values()), \
            "straggler query not in flight — seeded race did not arm"
        wal_before = victim.last_seq
        with monkeypatch.context() as m:
            m.setattr(ClusterRouter, "_quiesce", lambda self: None)
            with pytest.raises(RaceViolation):
                router.insert(pts + 2)
        # the violation fired at mutation ENTRY: nothing reached the WAL
        assert victim.last_seq == wal_before
    finally:
        release.set()                       # let the straggler drain
        router.close()                      # (close quiesces first)


def test_same_thread_engine_reentrancy_is_clean_under_sanitizer(
        race_setup, tmp_path, monkeypatch):
    """insert -> watermark compaction is same-thread nesting and must not
    trip the sanitizer (the tokens are owner-aware, not plain locks)."""
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    cfg, data, queries = race_setup
    from repro.serve.engine import AnnServingEngine
    eng = AnnServingEngine(cfg, ServeConfig(batch_size=16, delta_cap=64,
                                            compact_watermark=0.01),
                           dataset=data[:200], key=KEY)
    assert hasattr(eng, "__repro_race_token__")
    eng.insert(data[200:220].astype(np.int32))   # trips the watermark
    d, i = eng.run_padded(queries, queries.shape[0])
    assert i.shape == (queries.shape[0], cfg.k)
    assert eng.__repro_race_token__.epoch >= 1
