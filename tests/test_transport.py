"""RPC transport + worker-process contracts (DESIGN.md §10/§13).

Pinned here:
  * the length-prefixed frame codec round-trips metadata and numpy arrays
    (both the coalesced small-frame path and the vectored large-frame
    path) without pickle and with zero-copy receive views;
  * the SAME codec over a real TCP loopback socket: partial delivery at
    every byte split point, >64KB vectored frames, malformed-frame
    rejection, typed-error round-trip, and connect-time retry while the
    listener is not bound yet (refused == not-up-yet, not dead);
  * malformed frames (bad magic, implausible length, truncated stream,
    off-whitelist dtypes) surface as ``ConnectionError``/``TypeError``,
    never as garbage arrays;
  * worker-side exceptions cross the wire as typed errors and re-raise as
    the matching local class (``ReplicaKilled`` et al.);
  * a real worker subprocess serves bit-identical answers to an
    in-process ``ShardReplica`` over the same seed/key/config, survives
    SIGKILL via respawn + disk recovery, and the ``ClusterRouter`` keeps
    the §7 failover/consistency discipline over BOTH multi-process
    transports (AF_UNIX workers and loopback TCP workers);
  * the shm fast path (§13): a worker SIGKILL'd mid-query with a mapped
    slab outstanding leaks nothing — the recovery path reaps the orphan
    slab, ``/dev/shm`` returns to baseline, answers stay bit-identical.
"""
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (ClusterConfig, ClusterRouter, OP_DELETE,
                           OP_INSERT, RemoteReplica, ShardReplica,
                           WalRecord)
from repro.cluster import shm
from repro.cluster.replica import ReplicaDiverged, ReplicaKilled
from repro.cluster.transport import (Connection, KIND_REQUEST, KIND_RESPONSE,
                                     RemoteError, WIRE_DTYPES, connect_tcp,
                                     listen_tcp, recv_frame, send_frame)
from repro.cluster.worker import pack_records, unpack_records
from repro.core.index import IndexConfig, build_index, query_index
from repro.data import ann_synthetic as ds
from repro.serve.engine import AnnServingEngine, ServeConfig

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(num_tables=4, num_hashes=8, width=24, num_probes=20,
                       candidate_cap=256, universe=64, k=8, rerank_chunk=128)


@pytest.fixture(scope="module")
def small():
    spec = ds.DatasetSpec("transport-t", n=400, dim=16, universe=64,
                          num_clusters=8)
    data = np.asarray(ds.make_dataset(spec))
    queries = np.asarray(ds.make_queries(spec, data, 16))
    return data, queries


def serve_cfg(**kw):
    kw.setdefault("batch_size", 16)
    kw.setdefault("delta_cap", 128)
    return ServeConfig(**kw)


# ----------------------------------------------------------- frame codec


def _roundtrip(meta, arrays):
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    # send from a thread: a frame larger than the socketpair buffer would
    # deadlock a synchronous send with nobody draining the other end
    t = threading.Thread(
        target=send_frame, args=(a, KIND_REQUEST, 7, meta, arrays))
    t.start()
    try:
        kind, rid, rmeta, rarrays = recv_frame(b)
    finally:
        t.join()
        a.close()
        b.close()
    assert (kind, rid) == (KIND_REQUEST, 7)
    return rmeta, rarrays


def test_frame_roundtrip_small_coalesced():
    meta = {"method": "query", "n_real": 3, "nested": {"x": [1, 2]}}
    arrays = [np.arange(12, dtype=np.int32).reshape(3, 4),
              np.array([1.5, -2.5], np.float64),
              np.zeros((0, 5), np.int64),            # empty is legal
              np.array([True, False]),
              np.arange(6, dtype=np.uint8)]
    rmeta, rarrays = _roundtrip(meta, arrays)
    assert rmeta == meta
    assert len(rarrays) == len(arrays)
    for sent, got in zip(arrays, rarrays):
        assert got.dtype == sent.dtype and got.shape == sent.shape
        np.testing.assert_array_equal(got, sent)


def test_frame_roundtrip_large_vectored():
    # well past _COALESCE_BYTES: exercises the vectored sendall path
    big = np.arange(300 * 300, dtype=np.int64).reshape(300, 300)
    rmeta, (got,) = _roundtrip({"seq": 9}, [big])
    assert rmeta == {"seq": 9}
    np.testing.assert_array_equal(got, big)


def test_frame_rejects_off_whitelist_dtype():
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        with pytest.raises(TypeError, match="whitelist"):
            send_frame(a, KIND_REQUEST, 1, {},
                       [np.zeros(3, np.float16)])
    finally:
        a.close()
        b.close()


def test_codec_accepts_exactly_the_wire_whitelist():
    """The codec and ``WIRE_DTYPES`` cannot drift: every whitelisted dtype
    round-trips, every other numpy scalar dtype is rejected at encode time,
    and the whitelist itself is pinned (codes are tuple positions — a
    reorder or removal is a silent protocol break)."""
    assert WIRE_DTYPES == tuple(np.dtype(t) for t in (
        np.int32, np.int64, np.uint32, np.uint64, np.float32, np.float64,
        np.uint8, np.int8, np.int16, np.uint16, np.bool_))

    for dt in WIRE_DTYPES:
        arr = np.ones((3,), dt)
        _, (got,) = _roundtrip({}, [arr])
        assert got.dtype == dt
        np.testing.assert_array_equal(got, arr)

    # the complement: every concrete numpy scalar type NOT on the whitelist
    # must be rejected by the encoder (never silently coerced or shipped)
    complement = {np.dtype(t) for t in np.sctypeDict.values()
                  if np.dtype(t).kind not in "OMm"} - set(WIRE_DTYPES)
    assert np.dtype(np.float16) in complement          # sanity: non-empty
    for dt in sorted(complement, key=str):
        a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            with pytest.raises(TypeError, match="whitelist"):
                send_frame(a, KIND_REQUEST, 1, {}, [np.zeros(2, dt)])
        finally:
            a.close()
            b.close()


def test_frame_rejects_garbage_and_truncation():
    # bad magic after a plausible length prefix
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    a.sendall(np.uint64(14).tobytes() + b"\x00" * 14)
    with pytest.raises(ConnectionError, match="magic"):
        recv_frame(b)
    a.close()
    b.close()

    # implausible frame length must not trigger a giant allocation
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    a.sendall(np.uint64(1 << 60).tobytes())
    with pytest.raises(ConnectionError, match="implausible"):
        recv_frame(b)
    a.close()
    b.close()

    # peer dying mid-frame surfaces as ConnectionError, not a hang
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    a.sendall(np.uint64(100).tobytes() + b"\x01" * 10)
    a.close()
    with pytest.raises(ConnectionError, match="mid-frame"):
        recv_frame(b)
    b.close()


# ------------------------------------------------ frame codec over TCP


def _tcp_pair():
    """A connected (client, server) AF_INET loopback socket pair."""
    srv = listen_tcp("127.0.0.1", 0)
    host, port = srv.getsockname()[:2]
    client = connect_tcp(host, port, timeout_s=10.0)
    peer, _ = srv.accept()
    srv.close()
    return client, peer


def _capture_frame(meta, arrays, kind=KIND_REQUEST, rid=5):
    """The exact wire bytes of one frame, via a drained socketpair."""
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    t = threading.Thread(target=send_frame, args=(a, kind, rid, meta, arrays))
    t.start()
    try:
        hdr = bytearray()
        while len(hdr) < 8:
            hdr += b.recv(8 - len(hdr))
        n = int(np.frombuffer(bytes(hdr), np.uint64)[0])
        body = bytearray()
        while len(body) < n:
            body += b.recv(min(1 << 16, n - len(body)))
    finally:
        t.join()
        a.close()
        b.close()
    return bytes(hdr) + bytes(body)


def test_tcp_partial_recv_at_every_split_point():
    """``recv_frame`` must reassemble a frame no matter where the kernel
    splits the stream — pinned by sending the same frame over loopback
    TCP once per possible byte boundary, each time in two delayed halves
    (TCP, unlike AF_UNIX socketpairs, genuinely fragments)."""
    meta = {"method": "query", "n_real": 3}
    arrays = [np.arange(10, dtype=np.int32),
              np.array([True, False, True])]
    blob = _capture_frame(meta, arrays)
    cuts = range(1, len(blob))
    client, peer = _tcp_pair()
    got, errs = [], []

    def reader():
        try:
            for _ in cuts:
                got.append(recv_frame(peer))
        except Exception as exc:            # surfaced on the main thread
            errs.append(exc)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for cut in cuts:
            client.sendall(blob[:cut])
            time.sleep(0.001)               # let the first half land alone
            client.sendall(blob[cut:])
        t.join(timeout=60)
    finally:
        client.close()
        peer.close()
    assert not errs, errs
    assert len(got) == len(cuts)
    for kind, rid, rmeta, rarrays in got:
        assert (kind, rid) == (KIND_REQUEST, 5)
        assert rmeta == meta
        np.testing.assert_array_equal(rarrays[0], arrays[0])
        np.testing.assert_array_equal(rarrays[1], arrays[1])


def test_tcp_large_vectored_frame():
    # 720KB payload: far past both 64KB and the coalesce threshold, so the
    # vectored sendall path crosses many TCP segments
    big = np.arange(300 * 300, dtype=np.int64).reshape(300, 300)
    client, peer = _tcp_pair()
    t = threading.Thread(
        target=send_frame, args=(client, KIND_REQUEST, 3, {"seq": 1}, [big]))
    t.start()
    try:
        kind, rid, rmeta, (got,) = recv_frame(peer)
    finally:
        t.join()
        client.close()
        peer.close()
    assert (kind, rid, rmeta) == (KIND_REQUEST, 3, {"seq": 1})
    np.testing.assert_array_equal(got, big)


def test_tcp_rejects_garbage_and_truncation():
    client, peer = _tcp_pair()
    client.sendall(np.uint64(14).tobytes() + b"\x00" * 14)
    with pytest.raises(ConnectionError, match="magic"):
        recv_frame(peer)
    client.close()
    peer.close()

    client, peer = _tcp_pair()
    client.sendall(np.uint64(1 << 60).tobytes())
    with pytest.raises(ConnectionError, match="implausible"):
        recv_frame(peer)
    client.close()
    peer.close()

    client, peer = _tcp_pair()
    client.sendall(np.uint64(100).tobytes() + b"\x01" * 10)
    client.close()                          # peer dies mid-frame
    with pytest.raises(ConnectionError, match="mid-frame"):
        recv_frame(peer)
    peer.close()


def test_tcp_typed_error_and_echo_roundtrip():
    for exc, expect in [(ReplicaKilled("gone"), ReplicaKilled),
                        (ValueError("bad dim"), ValueError),
                        (ArithmeticError("weird"), RemoteError)]:
        client, peer = _tcp_pair()
        t = threading.Thread(
            target=_serve_one, args=(peer, lambda c, rid, *_: (
                c.respond_error(rid, exc))))
        t.start()
        conn = Connection(client, timeout_s=10.0)
        with pytest.raises(expect, match=r"\[worker\]"):
            conn.request("boom")
        t.join()
        conn.close()
        peer.close()

    client, peer = _tcp_pair()
    t = threading.Thread(
        target=_serve_one, args=(peer, lambda c, rid, method, meta, arrays: (
            c.respond(rid, {"method_seen": method, **meta}, arrays))))
    t.start()
    conn = Connection(client, timeout_s=10.0)
    sent = np.arange(5, dtype=np.int32)
    meta, (got,) = conn.request("echo", {"x": 3}, [sent])
    assert meta == {"method_seen": "echo", "x": 3}
    np.testing.assert_array_equal(got, sent)
    t.join()
    conn.close()
    peer.close()


def test_tcp_connect_retries_until_listener_binds():
    """Connection-refused at connect time means the worker has not bound
    yet — ``connect_tcp`` must retry past it instead of failing the boot."""
    probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()                           # port free: refused until bound

    accepted = []

    def late_listener():
        time.sleep(0.4)
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(1)
        peer, _ = srv.accept()
        accepted.append(peer)
        srv.close()

    t = threading.Thread(target=late_listener)
    t.start()
    client = connect_tcp("127.0.0.1", port, timeout_s=10.0)
    t.join()
    assert accepted
    client.close()
    accepted[0].close()


# ------------------------------------------------- request/response pairing


def _serve_one(sock, reply):
    """Minimal single-request server half for a socketpair."""
    conn = Connection(sock)
    rid, method, meta, arrays = conn.recv_request()
    reply(conn, rid, method, meta, arrays)


def test_connection_roundtrip_and_error_mapping():
    for exc, expect in [(ReplicaKilled("gone"), ReplicaKilled),
                        (ReplicaDiverged("fork"), ReplicaDiverged),
                        (ValueError("bad dim"), ValueError),
                        (ArithmeticError("weird"), RemoteError)]:
        a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
        t = threading.Thread(
            target=_serve_one, args=(b, lambda c, rid, *_: (
                c.respond_error(rid, exc))))
        t.start()
        client = Connection(a, timeout_s=10.0)
        with pytest.raises(expect, match=r"\[worker\]"):
            client.request("boom")
        t.join()
        client.close()
        b.close()

    # happy path: meta + arrays echo back under the request's id
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    t = threading.Thread(
        target=_serve_one, args=(b, lambda c, rid, method, meta, arrays: (
            c.respond(rid, {"method_seen": method, **meta}, arrays))))
    t.start()
    client = Connection(a, timeout_s=10.0)
    sent = np.arange(5, dtype=np.int32)
    meta, (got,) = client.request("echo", {"x": 3}, [sent])
    assert meta == {"method_seen": "echo", "x": 3}
    np.testing.assert_array_equal(got, sent)
    t.join()
    client.close()
    b.close()


def test_connection_detects_mispaired_response_id():
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    t = threading.Thread(
        target=_serve_one, args=(b, lambda c, rid, *_: (
            send_frame(c.sock, KIND_RESPONSE, rid + 99, {}))))
    t.start()
    client = Connection(a, timeout_s=10.0)
    with pytest.raises(ConnectionError, match="response id"):
        client.request("ping")
    t.join()
    client.close()
    b.close()


def test_pack_unpack_records_roundtrip():
    recs = [WalRecord(seq=3, op=OP_INSERT,
                      gids=np.array([4, 5], np.int32),
                      points=np.arange(8, dtype=np.int32).reshape(2, 4)),
            WalRecord(seq=4, op=OP_DELETE, gids=np.array([4], np.int32))]
    meta, arrays = pack_records(recs)
    out = unpack_records(meta, arrays)
    assert [(r.seq, r.op) for r in out] == [(3, OP_INSERT), (4, OP_DELETE)]
    np.testing.assert_array_equal(out[0].gids, recs[0].gids)
    np.testing.assert_array_equal(out[0].points, recs[0].points)
    np.testing.assert_array_equal(out[1].gids, recs[1].gids)
    assert out[1].points is None


# --------------------------------------------- worker process integration


def test_worker_env_inherits_platform_and_refuses_a_held_chip(monkeypatch):
    # workers inherit the parent's JAX_PLATFORMS (no CPU pin of their own);
    # where the parent would hold an accelerator, spawning is refused
    from repro.cluster import remote

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert remote._worker_env()["JAX_PLATFORMS"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in remote._worker_env()   # CPU-only host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="in-process router"):
        remote._worker_env()


def test_remote_replica_bit_identical_and_sigkill_recovery(
        cfg, small, tmp_path):
    """One worker subprocess == one in-process replica, bit for bit: same
    answers, same mutation application, and SIGKILL + respawn recovers the
    acknowledged state from its own snapshot + WAL."""
    data, queries = small
    local = ShardReplica(0, 0, cfg, serve_cfg(), KEY,
                         str(tmp_path / "local"), data, wal_fsync=False)
    remote = RemoteReplica(0, 0, cfg, serve_cfg(), KEY,
                           str(tmp_path / "remote"), data, wal_fsync=False)
    try:
        ld, li = local.query(queries, queries.shape[0])
        rd, ri = remote.query(queries, queries.shape[0])
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(rd))
        np.testing.assert_array_equal(np.asarray(li), np.asarray(ri))

        rec = WalRecord(seq=1, op=OP_INSERT,
                        gids=np.arange(local.next_gid, local.next_gid + 4,
                                       dtype=np.int32),
                        points=(queries[:4] + 1).astype(np.int32))
        local.log_and_apply(rec)
        remote.log_and_apply(rec)
        assert remote.last_seq == local.last_seq == 1
        assert remote.next_gid == local.next_gid
        assert remote.num_live == local.num_live
        ld, li = local.query(queries, queries.shape[0])
        rd, ri = remote.query(queries, queries.shape[0])
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(rd))
        np.testing.assert_array_equal(np.asarray(li), np.asarray(ri))

        # an UNANNOUNCED process death maps to the in-process failure mode
        remote.handle.sigkill()
        with pytest.raises(ReplicaKilled):
            remote.query(queries, queries.shape[0])
        assert remote.recover() >= 1        # respawn + WAL replay from disk
        rd, ri = remote.query(queries, queries.shape[0])
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(rd))
        np.testing.assert_array_equal(np.asarray(li), np.asarray(ri))

        # typed errors cross the wire: a diverging replay is rejected
        # remotely with the same exception class as locally (kept last:
        # log-then-apply means the diverged record IS in the WAL, exactly
        # as in-process — DESIGN.md §7's divergence-is-fatal contract)
        bad = WalRecord(seq=2, op=OP_INSERT,
                        gids=np.array([999999], np.int32),
                        points=queries[:1].astype(np.int32))
        with pytest.raises(ReplicaDiverged):
            remote.log_and_apply(bad)
    finally:
        local.close()
        remote.close()


@pytest.mark.parametrize("transport", ["process", "tcp"])
def test_process_router_matches_flat_and_survives_sigkill(
        transport, cfg, small, tmp_path):
    """The §7 consistency oracle over real worker processes: S=2 x R=2
    subprocesses answer bit-identically to the flat single-engine path,
    an unannounced SIGKILL mid-traffic fails over with zero drops, and
    crash-restart + peer catch-up restores full redundancy — over both
    the AF_UNIX wire and the loopback TCP (multi-host) wire."""
    data, queries = small
    state = build_index(cfg, KEY, jnp.asarray(data))
    fd, fi = map(np.asarray, query_index(cfg, state, jnp.asarray(queries)))

    router = ClusterRouter(
        cfg, serve_cfg(),
        ClusterConfig(num_shards=2, num_replicas=2, transport=transport,
                      hedge_ms=60000, wal_fsync=False, cache_capacity=0,
                      pipeline_depth=2),
        data, str(tmp_path), key=KEY)
    mirror = AnnServingEngine(cfg, serve_cfg(), dataset=jnp.asarray(data),
                              key=KEY)
    try:
        cd, ci = router.query(queries)
        np.testing.assert_array_equal(cd, fd)
        np.testing.assert_array_equal(ci, fi)

        # crash without telling the router: failover must keep identity
        router.replicas[0][0].handle.sigkill()
        router._rr[0] = 0                   # dead worker is the preferred
        cd2, ci2 = router.query(queries)    # replica for the next batch
        np.testing.assert_array_equal(cd2, fd)
        np.testing.assert_array_equal(ci2, fi)
        assert router.summary()["failovers"] >= 1

        # mutations while a worker is dead land on the survivors' WALs
        pts = (queries[:6] + 2).astype(np.int32)
        np.testing.assert_array_equal(router.insert(pts), mirror.insert(pts))
        router.delete([1, 3])
        mirror.delete([1, 3])

        # crash-restart: respawn + disk recovery + peer catch-up, then force
        # the recovered worker to serve by killing its peer
        info = router.recover_replica(0, 0)
        assert info["replayed"] + info["caught_up"] >= 1
        router.kill_replica(0, 1)
        cd3, ci3 = router.query(queries)
        md, mi = mirror.query_batch(queries)
        np.testing.assert_array_equal(cd3, md)
        np.testing.assert_array_equal(ci3, mi)
    finally:
        router.close()


# --------------------------------------------- shm fast path under SIGKILL


def _foreign_slabs(baseline):
    """Slab segments that appeared since ``baseline`` and belong to a
    DEAD owner — i.e. actual leaks (live workers legitimately hold
    rings until they exit)."""
    leaked = []
    for fn in set(shm.list_slabs()) - baseline:
        try:
            pid = int(fn[len(shm.SHM_PREFIX):].split("-")[0])
        except ValueError:
            continue
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            leaked.append(fn)
    return leaked


def test_sigkill_under_shm_reaps_slab_and_stays_identical(
        cfg, small, tmp_path):
    """The §13 drill: a worker is SIGKILL'd while SLOW mid-query — its
    request slab slot is claimed, its response never comes — and nothing
    leaks: the hedged re-issue answers bit-identically, the recovery
    path reaps the dead worker's orphan ring, and after ``close()`` the
    ``/dev/shm`` population is exactly the pre-test baseline."""
    data, queries = small
    shm.reap_orphan_slabs()                 # start from a clean room
    baseline = set(shm.list_slabs())
    router = ClusterRouter(
        cfg, serve_cfg(),
        ClusterConfig(num_shards=2, num_replicas=2, transport="process",
                      hedge_ms=200.0, wal_fsync=False, cache_capacity=0,
                      shm_threshold_bytes=64),
        data, str(tmp_path), key=KEY)
    try:
        d0, i0 = router.query(queries)      # warm: slabs mapped both ways

        # victim hangs well past the hedge deadline with the staged
        # request slot outstanding; the peer's hedged answer wins
        victim = router.replicas[0][0]
        victim.slow_ms = 30000.0
        router._rr[0] = 0                   # victim is preferred next
        done = threading.Event()

        def kill_mid_query():
            # fires while the victim sleeps inside its handler — the
            # mapped slab (and its in-flight slot borrow) dies with it
            time.sleep(0.6)
            victim.handle.sigkill()
            done.set()

        killer = threading.Thread(target=kill_mid_query)
        killer.start()
        d1, i1 = router.query(queries)      # hedge fires at 200ms
        killer.join()
        assert done.is_set()
        np.testing.assert_array_equal(d1, d0)
        np.testing.assert_array_equal(i1, i0)
        assert router.summary()["hedged_batches"] >= 1

        # recovery respawns the worker AND reaps any orphaned ring the
        # SIGKILL left behind; no dead-owner segment may survive it
        router.recover_replica(0, 0)
        assert _foreign_slabs(baseline) == []

        d2, i2 = router.query(queries)
        np.testing.assert_array_equal(d2, d0)
        np.testing.assert_array_equal(i2, i0)
    finally:
        router.close()
    # descriptor-leak oracle: the /dev/shm delta is exactly zero
    shm.reap_orphan_slabs()
    assert set(shm.list_slabs()) == baseline


def test_reap_orphan_slabs_spares_live_owners(tmp_path):
    """The reaper unlinks dead-owner segments only: a ring owned by this
    live process survives, a hand-planted segment named for a dead pid
    goes away."""
    ours = shm.SlabRing(slots=2, slot_bytes=64, tag="keep")
    # a real dead pid: a subprocess that has already exited
    probe = subprocess.run([sys.executable, "-c",
                            "import os; print(os.getpid())"],
                           capture_output=True, text=True, check=True)
    dead_pid = int(probe.stdout)
    orphan = f"{shm.SHM_PREFIX}{dead_pid}-wtx-deadbeef"
    path = os.path.join(shm.SHM_DIR, orphan)
    with open(path, "wb") as f:
        f.write(b"\x00" * 64)
    try:
        reaped = shm.reap_orphan_slabs()
        assert orphan in reaped
        assert not os.path.exists(path)
        assert ours.name in shm.list_slabs()
        assert ours.free_slots() == 2       # untouched by the sweep
    finally:
        ours.close()
        if os.path.exists(path):
            os.unlink(path)
    assert ours.name not in shm.list_slabs()
