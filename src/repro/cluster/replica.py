"""One shard replica: engine + WAL + snapshots (DESIGN.md §7).

A replica owns a full copy of its shard — an ``AnnServingEngine`` over the
shard's points — plus the two pieces that make it durable and replaceable:

  * a :class:`~repro.cluster.wal.WriteAheadLog`: every mutation batch is
    fsync'd to the log *before* it is applied to the engine, so an
    acknowledged insert/delete survives a kill;
  * a ``CheckpointManager`` snapshot, taken whenever applying a mutation
    triggered a compaction (the index is then one flat segment — the
    cheapest possible state to capture) and at explicit ``snapshot()``
    calls.  The snapshot stores the raw shard rows + local gids +
    ``next_gid`` + the WAL seq it covers; the hash-table state is NOT
    stored — it is rebuilt deterministically from the shared params key,
    which keeps snapshots small and restore elastic.

Recovery (:meth:`ShardReplica.recover`) = restore the latest snapshot,
rebuild the index, replay the WAL tail.  Because ``SegmentedIndex`` applies
mutations deterministically (gid assignment is a counter; sealing points
depend only on the order and sizes of inserts), replay reconstructs the
replica's acknowledged state bit-identically — the determinism is *checked*
on every replayed insert against the gids recorded at append time.

A replica that was down while its peers kept acknowledging mutations has a
WAL gap; :meth:`catch_up_from` closes it from a live peer — record-level
when the peer still has the records, full state transfer when the peer
already truncated them into a snapshot.  The catch-up primitives
(``wal_records`` / ``apply_records`` / ``adopt_payload`` /
``export_payload``) are the replica's narrow interface: the remote proxy
(``repro.cluster.remote.RemoteReplica``) implements the same five methods
over RPC, which is what lets one ``catch_up_from`` serve both the
in-process and the cross-process topologies.

Snapshot cadence (DESIGN.md §10): besides riding on compaction, snapshots
trigger on WAL growth (``snapshot_every_bytes``) and wall-clock age
(``snapshot_every_s``), so recovery time is bounded by policy instead of
by how long compaction happens not to fire.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import racecheck
from repro.ckpt import CheckpointManager
from repro.core.index import IndexConfig, build_index
from repro.core.segments import SegmentedIndex
from repro.kernels.rows import widen_rows
from repro.serve.engine import AnnServingEngine, ServeConfig

from .concurrency import under_quiesce
from .wal import OP_DELETE, OP_INSERT, WalRecord, WriteAheadLog

__all__ = ["ShardReplica", "ReplicaKilled", "ReplicaDiverged"]


class ReplicaKilled(RuntimeError):
    """Raised when a query/mutation reaches a dead replica."""


class ReplicaDiverged(RuntimeError):
    """Replay/apply produced different gids than the WAL recorded."""


class ShardReplica:
    """One replica of one shard; all replicas of a shard are bit-identical."""

    def __init__(self, shard_id: int, replica_id: int, cfg: IndexConfig,
                 serve_cfg: ServeConfig, key: jax.Array, root: str,
                 seed_dataset: np.ndarray, keep_snapshots: int = 2,
                 wal_fsync: bool = True,
                 snapshot_every_bytes: Optional[int] = None,
                 snapshot_every_s: Optional[float] = None):
        self.shard_id = shard_id
        self.replica_id = replica_id
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.key = key
        self.root = root
        self._wal_fsync = wal_fsync
        self.snapshot_every_bytes = snapshot_every_bytes
        self.snapshot_every_s = snapshot_every_s
        self._last_snap_t = time.monotonic()
        os.makedirs(root, exist_ok=True)
        self.ckpt = CheckpointManager(os.path.join(root, "ckpt"),
                                      keep=keep_snapshots)
        self.wal = WriteAheadLog(os.path.join(root, "wal.log"),
                                 fsync=wal_fsync)
        self.alive = True
        self.last_seq = self.wal.last_seq
        self.snapshots_taken = 0
        # test/chaos seams driven by the router's failure-injection hooks
        self.fail_next_queries = 0     # raise ReplicaKilled on next N queries
        self.slow_ms = 0.0             # added latency per query batch
        self.recovered_records = 0     # WAL records replayed by a ctor recover
        if self.ckpt.latest_step() is None and self.last_seq == 0:
            # fresh replica: build from the seed slice and immediately take
            # the base snapshot, so recovery ALWAYS has a snapshot to start
            # from (the seed rows are not in the WAL).
            self.engine = AnnServingEngine(
                cfg, serve_cfg, dataset=jnp.asarray(seed_dataset), key=key)
            self._last_snap_compactions = self.engine.index.compactions
            self.snapshot()
        else:
            # directory already holds state (restart path): recover from it
            self.engine = None
            self.recovered_records = self.recover()
        # opt-in race sanitizer (REPRO_SANITIZE=1): instrument at the END of
        # the ctor so boot-time recover()/snapshot() stay unwrapped
        racecheck.maybe_instrument(
            self, f"shard{shard_id}r{replica_id}",
            queries=("query",),
            mutations=("log_and_apply", "apply_records", "adopt_payload",
                       "recover", "catch_up_from", "compact", "kill"))

    # -- mutation log + apply ---------------------------------------------

    @under_quiesce
    def log_and_apply(self, record: WalRecord) -> int:
        """WRITE-ahead: fsync the record, then apply it.  Returns removed
        count for deletes (insert returns 0)."""
        if not self.alive:
            raise ReplicaKilled(
                f"shard {self.shard_id} replica {self.replica_id} is down")
        self.wal.append_record(record)
        return self._apply(record)

    @under_quiesce
    def _apply(self, record: WalRecord) -> int:
        removed = 0
        if record.op == OP_INSERT:
            got = self.engine.insert(record.points)
            if not np.array_equal(np.asarray(got, np.int32), record.gids):
                raise ReplicaDiverged(
                    f"shard {self.shard_id} replica {self.replica_id}: "
                    f"insert assigned gids {got[:4]}… but the WAL recorded "
                    f"{record.gids[:4]}… (seq {record.seq})")
        elif record.op == OP_DELETE:
            removed = self.engine.delete(record.gids)
        else:
            raise ValueError(f"unknown WAL op {record.op}")
        self.last_seq = record.seq
        self._maybe_snapshot()
        return removed

    def _maybe_snapshot(self) -> None:
        """Snapshot-cadence policy (DESIGN.md §10).

        Three independent triggers, any of which fires a snapshot + WAL
        truncation: (1) applying the mutation compacted the index (the
        original ride-on-compaction trigger — one flat segment is the
        cheapest state to capture); (2) the WAL grew past
        ``snapshot_every_bytes``; (3) the last snapshot is older than
        ``snapshot_every_s``.  (2) and (3) bound recovery work by policy:
        WAL replay never exceeds one cadence interval of mutations, no
        matter how long the compaction watermarks stay unfired.
        """
        if self.engine.index.compactions != self._last_snap_compactions:
            # the index is one flat segment right now, so the payload is
            # minimal and the WAL prefix it covers can be truncated away
            self.snapshot()
            return
        if (self.snapshot_every_bytes is not None
                and self.wal.size_bytes >= self.snapshot_every_bytes):
            self.snapshot()
            return
        if (self.snapshot_every_s is not None
                and time.monotonic() - self._last_snap_t
                >= self.snapshot_every_s):
            self.snapshot()

    # -- query -------------------------------------------------------------

    def query(self, batch: np.ndarray, n_real: int):
        """Serve one pre-padded batch (padded results; router slices).

        Honors the chaos seams: a killed replica raises, an injected-slow
        replica sleeps past the router's hedge deadline first.
        """
        if not self.alive:
            raise ReplicaKilled(
                f"shard {self.shard_id} replica {self.replica_id} is down")
        if self.fail_next_queries > 0:
            self.fail_next_queries -= 1
            raise ReplicaKilled(
                f"shard {self.shard_id} replica {self.replica_id}: "
                "injected query failure")
        if self.slow_ms > 0:
            time.sleep(self.slow_ms / 1e3)
        return self.engine.run_padded(batch, n_real)

    # -- durability --------------------------------------------------------

    def export_payload(self):
        """(dataset rows, local gids, next_gid) covering every acknowledged
        mutation — the snapshot payload AND the peer-state-transfer unit.

        A shard that emptied out (delete-all + compact) has no segment to
        checkpoint; the empty payload is still valid — ``next_gid`` must
        survive so replay keeps assigning the same ids.
        """
        try:
            state, gids, next_gid = self.engine.checkpoint_payload()
            return (np.asarray(widen_rows(state.dataset)),
                    np.asarray(gids, np.int32), int(next_gid))
        except RuntimeError:
            return (np.zeros((0, self.engine.index.dim), np.int32),
                    np.zeros((0,), np.int32), self.engine.index.next_gid)

    def snapshot(self) -> int:
        """Checkpoint the engine state + WAL position; truncate the log.

        Returns the snapshot step (== the WAL seq it covers).  A repeat
        snapshot at the current seq (e.g. an explicit compact right after
        an auto-snapshot) is a no-op: the existing snapshot already covers
        the identical logical state, and rewriting it would only open an
        overwrite window on the one file recovery depends on.
        """
        if self.ckpt.latest_step() == self.last_seq:
            # (an empty ckpt dir reports latest_step() None, which never
            # equals a seq, so the base snapshot always proceeds — the
            # guard must not depend on in-memory counters like
            # snapshots_taken, which reset to 0 on restart)
            return self.last_seq
        dataset, gids, next_gid = self.export_payload()
        self.ckpt.save(self.last_seq, {
            "dataset": dataset,
            "gids": gids,
            "next_gid": np.int32(next_gid),
            "wal_seq": np.int64(self.last_seq),
        })
        self.wal.truncate_upto(self.last_seq)
        self._last_snap_compactions = self.engine.index.compactions
        self._last_snap_t = time.monotonic()
        self.snapshots_taken += 1
        return self.last_seq

    @under_quiesce
    def compact(self) -> None:
        """Force a major compaction and snapshot the flat result (the
        router's ``compact()`` fan-out lands here; the remote proxy ships
        the same call as one RPC)."""
        self.engine.compact()
        self.snapshot()

    def kill(self) -> None:
        """Simulate a process death: drop in-memory state, keep disk."""
        self.alive = False
        self.engine = None
        self.wal.close()

    @under_quiesce
    def recover(self) -> int:
        """Snapshot restore + WAL replay; returns #records replayed.

        The rebuilt index is bit-identical in content to the killed
        replica's acknowledged state: the snapshot rows are exact, the hash
        tables are rebuilt from the same deterministic params key, and the
        WAL tail replays the post-snapshot mutations in their original
        order (gid assignment re-checked per record).
        """
        if getattr(self, "wal", None) is not None and not self.wal.closed:
            # died without kill() (health markdown / failed mutation): the
            # old append handle is still open — close it or every
            # markdown->recover cycle leaks an fd
            self.wal.close()
        self.wal = WriteAheadLog(os.path.join(self.root, "wal.log"),
                                 fsync=self._wal_fsync)
        step = self.ckpt.latest_step()
        if step is None:
            raise RuntimeError(
                f"shard {self.shard_id} replica {self.replica_id}: no "
                "snapshot to recover from (base snapshot missing)")
        snap = self.ckpt.restore_flat_step(step)
        dataset = jnp.asarray(snap["dataset"])
        state = build_index(self.cfg, self.key, dataset)
        index = SegmentedIndex.from_checkpoint(
            self.cfg, state, jnp.asarray(snap["gids"]),
            int(snap["next_gid"]), delta_cap=self.serve_cfg.delta_cap,
            cap_quantile=self.serve_cfg.cand_cap_quantile,
            cap_sample=self.serve_cfg.cand_cap_sample)
        self.engine = AnnServingEngine(self.cfg, self.serve_cfg, index=index)
        self._last_snap_compactions = self.engine.index.compactions
        self.last_seq = int(snap["wal_seq"])
        replayed = 0
        for rec in self.wal.records(after_seq=self.last_seq):
            self._apply(rec)
            replayed += 1
        self.alive = True
        # a restarted process does not inherit injected chaos
        self.fail_next_queries = 0
        self.slow_ms = 0.0
        return replayed

    # -- catch-up primitives (the replica interface the proxy mirrors) ------

    def wal_records(self, after_seq: int = 0):
        """Complete WAL records with seq > ``after_seq`` (peer-serving side
        of record-level catch-up)."""
        return self.wal.records(after_seq=after_seq)

    @under_quiesce
    def apply_records(self, records) -> int:
        """Append + apply already-sequenced records from a peer (seq
        preserved); returns how many were applied."""
        for rec in records:
            self.wal.append_record(rec)
            self._apply(rec)
        return len(records)

    @under_quiesce
    def adopt_payload(self, dataset, gids, next_gid: int, seq: int) -> None:
        """Full state transfer: replace the engine with a peer's exported
        payload at ``seq`` and snapshot it as our own durable base.

        Rebuilds the hash tables from the shared params key (payload, not
        IndexState — survives an emptied shard), exactly like recover().
        """
        dataset = np.asarray(dataset, np.int32)
        state = build_index(self.cfg, self.key, jnp.asarray(dataset))
        index = SegmentedIndex.from_checkpoint(
            self.cfg, state, jnp.asarray(np.asarray(gids, np.int32)),
            int(next_gid), delta_cap=self.serve_cfg.delta_cap,
            cap_quantile=self.serve_cfg.cand_cap_quantile,
            cap_sample=self.serve_cfg.cand_cap_sample)
        self.engine = AnnServingEngine(self.cfg, self.serve_cfg, index=index)
        self.last_seq = int(seq)
        self._last_snap_compactions = self.engine.index.compactions
        self.snapshot()                # own durable base at the new seq

    @under_quiesce
    def catch_up_from(self, peer) -> int:
        """Close the WAL gap against a live peer; returns #records applied.

        Mutations acknowledged while this replica was down never reached
        its WAL.  If the peer still has the missing records (its WAL starts
        at or before our ``last_seq + 1``), they are appended to our WAL
        (seq preserved) and applied — the cheap path.  If the peer already
        truncated them into a snapshot, fall back to a full state transfer
        of the peer's payload.  ``peer`` is anything with the replica
        interface — an in-process ``ShardReplica`` or a ``RemoteReplica``
        proxy; this method only touches ``last_seq`` / ``wal_records`` /
        ``export_payload``, so catch-up works across any topology mix.
        """
        if peer.last_seq <= self.last_seq:
            return 0
        missing = peer.wal_records(after_seq=self.last_seq)
        have = {r.seq for r in missing}
        if all(s in have for s in range(self.last_seq + 1,
                                        peer.last_seq + 1)):
            return self.apply_records(missing)
        gap = peer.last_seq - self.last_seq
        dataset, gids, next_gid = peer.export_payload()
        self.adopt_payload(dataset, gids, next_gid, peer.last_seq)
        return gap

    # -- router-facing introspection ---------------------------------------

    @property
    def next_gid(self) -> int:
        """The shard-local gid counter (router restart re-derives the
        global counter as the sum of these)."""
        return self.engine.index.next_gid

    @property
    def num_live(self) -> int:
        return self.engine.index.num_live

    def validate_queries(self, queries) -> np.ndarray:
        return self.engine._validate_queries(queries)

    def bucket_for(self, q: int) -> int:
        return self.engine.bucket_for(q)

    def telemetry(self) -> dict:
        """Per-replica stats the router's ``summary()`` aggregates — one
        dict (and, remotely, one RPC) instead of N attribute reaches into
        the engine."""
        eng = self.engine
        return {
            "last_seq": self.last_seq,
            "snapshots": self.snapshots_taken,
            "wal_bytes": self.wal.size_bytes if not self.wal.closed else None,
            "num_live": eng.index.num_live,
            "bucket_cold_hits": eng.stats["bucket_cold_hits"],
            "cand_buckets": dict(sorted(eng.stats["cand_buckets"].items())),
            "overflow_hits": eng.stats["overflow_hits"],
            "truncated_candidates": eng.stats["truncated_candidates"],
            "skew_segments": eng.index.skew_summary(),
            # full registry snapshot (mergeable: the router folds one per
            # replica into the cluster view) + the flight recorder's
            # slow-batch exemplars — both JSON-able, so the process
            # transport carries them in the RPC meta unchanged
            "metrics": eng.metrics.snapshot(),
            "flight": {**eng.flight.summary(),
                       "exemplars": eng.flight.exemplars()},
        }

    def close(self) -> None:
        self.wal.close()
