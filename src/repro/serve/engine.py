"""Online serving engines.

``FeatureEngine`` is the paper's online request mode as a service: a
deployed feature script + live store + pre-aggregation states behind a
``request()`` call (Figure 3's Online Request Mode), with §8.2 memory
guarding and a bounded-memory retention lifecycle: ``retention="auto"``
derives each table's TTL horizon from the widest ROWS_RANGE window span
in the deployed script, runs a scheduled evict+compaction pass every
``compact_every`` ingested rows, and truncates the store binlog below
the consumed pre-aggregation offset — steady-state memory is bounded by
the window span, not total ingest (docs/architecture.md, "Store
lifecycle").

Batched serving: ``submit_request()`` enqueues a request into a
``RequestBatcher`` and ``flush()`` drains the queue through the batched
driver — B requests share one jitted call, one host->device transfer,
and one dispatch, so per-request cost falls roughly as 1/B until the
device saturates.  ``request_batch()`` computes a caller-assembled batch
directly.  The trade-off knobs (batch size vs tail latency) are
documented on ``RequestBatcher``; bulk ingest (``ingest_many``)
amortizes the same way on the write path via ``put_many`` +
``PreAgg.update_many``.

Stats hygiene: ``latencies_ms`` holds REAL request completion samples
only — every request in a batch completed when its batch call returned,
so each gets the batch wall time as its sample (never an amortized
``dt/B`` synthesized share, and never ingest timings).  Write-path
timing lives separately in ``ingest_ms`` / ``ingest_stats()`` so
``latency_percentiles()`` answers "what did requests experience", not
"what did the process do" (tests/test_serve_loop.py regression).

Event-driven serving (``serve.loop.ServeLoop``) wraps this engine with
deadline-aware batching, admission control, and a snapshot double
buffer: ``snapshot()`` cuts an immutable ``EngineSnapshot`` (store view
+ pre-agg states — O(#tables), no array copies) and
``request_batch(..., snapshot=snap)`` serves from the frozen view while
``ingest_many``/compaction/replication mutate the live store; the loop
swaps snapshots atomically between flushes (docs/architecture.md,
"Serving loop").

Sharded serving (paper §5 tablet partitioning): constructing the engine
with ``mesh=`` (a 1-D ``jax.sharding.Mesh``, see
``distributed.sharding.key_shard_mesh``) or ``n_shards=`` swaps the
store for a ``ShardedOnlineStore`` that hash-partitions keys across
shards, keeps per-shard pre-agg bucket planes, and transparently routes
``request`` / ``request_batch`` / ``submit_request`` / ``ingest_many``
through ``CompiledScript.online_sharded_batch`` — a ``shard_map`` fan-out
whose per-shard window folds are bit-exact vs the unsharded path
(tests/test_sharded_online.py).  ``rebalance()`` migrates hot keys
between shards (``core.union.LoadBalancer`` greedy LPT) together with
their pre-agg state.  With ``n_shards`` but no mesh, the same stacked
computation runs as a vmap over logical shards on one device.

Replicated serving (paper §5 deployment, replicated tablets):
``FeatureEngine(replication=R)`` attaches R follower replicas per shard
(``storage.replication.ReplicationManager``) fed asynchronously from the
store binlog every ``ship_every`` ingested rows, a
``FailoverController`` that promotes the most-caught-up follower when a
shard dies, and snapshot watermarks for pre-agg plane recovery.
``kill_shard()`` / ``heal()`` are the fault-injection hooks
(tests/test_replication.py, benchmarks/bench_failover.py): serving
after heal is **bitwise identical** to a never-killed engine because
promotion replays the same ordered binlog apply path the leader ran.

``ServingEngine`` wraps a model's prefill/decode for batched requests —
the "online ML" consumer of the features.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compiler import CompiledScript, compile_script
from ..core.types import Table
from ..distributed.fault import CheckpointManager
from ..spans import span
from ..storage.memest import MemoryGuard
from ..storage.replication import (FailoverController, PromotionRecord,
                                   ReplicationManager,
                                   recover_preagg_shard)
from ..storage.timestore import OnlineStore, ShardedOnlineStore
from .batcher import RequestBatcher

__all__ = ["FeatureEngine", "EngineSnapshot", "ServingEngine"]


class EngineSnapshot:
    """Atomic point-in-time view of everything the request path reads:
    the store (``storage.timestore.StoreSnapshot`` — frozen tables +
    frozen routing) and the pre-aggregation bucket states (immutable
    jnp pytrees, so holding the reference IS the snapshot).

    The serving loop serves every flush from one of these and calls
    ``refresh()`` only at controlled points (after an ingest apply /
    compaction / failover), so a bulk write never stalls — or leaks
    into — an in-flight batch.  ``refresh`` rebinds one reference per
    field; readers see the old view or the new one, never a mix.
    """

    def __init__(self, engine: "FeatureEngine"):
        self._engine = engine
        self.store = engine.store.snapshot()
        self.pre_states = engine.pre_states
        self.version = 0

    def refresh(self) -> int:
        """Re-cut from the live engine (atomic swap); returns version."""
        self.store.refresh()
        self.pre_states = self._engine.pre_states
        self.version += 1
        return self.version


class FeatureEngine:
    """Deployed feature script + online store (paper Figure 3, right)."""

    def __init__(self, script_sql: str, tables: Dict[str, Table],
                 capacity: int = 4096, use_preagg: bool = False,
                 ttl_ms: int = 0, time_unit: str = "ms",
                 max_memory_bytes: int = 1 << 34,
                 batch_size: int = 64, max_wait_ms: float = 5.0,
                 latency_window: int = 16384,
                 mesh=None, n_shards: Optional[int] = None,
                 shard_axis: str = "shard", route_slots: int = 1024,
                 retention=None, compact_every: int = 256,
                 replication: int = 0, ship_every: int = 64,
                 checkpoint_dir: Optional[str] = None,
                 heartbeat_timeout_s: float = 60.0,
                 fused_fold: bool = False):
        # fused_fold routes every request's window folds through the
        # unit-fold megakernel (kernels/unit_fold) — bitwise equal to
        # the staged fold engine, one dispatch per window group
        self.cs: CompiledScript = compile_script(
            _parse(script_sql, time_unit), tables=tables,
            fused_unit_fold=fused_fold)
        self.use_preagg = use_preagg
        self.ttl_ms = ttl_ms
        self.sharded = mesh is not None or (n_shards or 0) > 1
        if self.sharded:
            ok, why = self.cs.sharded_eligible()
            if not ok:
                raise ValueError(f"script not shardable by key: {why}")
            self.store = ShardedOnlineStore(
                capacity=capacity, n_shards=n_shards, mesh=mesh,
                axis=shard_axis, n_route_slots=route_slots)
        else:
            self.store = OnlineStore(capacity=capacity)
        self.guard = MemoryGuard(max_memory_bytes)
        # resolve the partition column ONCE: every window must agree (a
        # per-request next(iter(set)) is both wasted work and
        # nondeterministic under multiple partition columns)
        part_cols = sorted({w.node.spec.partition_by
                            for w in self.cs.windows})
        if len(part_cols) > 1:
            raise ValueError(
                f"script partitions windows by multiple columns "
                f"{part_cols}; one shared key column is required")
        self.key_col: Optional[str] = part_cols[0] if part_cols else None
        need = self.cs.required_store_columns()
        for tname, cols in need.items():
            table = tables[tname]
            specs = {}
            for c in cols:
                dd = table.schema.column(c).ctype.device_dtype
                specs[c] = np.float32 if dd.kind == "f" else np.int32
            self.store.create_table(tname, specs)
        self._need = need
        if not use_preagg:
            self.pre_states = None
        elif self.sharded:
            self.pre_states = self._place_pre(
                self.cs.init_preagg_states_sharded(self.store.n_shards))
        else:
            self.pre_states = self.cs.init_preagg_states()
        self.dicts = {name: t.dicts for name, t in tables.items()}
        self.tables = tables
        self.batcher = RequestBatcher(batch_size, max_wait_ms=max_wait_ms)
        # ---- retention lifecycle (store TTL + binlog watermark) ------
        # retention=None: off (explicit ttl_ms still applies);
        # retention="auto": per-table horizon = widest ROWS_RANGE window
        # span sourcing the table; retention=<int ms>: a horizon FLOOR —
        # never below any live window span.  Every ``compact_every`` ingested
        # rows the table is evicted+compacted below (high-watermark ts -
        # horizon) and the binlog is truncated below the consumed
        # pre-agg offset, so steady-state memory is bounded by the
        # window span instead of total ingest.
        self.compact_every = max(1, int(compact_every))
        self.retention_ms = self._derive_retention(retention)
        self._pending_rows: Dict[str, int] = {t: 0 for t in need}
        self._hwm_ts: Dict[str, int] = {t: -(2**31) for t in need}
        self._consumed_offset = 0
        self.n_requests = 0
        # bounded: sustained traffic must not grow host memory without
        # limit; percentiles are over the most recent window.  Request
        # and ingest timings are SEPARATE streams: latencies_ms holds
        # only real request completion samples (latency_percentiles),
        # ingest_ms holds write-path batch timings (ingest_stats) —
        # mixing them would let a burst of cheap amortized ingest rows
        # drown the request tail.
        self.latencies_ms: Deque[float] = collections.deque(
            maxlen=latency_window)
        self.ingest_ms: Deque[float] = collections.deque(
            maxlen=latency_window)
        self.rows_ingested = 0
        # ---- replication (per-shard followers + failover) ------------
        self.replication = int(replication)
        if self.replication and not self.sharded:
            raise ValueError("replication=R needs a sharded engine "
                             "(mesh= or n_shards=); an unsharded store "
                             "has no shard to replicate")
        self.ckpt = (CheckpointManager(checkpoint_dir)
                     if checkpoint_dir else None)
        self.failovers: List[PromotionRecord] = []
        if self.replication:
            self.repl = ReplicationManager(self.store, self.replication)
            self.controller = FailoverController(
                self.repl, timeout_s=heartbeat_timeout_s)
            self.ship_every = max(1, int(ship_every))
            self._rows_since_ship = 0
            # pre-agg recovery snapshot: (binlog watermark, stacked
            # bucket planes at that watermark).  jnp leaves are
            # immutable and every update replaces them functionally, so
            # a shallow dict copy IS a consistent point-in-time snapshot.
            self._snapshot = (0, dict(self.pre_states)
                              if self.pre_states is not None else None)
        else:
            self.repl = None
            self.controller = None

    # ---------------------------------------------------------- retention
    def _derive_retention(self, retention) -> Dict[str, Optional[int]]:
        """Per-table retention horizon (ms) from the deployed script.

        A table's horizon is the widest ROWS_RANGE window span among the
        windows sourcing it — rows older than (high-watermark -
        horizon) can never enter any window again, so evicting them
        changes no served feature (float results may shift within
        reduction-order tolerance: the prefix-scan anchor moves).
        Tables read by row-count (ROWS) frames or by LAST JOINs have no
        time horizon (the newest N rows / the last row per key can be
        arbitrarily old) and are left unbounded.
        """
        if retention is None:
            return {}
        fixed = None if retention == "auto" else int(retention)
        join_tables = {js.right_table for js in self.cs.script.last_joins}
        spans: Dict[str, Optional[int]] = {}
        for t in self._need:
            if t in join_tables:
                spans[t] = None
                continue
            span: Optional[int] = None
            for w in self.cs.windows:
                if t not in w.sources:
                    continue
                spec = w.node.spec
                if spec.frame_rows:
                    span = None
                    break
                span = max(span or 0, min(spec.preceding, 2**30))
            if span is not None and fixed is not None:
                # a numeric retention only ever EXTENDS the horizon —
                # shrinking below a live window span would evict rows
                # requests still fold, changing served features
                span = max(span, fixed)
            spans[t] = span
        return spans

    def _evict_release(self, table: str, horizon_ts: int):
        """Evict + compact below ``horizon_ts`` and credit the memory
        guard for the dropped rows (both the explicit ``ttl_ms`` path
        and the scheduled retention pass — without the release,
        ``guard.used`` would track total ingest instead of resident
        rows and eventually refuse writes to a bounded store)."""
        before = self.store.n_rows(table)
        self.store.evict(table, horizon_ts)
        evicted = before - self.store.n_rows(table)
        if evicted > 0:
            self.guard.release(evicted * (64 + 8 * len(self._need[table])))
        if self.repl is not None:
            # eviction is a replication barrier: binlog shipping replays
            # puts only, so followers must first apply every entry the
            # leader has (ship to the log head) and then run the SAME
            # eviction pass — otherwise a lagging follower could keep a
            # row the leader dropped (or vice versa) and promotion would
            # not be bitwise
            self.repl.ship()
            self.repl.evict(table, horizon_ts)

    def _after_ingest(self, table: str, n_rows: int, max_ts: int):
        """Scheduled retention tick on the ingest path.

        The engine folds pre-aggregation synchronously at ingest, so
        everything written to the binlog is already consumed — the
        consumed offset IS the truncation low-watermark.  Store
        eviction runs every ``compact_every`` rows per table (one
        jitted compaction pass), never per row.
        """
        if max_ts > self._hwm_ts.get(table, -(2**31)):
            self._hwm_ts[table] = max_ts
        self._consumed_offset = self.store._binlog_offset
        if self.repl is not None:
            self._rows_since_ship += n_rows
            if self._rows_since_ship >= self.ship_every:
                self._rows_since_ship = 0
                self.repl.ship()
                self.controller.beat()
        if not self.retention_ms:
            return
        self._pending_rows[table] = self._pending_rows.get(table, 0) + \
            n_rows
        if self._pending_rows[table] < self.compact_every:
            return
        self._pending_rows[table] = 0
        horizon = self.retention_ms.get(table)
        if horizon is not None:
            self._evict_release(table, self._hwm_ts[table] - horizon)
        self.store.truncate_binlog(self._durable_offset())

    def _durable_offset(self) -> int:
        """Binlog truncation low-watermark: entries below it are (a)
        folded into pre-agg state (consumed), (b) applied by EVERY
        follower replica (``ReplicationLog.safe_offset``), and (c) above
        the latest recovery snapshot's watermark — so neither a lagging
        follower catch-up, a promotion tail replay, nor a snapshot +
        replay recovery can ever need a truncated entry."""
        off = self._consumed_offset
        if self.repl is not None:
            off = min(off, self.repl.log.safe_offset(), self._snapshot[0])
        return off

    # ------------------------------------------------------------- ingest
    def ingest(self, table: str, row: Dict[str, Any]):
        """Insert an event (Put path + async pre-agg via binlog)."""
        if self.sharded:   # same routing path as bulk ingest
            return self.ingest_many(table, [row])
        t0 = time.perf_counter()
        key = self._encode(table, self._key_col(), row[self._key_col()])
        ts = int(row[self.cs.script.order_column])
        values = {c: float(self._encode(table, c, row[c]))
                  for c in self._need[table]}
        self.guard.charge(64 + 8 * len(values))
        self.store.put(table, key, ts, values)
        if self.use_preagg:
            self.pre_states = self.cs.preagg_update(
                self.pre_states, table, key, ts, values)
        if self.ttl_ms:
            self._evict_release(table, ts - self.ttl_ms)
        self._after_ingest(table, 1, ts)
        self.ingest_ms.append((time.perf_counter() - t0) * 1e3)
        self.rows_ingested += 1

    def ingest_many(self, table: str, rows: Sequence[Dict[str, Any]]):
        """Bulk insert of N events with one store sort-merge
        (``put_many``) and one batched pre-agg fold (``update_many``)
        instead of N O(capacity) shifts + N scatters."""
        if not rows:
            return
        t0 = time.perf_counter()
        with span("ingest.apply"):
            kc = self._key_col()
            keys = np.asarray([self._encode(table, kc, r[kc])
                               for r in rows], np.int32)
            ts = np.asarray([int(r[self.cs.script.order_column])
                             for r in rows], np.int32)
            cols = {c: np.asarray([float(self._encode(table, c, r[c]))
                                   for r in rows], np.float32)
                    for c in self._need[table]}
            nbytes = len(rows) * (64 + 8 * len(cols))
            self.guard.charge(nbytes)
            try:
                self.store.put_many(table, keys, ts, cols)
            except Exception:
                self.guard.release(nbytes)   # nothing was stored
                raise
            if self.use_preagg:
                if self.sharded:
                    self.pre_states = self.cs.preagg_update_many_sharded(
                        self.pre_states, table, keys, ts, cols,
                        self._preagg_owned())
                else:
                    self.pre_states = self.cs.preagg_update_many(
                        self.pre_states, table, keys, ts, cols)
            if self.ttl_ms:
                self._evict_release(table, int(ts.max()) - self.ttl_ms)
            self._after_ingest(table, len(rows), int(ts.max()))
        # write-path timing is tracked apart from request latencies:
        # one amortized batch write must never appear as N cheap
        # "request" samples and deflate the served percentiles
        self.ingest_ms.append((time.perf_counter() - t0) * 1e3)
        self.rows_ingested += len(rows)

    # ------------------------------------------------------------ request
    def request(self, row: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Online request mode: features for one (virtually inserted)
        tuple of the base table."""
        if self.sharded:   # single-request batch through the shard fan-out
            return self.request_batch([row])[0]
        t0 = time.perf_counter()
        key, ts, values = self._encode_request(row)
        feats = self.cs.online(self.store, key, ts, values,
                               preagg_states=self.pre_states
                               if self.use_preagg else None)
        self.n_requests += 1
        self.latencies_ms.append((time.perf_counter() - t0) * 1e3)
        return feats

    def request_batch(self, rows: Sequence[Dict[str, Any]],
                      snapshot: Optional[EngineSnapshot] = None
                      ) -> List[Dict[str, np.ndarray]]:
        """Features for B requests in one jitted call (batched driver).

        With ``snapshot=`` the batch is served from the frozen
        ``EngineSnapshot`` view instead of the live store/pre-agg state
        — the serving loop's double-buffered read path: concurrent
        ``ingest_many`` + compaction mutate the live store without
        stalling or dirtying this call.
        """
        if not rows:
            return []
        t0 = time.perf_counter()
        with span("serve.encode"):
            keys, ts, values = self._encode_batch(rows)
        store = self.store if snapshot is None else snapshot.store
        pre = (self.pre_states if snapshot is None
               else snapshot.pre_states)
        if self.sharded:
            feats = self.cs.online_sharded_batch(
                store, keys, ts, values,
                preagg_states=pre if self.use_preagg else None)
        elif self._serves_fused():
            # fused scripts serve batches through the megakernel fast
            # path (bitwise equal to online_batch; one unit_fold
            # dispatch per window group, warm executable per pad class)
            feats = self.cs.online_batch_fast(store, keys, ts, values)
        else:
            feats = self.cs.online_batch(
                store, keys, ts, values,
                preagg_states=pre if self.use_preagg else None)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.n_requests += len(rows)
        # every request in the batch completed when the batch call
        # returned: the batch wall time IS each one's real service
        # latency.  (The old amortized dt/B share was a throughput
        # figure masquerading as B latency samples — it understated
        # the percentiles by exactly the batching factor.)
        self.latencies_ms.extend([dt_ms] * len(rows))
        return [{k: v[i] for k, v in feats.items()}
                for i in range(len(rows))]

    def fused_serving_hlo(self, rows: Sequence[Dict[str, Any]]) -> str:
        """Compiled HLO text of the executable ``request_batch(rows)``
        runs on a ``fused_fold=True`` engine — where a compiled unit-fold
        kernel shows as a ``tpu_custom_call``."""
        if not self._serves_fused():
            raise ValueError("request_batch does not serve this engine "
                             "through the fused step")
        return self.cs.online_batch_fast_hlo(self.store,
                                             *self._encode_batch(rows))

    def _serves_fused(self) -> bool:
        return (not self.sharded and not self.use_preagg
                and getattr(self.cs.ctx, "fused_unit_fold", False))

    def snapshot(self) -> EngineSnapshot:
        """Cut an immutable view of (store, pre-agg states) for the
        double-buffered serving loop (O(#tables); no array copies)."""
        return EngineSnapshot(self)

    def submit_request(self, row: Dict[str, Any]) -> int:
        """Enqueue a request for batched execution; returns its id."""
        return self.batcher.submit(row)

    def flush(self) -> Dict[int, Dict[str, np.ndarray]]:
        """Drain the request queue through the batched path.

        Only real requests are handed to the batched driver (it pads
        internally for shape stability and slices the padding off), so
        latency samples, ``n_requests``, and pre-agg query stats count
        real traffic only.  Returns {request_id: features}.
        """
        out: Dict[int, Dict[str, np.ndarray]] = {}
        while self.batcher.queue:
            ids, payloads, n_real = self.batcher.next_batch()
            feats = self.request_batch(payloads[:n_real])
            for rid, f in zip(ids, feats):
                out[rid] = f
        return out

    # ---------------------------------------------------------- rebalance
    def rebalance(self) -> bool:
        """Hot-key rebalancing for the sharded engine: recompute the
        key->shard map from observed ingest load (greedy LPT over the
        ``LoadBalancer`` cost EMA) and migrate both resident store rows
        and per-shard pre-agg bucket planes to the new owners.  Returns
        True if any key moved.  Served results are unchanged — only the
        placement moves (tests/test_sharded_online.py asserts parity
        across a rebalance)."""
        if not self.sharded:
            return False
        store: ShardedOnlineStore = self.store
        n_keys = {wi: w.preagg.n_keys
                  for wi, w in enumerate(self.cs.windows)
                  if w.preagg is not None and self.use_preagg}
        old_owner = {wi: store.owner_of_keys(np.arange(nk))
                     for wi, nk in n_keys.items()}
        if not store.rebalance():
            return False
        if self.use_preagg and self.pre_states:
            for wi, w in enumerate(self.cs.windows):
                if w.preagg is None:
                    continue
                new_owner = store.owner_of_keys(np.arange(n_keys[wi]))
                self.pre_states[wi] = w.preagg.migrate_state_sharded(
                    self.pre_states[wi], old_owner[wi], new_owner)
            self.pre_states = self._place_pre(self.pre_states)
        if self.repl is not None:
            # ownership changed under shipped history: the binlog filter
            # and pre-agg masks now route differently, so followers are
            # re-seeded from the migrated leaders and the recovery
            # snapshot is re-cut — replay never crosses a rebalance
            self.repl.resync()
            self.checkpoint()
        return True

    # --------------------------------------------------------- replication
    def _require_replication(self):
        if self.repl is None:
            raise ValueError("engine was built without replication=R")

    def ship_replicas(self) -> int:
        """Ship the unacked binlog tail to every follower now (the
        ingest path does this every ``ship_every`` rows)."""
        self._require_replication()
        n = self.repl.ship()
        self.controller.beat()
        return n

    def checkpoint(self) -> int:
        """Cut a recovery snapshot at the current binlog offset: pre-agg
        planes in memory (always) and, with ``checkpoint_dir=``, the
        full stacked state via ``CheckpointManager`` (step == binlog
        watermark, so cold recovery = restore + replay the tail).
        Returns the watermark."""
        wm = self.store._binlog_offset
        pre = dict(self.pre_states) if self.pre_states is not None else None
        if self.repl is not None:
            self._snapshot = (wm, pre)
        if self.ckpt is not None:
            self.ckpt.save(wm, {"tables": dict(self.store.tables),
                                "pre": pre})
        return wm

    def kill_shard(self, shard: int) -> Dict[str, Any]:
        """Fault injection: shard ``shard`` dies — its resident rows and
        pre-agg bucket plane are lost (wiped), and the controller marks
        it dead.  Serving continues (the dead shard's keys read empty)
        until ``heal()`` promotes a follower.  Returns the replication
        lag at the moment of death (entries each follower was behind)."""
        self._require_replication()
        end = self.store._binlog_offset
        lag = {r: int(v) for r, v in enumerate(
            self.repl.log.lag(end)[shard])}
        self.store.wipe_shard(shard)
        if self.pre_states is not None:
            empty = self.cs.init_preagg_states_sharded(self.store.n_shards)
            for wi, w in enumerate(self.cs.windows):
                if w.preagg is None:
                    continue
                self.pre_states[wi] = w.preagg.restore_shard_plane(
                    self.pre_states[wi], empty[wi], shard)
        self.controller.mark_dead(shard)
        return {"shard": shard, "leader_offset": end,
                "lag_at_kill": lag}

    def heal(self) -> List[PromotionRecord]:
        """Fail over every dead shard: promote its most-caught-up
        follower (binlog tail replayed through the same ordered apply
        path) into the leader slot, and rebuild its pre-agg plane from
        the latest snapshot + binlog replay restricted to the shard.
        Serving afterwards is bitwise identical to a never-killed
        engine (tests/test_replication.py)."""
        self._require_replication()
        healed = []
        for shard in self.controller.dead_shards():
            t0 = time.perf_counter()
            rec = self.controller.failover(shard)
            if self.pre_states is not None:
                wm, snap = self._snapshot
                self.pre_states = recover_preagg_shard(
                    self.cs, self.pre_states, snap, wm, self.store,
                    shard, self._preagg_owned())
                self.pre_states = self._place_pre(self.pre_states)
            rec.recovery_s = time.perf_counter() - t0   # incl. pre-agg
            healed.append(rec)
        self.failovers.extend(healed)
        return healed

    def replication_stats(self) -> Dict[str, Any]:
        """Lag/recovery observability for dashboards and benchmarks."""
        if self.repl is None:
            return {"n_replicas": 0}
        st = self.repl.stats()
        st["snapshot_watermark"] = self._snapshot[0]
        st["dead_shards"] = self.controller.dead_shards()
        st["failovers"] = [dataclasses.asdict(r) for r in self.failovers]
        return st

    def _preagg_owned(self):
        """Per-window ownership masks, cached against the store's
        assignment version (masks only change on rebalance — rebuilding
        the one-hot per ingest would tax the hot write path)."""
        ver = self.store.n_rebalances
        cached = getattr(self, "_owned_cache", None)
        if cached is None or cached[0] != ver:
            masks = self.cs.preagg_owned_masks(self.store.owner_of_keys,
                                               self.store.n_shards)
            cached = (ver, masks)
            self._owned_cache = cached
        return cached[1]

    def _place_pre(self, pre_states):
        """Co-locate stacked pre-agg planes with their store shards."""
        if self.store.mesh is None:
            return pre_states
        from ..distributed.sharding import stacked_store_sharding

        sh = stacked_store_sharding(self.store.mesh, self.store.axis)
        return jax.device_put(pre_states, sh)

    # ------------------------------------------------------------ helpers
    def _key_col(self) -> str:
        if self.key_col is None:
            raise ValueError("script has no window partition column; "
                             "store ingest needs a key")
        return self.key_col

    def _encode_request(self, row: Dict[str, Any]):
        base = self.cs.script.base_table
        key = self._encode(base, self._key_col(), row[self._key_col()])
        ts = int(row[self.cs.script.order_column])
        values = {c: float(self._encode(base, c, row[c]))
                  for c in self._need[base]}
        return key, ts, values

    def _encode_batch(self, rows: Sequence[Dict[str, Any]]):
        enc = [self._encode_request(r) for r in rows]
        values = {c: [e[2][c] for e in enc]
                  for c in self._need[self.cs.script.base_table]}
        return [e[0] for e in enc], [e[1] for e in enc], values

    def _encode(self, table: str, col: str, v):
        d = self.dicts.get(table, {}).get(col)
        if d is not None and isinstance(v, str):
            return d.encode(v)
        return v

    def latency_percentiles(self) -> Dict[str, float]:
        """Percentiles over REQUEST completion samples only ({} when no
        requests have been served — never a fabricated zero row)."""
        if not self.latencies_ms:
            return {}
        arr = np.asarray(self.latencies_ms)
        return {f"TP{p}": float(np.percentile(arr, p))
                for p in (50, 90, 95, 99)}

    def ingest_stats(self) -> Dict[str, float]:
        """Write-path timing, tracked apart from request latencies:
        per-``ingest``/``ingest_many`` call wall times + total rows."""
        if not self.ingest_ms:
            return {}
        arr = np.asarray(self.ingest_ms)
        return {"rows": float(self.rows_ingested),
                "calls": float(arr.size),
                "TP50": float(np.percentile(arr, 50)),
                "TP99": float(np.percentile(arr, 99)),
                "max_ms": float(arr.max())}

    def reset_stats(self):
        """Drop warmup (compile) samples before measuring percentiles."""
        self.latencies_ms.clear()
        self.ingest_ms.clear()
        self.rows_ingested = 0
        self.n_requests = 0

    # ------------------------------------------------------------- offline
    def offline(self, tables: Optional[Dict[str, Table]] = None
                ) -> Dict[str, np.ndarray]:
        """Offline (training-set) feature materialization for this
        deployment's script.

        A sharded engine reuses its serving mesh for the offline batch:
        the same key-partitioned, skew-aware schedule that fans requests
        out (``CompiledScript.offline_sharded``) folds the historical
        tables, so training features are computed by the same executors
        that will serve them — and bit-exactly equal to the
        single-device ``offline`` either way."""
        tables = tables or self.tables
        if self.sharded:
            return self.cs.offline_sharded(tables, mesh=self.store.mesh,
                                           n_shards=self.store.n_shards,
                                           axis=getattr(self.store, "axis",
                                                        "shard"))
        return self.cs.offline(tables)

    def bulk_load(self, table: str, rows_table: Table):
        """LOAD DATA: ingest a whole historical table at once.

        A sharded engine routes the rows to their owning shards with one
        vmapped sort-merge and folds per-shard pre-agg planes under the
        same ownership masks the serving path reads — the write-side
        counterpart of ``offline``'s mesh reuse.  Pre-agg bucket states
        fold the loaded rows too (one ``update_many`` / sharded scatter)
        — otherwise a ``use_preagg`` engine would serve long-window
        queries from empty bucket planes over its bulk-loaded history."""
        cols = {c: rows_table.columns[c].astype(np.float32)
                for c in self._need[table]}
        keys_arr = rows_table.columns[self._key_col()]
        ts_arr = rows_table.columns[self.cs.script.order_column]
        self.store.bulk_load(table, keys_arr, ts_arr, cols)
        # loaded rows must be charged like ingested ones — the
        # retention pass credits the guard per evicted row, and an
        # uncharged bulk row would debit bytes some ingested row paid
        self.guard.charge(len(rows_table) * (64 + 8 * len(cols)))
        if self.use_preagg:
            keys_np = np.asarray(keys_arr, np.int32)
            ts_np = np.asarray(ts_arr, np.int32)
            if self.sharded:
                self.pre_states = self.cs.preagg_update_many_sharded(
                    self.pre_states, table, keys_np, ts_np, cols,
                    self._preagg_owned())
            else:
                self.pre_states = self.cs.preagg_update_many(
                    self.pre_states, table, keys_np, ts_np, cols)
        if len(ts_arr):
            # advance the high-watermark/consumed offset without a
            # pending-row tick (a load is one-shot, not stream traffic)
            self._after_ingest(table, 0, int(np.max(ts_arr)))
        if self.repl is not None:
            # a load is a snapshot barrier: it overwrites store state
            # (replaying the full binlog would resurrect pre-load rows)
            # and its binlog entries are written in sorted — not
            # arrival — order, so pre-agg replay must never cross it.
            # Followers are re-seeded from the loaded leaders and the
            # recovery watermark moves past the load.
            self.repl.resync()
            self.checkpoint()


def _parse(sql, time_unit):
    from ..core.sql import parse

    return parse(sql, time_unit=time_unit)


class ServingEngine:
    """Model serving: prefill once, then batched decode steps."""

    def __init__(self, cfg, params, max_len: int = 2048,
                 dtype=jnp.bfloat16):
        from ..models import decode_step, forward_prefill, \
            init_decode_state

        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, b: forward_prefill(cfg, p, b,
                                         cache_capacity=max_len))
        self._decode = jax.jit(
            lambda p, s, t: decode_step(cfg, p, s, t))
        self._init_state = lambda b: init_decode_state(cfg, b, max_len,
                                                       dtype=dtype)
        self.state = None

    def prefill(self, batch) -> np.ndarray:
        logits, state = self._prefill(self.params, batch)
        # pad the cache to max_len capacity happens inside forward_prefill
        self.state = state
        return np.asarray(logits)

    def decode(self, tokens: np.ndarray) -> np.ndarray:
        logits, self.state = self._decode(self.params, self.state,
                                          jnp.asarray(tokens, jnp.int32))
        return np.asarray(logits)

    def generate_greedy(self, batch, n_tokens: int) -> np.ndarray:
        logits = self.prefill(batch)
        out = []
        tok = np.argmax(logits, axis=-1)[:, None].astype(np.int32)
        for _ in range(n_tokens):
            out.append(tok)
            logits = self.decode(tok)
            tok = np.argmax(logits, axis=-1)[:, None].astype(np.int32)
        return np.concatenate(out, axis=1)
