"""Online time-series store — TPU-native adaptation of the refined skiplist.

The paper's §7.2 structure is a two-level skiplist: level 1 sorted by key,
level 2 per-key linked lists sorted by timestamp, with lock-free CAS
inserts and batch TTL eviction.  Pointer-chasing has no TPU analogue, so we
keep the *invariant* (data pre-ranked by (key, ts) so online access is a
seek + contiguous scan) in a dense representation:

    keys : (capacity,) int32   sorted ascending; padding = INT32_MAX
    ts   : (capacity,) int32   sorted within each key run; padding = MAX
    cols : {name: (capacity,) float32/int32}
    count: ()        int32     live rows

All operations are pure jax (jit-able, static shapes):

  * ``insert``       O(capacity) vectorized shift (a write is a roll of the
                     suffix — fully parallel on a vector unit, unlike a CAS
                     chain, and single-writer per shard matches the paper's
                     replicator-lock serialization anyway),
  * ``insert_many``  a sorted M-row batch merged by M binary searches and
                     a few dozen streaming passes of static shifts: no
                     gather, sort or loop spans the store,
  * ``range_bounds`` O(log capacity) via branchless binary search,
  * ``evict_before`` batch TTL deletion (§7.2): drop every row with
                     ts < horizon in one compaction pass,
  * a host-side ``binlog`` (insert sequence numbers) drives asynchronous
    pre-aggregation updates exactly like the paper's
    ``replicator->AppendEntry`` (§5.1 Aggregator Update).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import vary_like
from ..spans import span

INT_MAX = np.int32(2**31 - 1)

__all__ = ["StoreState", "OnlineStore", "ShardedOnlineStore",
           "StoreSnapshot", "insert", "insert_many",
           "insert_many_stacked", "range_bounds", "evict_before",
           "gather_window", "gather_key_unit", "next_pow2"]


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): batch-size padding that keeps
    jit recompiles logarithmic in batch size."""
    return 1 << max(0, (n - 1).bit_length())

# StoreState is a plain pytree: dict with fixed structure.
StoreState = Dict


def make_state(capacity: int, col_specs: Dict[str, jnp.dtype]) -> StoreState:
    return {
        "keys": jnp.full((capacity,), INT_MAX, jnp.int32),
        "ts": jnp.full((capacity,), INT_MAX, jnp.int32),
        "cols": {name: jnp.zeros((capacity,), dtype)
                 for name, dtype in col_specs.items()},
        "count": jnp.zeros((), jnp.int32),
    }


def _bsearch(keys: jnp.ndarray, tss: jnp.ndarray, key, ts,
             strict: bool, unroll: bool = False) -> jnp.ndarray:
    """Branchless binary search over the (key, ts)-sorted store:
    first index i with (keys[i], ts[i]) > (key, ts)   [strict=True]
    or >= (key, ts)                                    [strict=False].
    O(log capacity) scalar gathers — the dense-array analogue of the
    skiplist seek (§7.2): pre-ranked data makes access logarithmic,
    never a scan."""
    n = keys.shape[0]
    steps = max(1, (n - 1).bit_length() + 1)
    lo = vary_like(jnp.int32(0), keys, tss, key, ts)
    hi = vary_like(jnp.int32(n), keys, tss, key, ts)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) // 2
        m = jnp.clip(mid, 0, n - 1)
        k_m = keys[m]
        t_m = tss[m]
        if strict:
            gt = (k_m > key) | ((k_m == key) & (t_m > ts))
        else:
            gt = (k_m > key) | ((k_m == key) & (t_m >= ts))
        go_left = gt & (lo_ < hi_)
        hi_ = jnp.where(go_left, mid, hi_)
        lo_ = jnp.where(go_left | (lo_ >= hi_), lo_, mid + 1)
        return lo_, hi_

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi), unroll=unroll)
    return lo.astype(jnp.int32)


def insert_pos(state: StoreState, key, ts) -> jnp.ndarray:
    """First index i with (keys[i], ts[i]) > (key, ts): insert *after*
    peers, preserving arrival order among equal timestamps (this is what
    makes online replay bitwise-match the offline stable sort).  Padding
    rows (INT_MAX keys) always compare "after"."""
    pos = _bsearch(state["keys"], state["ts"], key, ts, strict=True)
    return jnp.minimum(pos, state["count"])


@jax.jit
def insert(state: StoreState, key, ts, values: Dict[str, jnp.ndarray]
           ) -> StoreState:
    """Sorted insert of one row (vectorized suffix shift)."""
    pos = insert_pos(state, key, ts)
    idx = jnp.arange(state["keys"].shape[0], dtype=jnp.int32)

    def shifted(arr, new_val):
        prev = jnp.roll(arr, 1)
        out = jnp.where(idx > pos, prev, arr)
        return jnp.where(idx == pos, jnp.asarray(new_val, arr.dtype), out)

    new_cols = {}
    for name, arr in state["cols"].items():
        new_cols[name] = shifted(arr, values.get(name, 0))
    return {
        "keys": shifted(state["keys"], key),
        "ts": shifted(state["ts"], ts),
        "cols": new_cols,
        "count": state["count"] + 1,
    }


def _shift(x: jnp.ndarray, s: int) -> jnp.ndarray:
    """``x`` moved ``s`` slots toward its end: the first ``s`` slots read
    zero and the last ``s`` values fall off (a static pad, no gather)."""
    n = x.shape[0]
    if s >= n:
        return jnp.zeros_like(x)
    return jax.lax.pad(x, jnp.zeros((), x.dtype), [(s, -s, 0)])


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of an int32 array in log2(n) passes of a
    static shift and an add.  ``jnp.cumsum`` is exact too, but a 2**20-row
    one takes ~20 s to compile for a TPU."""
    s = 1
    while s < x.shape[0]:
        x = x + _shift(x, s)
        s *= 2
    return x


@jax.jit
def insert_many(state: StoreState, keys, ts, values: Dict[str, jnp.ndarray],
                n_new) -> StoreState:
    """Sorted insert of a padded batch of rows with ONE merge.

    ``keys``/``ts`` are (M,) int32 with padding rows carrying INT_MAX in
    both; ``n_new`` is the number of real rows.  Only the batch is
    sorted; it is merged into the already-sorted store without a sort,
    a gather or a loop over the store — the bulk-ingest analogue of the
    skiplist's batch build:

    1. M binary searches (O(M log capacity)) give ``at[r]``, the first
       old row after sorted new row r;
    2. old row j moves by ``c[j] = #{r : at[r] <= j}``: M ones
       scatter-added at ``at``, then a prefix sum of log2(capacity)
       static shifts;
    3. the old rows move by ``c`` in bit_length(M) rounds, highest bit
       first: round b shifts every array by the static ``2**b`` and
       selects.  ``c`` never decreases, so ``c`` with its bits below b
       cleared keeps positions strictly increasing: no two rows meet;
    4. the M new rows are scattered to ``at[r] + r``, the slots the old
       rows left.

    So an apply costs O(M log capacity) plus about log2(capacity) +
    bit_length(M) streaming passes over the store.  No sort spans the
    store: XLA's TPU sort takes minutes to compile at 2**20 rows, and
    every follower device compiles its own copy.

    Ordering matches M sequential ``insert`` calls: new rows land *after*
    existing peers with equal (key, ts), and arrival order among the new
    rows themselves is preserved.  Rows merged beyond ``capacity`` are
    dropped — the host wrapper guarantees they are padding only.
    """
    cap = state["keys"].shape[0]
    m = keys.shape[0]
    order = jnp.arange(m, dtype=jnp.int32)
    k_new, t_new, order = jax.lax.sort(
        (jnp.asarray(keys, jnp.int32), jnp.asarray(ts, jnp.int32), order),
        num_keys=3)
    at = jax.vmap(lambda k, t: _bsearch(state["keys"], state["ts"], k, t,
                                        strict=True, unroll=True))(k_new,
                                                                   t_new)
    # padding rows search to ``cap``: they add no shift and are dropped
    shift = _prefix_sum(jnp.zeros((cap,), jnp.int32).at[at].add(
        1, mode="drop", indices_are_sorted=True))
    old = [state["keys"], state["ts"], *state["cols"].values()]
    for b in reversed(range(m.bit_length())):
        s = 1 << b
        moved = _shift(shift, s)
        arrive = (moved & s) != 0
        old = [jnp.where(arrive, _shift(x, s), x) for x in old]
        # a slot whose row left and that nothing reached is a hole for a
        # new row: zero its shift so the stale copy never moves again
        shift = jnp.where(arrive, moved, jnp.where((shift & s) != 0, 0,
                                                   shift))
    dest = at + jnp.arange(m, dtype=jnp.int32)

    def place(arr, new):
        return arr.at[dest].set(new, mode="drop", indices_are_sorted=True,
                                unique_indices=True)

    keys_out, ts_out, *cols_out = old
    new_cols = {}
    for (name, arr), merged in zip(state["cols"].items(), cols_out):
        v = jnp.asarray(values.get(name, jnp.zeros((m,), arr.dtype)),
                        arr.dtype)
        new_cols[name] = place(merged, v[order])
    return {
        "keys": place(keys_out, k_new),
        "ts": place(ts_out, t_new),
        "cols": new_cols,
        "count": state["count"] + jnp.asarray(n_new, jnp.int32),
    }


@jax.jit
def insert_many_stacked(states: StoreState, keys, ts,
                        values: Dict[str, jnp.ndarray], n_new) -> StoreState:
    """``insert_many`` vmapped over a leading shard dim.

    ``states`` leaves carry shape (n_shards, capacity, ...); ``keys``/``ts``
    are (n_shards, M) blocks whose non-owned slots hold INT_MAX padding and
    ``n_new`` is the per-shard real-row count.  Every op is elementwise
    along the shard dim, so under a sharded-in/sharded-out jit the merge
    stays local to each shard's device (no cross-shard traffic).
    """
    return jax.vmap(insert_many)(states, keys, ts, values, n_new)


@jax.jit
def evict_before_stacked(states: StoreState, horizon_ts) -> StoreState:
    return jax.vmap(evict_before, in_axes=(0, None))(states, horizon_ts)


def range_bounds(state: StoreState, key, t0, t1) -> Tuple[jnp.ndarray,
                                                          jnp.ndarray]:
    """[lo, hi) of rows with keys==key and ts in [t0, t1] (peers at t1
    included — matches the position-based offline semantics when the
    querying row is about to be inserted after its peers).  Two binary
    searches: O(log capacity), independent of table size."""
    keys, tss = state["keys"], state["ts"]
    n = state["count"]
    lo = jnp.minimum(_bsearch(keys, tss, key, t0, strict=False), n)
    hi = jnp.minimum(_bsearch(keys, tss, key, t1, strict=True), n)
    lo = jnp.minimum(lo, hi)
    return lo, hi


@jax.jit
def evict_before(state: StoreState, horizon_ts) -> StoreState:
    """Batch TTL eviction (§7.2): remove all rows with ts < horizon.

    Dense-array equivalent of the skiplist's contiguous-head deletion:
    one stable compaction (keep-mask prefix sum + scatter).
    """
    keys, tss = state["keys"], state["ts"]
    cap = keys.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = idx < state["count"]
    keep = live & (tss >= horizon_ts)
    dest = jnp.cumsum(keep.astype(jnp.int32)) - 1
    # out-of-bounds destinations are dropped by the scatter
    scatter_to = jnp.where(keep, dest, cap)

    def compact(arr, fill):
        out = jnp.full_like(arr, fill)
        return out.at[scatter_to].set(arr, mode="drop")

    new_cols = {k: compact(v, 0) for k, v in state["cols"].items()}
    return {
        "keys": compact(keys, INT_MAX),
        "ts": compact(tss, INT_MAX),
        "cols": new_cols,
        "count": jnp.sum(keep.astype(jnp.int32)),
    }


def gather_key_unit(state: StoreState, key, ts, max_rows: int,
                    col_names: List[str]
                    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray,
                               jnp.ndarray]:
    """Unit-layout adapter: one key's WHOLE history up to ``ts``.

    Gathers the newest ``max_rows`` rows of ``key`` with timestamps <=
    ``ts`` (peers at ``ts`` included — the querying request inserts
    after its peers) into the fixed (cols, ts, valid) buffers the unit
    fold core consumes.  The gather anchors at the key segment's FIRST
    row, not the window start: that is what makes the online request
    fold replay the offline unit fold bitwise (same rows, same unit
    positions, same prefix-scan anchor).  When a key's history exceeds
    ``max_rows`` the oldest context rows are dropped — window semantics
    survive as long as the window rows fit, but float equality vs the
    offline fold degrades to reduction-order tolerance.
    """
    lo, hi = range_bounds(state, key, jnp.int32(-2**31), ts)
    return gather_window(state, lo, hi, max_rows, col_names)


def gather_window(state: StoreState, lo: jnp.ndarray, hi: jnp.ndarray,
                  max_rows: int, col_names: List[str]
                  ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray,
                             jnp.ndarray]:
    """Gather the newest ``max_rows`` rows of [lo, hi) into fixed buffers.

    Returns (cols, ts, valid).  Rows are in time order; if the range holds
    more than ``max_rows`` rows only the most recent are kept (the same
    truncation MAXSIZE applies to windows).
    """
    start = jnp.maximum(lo, hi - max_rows)
    base = jnp.arange(max_rows, dtype=jnp.int32)
    idx = start + base
    valid = idx < hi
    safe = jnp.clip(idx, 0, state["keys"].shape[0] - 1)
    cols = {c: jnp.take(state["cols"][c], safe, axis=0)
            for c in col_names}
    ts = jnp.take(state["ts"], safe, axis=0)
    return cols, ts, valid


class StoreSnapshot:
    """Immutable point-in-time read view of a store — the snapshot half
    of the serving loop's double buffer (``serve.loop.ServeLoop``).

    Cutting a snapshot is O(#tables): every ``StoreState`` leaf is an
    immutable jnp array and every store mutation *replaces* whole table
    entries (``self.tables[t] = insert(...)``) instead of writing in
    place, so a shallow copy of the ``tables`` dict IS a consistent
    frozen view — no array is ever copied.  The sharded routing state
    (``assignment``) is frozen with it so a concurrent ``rebalance()``
    cannot desynchronize a snapshot's routing from its resident rows.

    The view quacks like the store for the READ surface the online
    drivers touch (``tables``, ``capacity``, and for sharded stores
    ``n_shards``/``mesh``/``axis``/``owner_of_keys``), so
    ``CompiledScript.online_batch`` / ``online_sharded_batch`` run
    against it unchanged — including their two-level jitted-fn cache,
    which keys on the view's (stable) identity.

    ``refresh()`` re-cuts from the live store *in place*: a single
    attribute rebind per field, so readers in the serving loop see
    either the old frozen view or the new one, never a mix — the atomic
    swap that lets ``ingest_many`` + compaction + replication shipping
    proceed on the live store without stalling (or dirtying) in-flight
    requests.
    """

    def __init__(self, store):
        self._store = store
        self.capacity = store.capacity
        self.col_specs = store.col_specs
        self.sharded = isinstance(store, ShardedOnlineStore)
        if self.sharded:
            self.n_shards = store.n_shards
            self.mesh = store.mesh
            self.axis = store.axis
            self.n_route_slots = store.n_route_slots
        self.version = -1
        self.refresh()

    def refresh(self) -> int:
        """Atomically re-cut the view from the live store; returns the
        new snapshot version."""
        store = self._store
        self.tables = dict(store.tables)
        if self.sharded:
            self.assignment = store.assignment.copy()
        self.version += 1
        return self.version

    # ------------------------------------------------ read-only surface
    def route_slots(self, keys) -> np.ndarray:
        from ..core.hll import splitmix64

        k = np.atleast_1d(np.asarray(keys)).astype(np.uint64)
        return (splitmix64(k) % np.uint64(self.n_route_slots)).astype(
            np.int64)

    def owner_of_keys(self, keys) -> np.ndarray:
        """Key -> owning shard under the FROZEN assignment."""
        return self.assignment[self.route_slots(keys)].astype(np.int64)

    def n_rows_per_shard(self, table: str) -> np.ndarray:
        return np.asarray(self.tables[table]["count"])

    def n_rows(self, table: str) -> int:
        return int(np.sum(np.asarray(self.tables[table]["count"])))


class _BinlogMixin:
    """Bounded binlog shared by both stores.

    Offsets are STABLE across truncation: ``self.binlog`` holds entries
    [``_binlog_base``, ``_binlog_offset``) and ``read_binlog`` addresses
    by absolute offset.  ``truncate_binlog`` drops entries below a
    consumer low-watermark (the pre-aggregation consumed offset — see
    ``serve.engine.FeatureEngine``) so a long-lived store's log stays
    bounded instead of growing with total ingest.
    """

    def read_binlog(self, from_offset: int):
        if from_offset < self._binlog_base:
            raise ValueError(
                f"binlog offset {from_offset} was truncated (log now "
                f"starts at {self._binlog_base}); consumers must keep "
                f"their read offset at or above the truncation "
                f"low-watermark")
        return (self.binlog[from_offset - self._binlog_base:],
                self._binlog_offset)

    def truncate_binlog(self, below_offset: int) -> int:
        """Drop binlog entries below ``below_offset`` (clamped to the
        written end).  Returns the number of entries dropped.  Offsets
        of the surviving entries are unchanged."""
        upto = min(int(below_offset), self._binlog_offset)
        drop = upto - self._binlog_base
        if drop <= 0:
            return 0
        del self.binlog[:drop]
        self._binlog_base = upto
        return drop


class OnlineStore(_BinlogMixin):
    """Host-facing wrapper: one StoreState per table + a binlog.

    The binlog (monotone offsets, host side) decouples pre-aggregation
    updates from the insert path, mirroring §5.1's asynchronous
    ``update_aggr`` closures: consumers (PreAggregator) read the log tail
    and fold new rows into their buckets.  Consumed entries are dropped
    by ``truncate_binlog`` (offsets stay stable).
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.tables: Dict[str, StoreState] = {}
        self.col_specs: Dict[str, Dict[str, jnp.dtype]] = {}
        self.binlog: List[Tuple[str, int, int, Dict[str, float]]] = []
        self._binlog_offset = 0
        self._binlog_base = 0

    def create_table(self, name: str, col_specs: Dict[str, jnp.dtype]):
        self.tables[name] = make_state(self.capacity, col_specs)
        self.col_specs[name] = dict(col_specs)

    def bulk_load(self, table: str, keys, ts, cols: Dict[str, "np.ndarray"]
                  ) -> int:
        """LOAD DATA path: sort once by (key, ts, arrival) and overwrite
        the table state (paper Figure 3's offline->online sync)."""
        keys = np.asarray(keys, np.int32)
        ts = np.asarray(ts, np.int32)
        n = keys.shape[0]
        if n > self.capacity:
            raise ValueError(f"bulk load of {n} rows exceeds capacity "
                             f"{self.capacity}")
        order = np.lexsort((np.arange(n), ts, keys))
        st = make_state(self.capacity, self.col_specs[table])
        st["keys"] = st["keys"].at[:n].set(jnp.asarray(keys[order]))
        st["ts"] = st["ts"].at[:n].set(jnp.asarray(ts[order]))
        for name in st["cols"]:
            arr = np.asarray(cols[name])[order]
            st["cols"][name] = st["cols"][name].at[:n].set(
                jnp.asarray(arr, st["cols"][name].dtype))
        st["count"] = jnp.asarray(n, jnp.int32)
        self.tables[table] = st
        ko = keys[order].tolist()
        tso = ts[order].tolist()
        # entries carry the column values: the binlog must be a FULL
        # record of every row so a replica/recovery replay of the log
        # rebuilds the state bitwise (storage.replication)
        co = {c: np.asarray(cols[c])[order].tolist() for c in cols}
        self.binlog.extend(
            (table, ko[i], tso[i], {c: float(co[c][i]) for c in co})
            for i in range(n))
        self._binlog_offset += n
        return n

    def put(self, table: str, key: int, ts: int,
            values: Dict[str, float]) -> int:
        """Insert + append to binlog; returns the binlog offset."""
        st = self.tables[table]
        self.tables[table] = insert(st, jnp.int32(key), jnp.int32(ts),
                                    {k: jnp.asarray(v) for k, v in
                                     values.items()})
        off = self._binlog_offset
        self.binlog.append((table, int(key), int(ts), dict(values)))
        self._binlog_offset += 1
        return off

    def put_many(self, table: str, keys, ts,
                 cols: Dict[str, "np.ndarray"]) -> int:
        """Bulk insert of N rows with one sort-merge (vs N O(capacity)
        shifts for sequential ``put``); returns the first binlog offset.

        Equivalent to ``put``-ing the rows in order: rows are appended to
        the binlog in arrival order and land after existing (key, ts)
        peers in the store.  Batches are padded to the next power of two
        so jit recompiles stay logarithmic in batch size.
        """
        keys = np.asarray(keys, np.int32)
        ts = np.asarray(ts, np.int32)
        n = keys.shape[0]
        if n == 0:
            return self._binlog_offset
        # the live row count waits for the merges dispatched before it
        with span("ingest.wait"):
            live = self.n_rows(table)
        if live + n > self.capacity:
            raise ValueError(f"bulk put of {n} rows overflows capacity "
                             f"{self.capacity}")
        m = next_pow2(n)
        k_pad = np.full((m,), INT_MAX, np.int32)
        t_pad = np.full((m,), INT_MAX, np.int32)
        k_pad[:n] = keys
        t_pad[:n] = ts
        specs = self.col_specs[table]
        vals = {}
        for name, dtype in specs.items():
            v = np.zeros((m,), dtype)
            if name in cols:
                v[:n] = np.asarray(cols[name], dtype)
            vals[name] = jnp.asarray(v)
        self.tables[table] = insert_many(
            self.tables[table], jnp.asarray(k_pad), jnp.asarray(t_pad),
            vals, n)
        off = self._binlog_offset
        kl, tl = keys.tolist(), ts.tolist()
        self.binlog.extend(
            (table, kl[i], tl[i],
             {c: float(cols[c][i]) for c in cols}) for i in range(n))
        self._binlog_offset += n
        return off

    def evict(self, table: str, horizon_ts: int):
        """Batch TTL eviction + slot compaction (one pass, §7.2)."""
        self.tables[table] = evict_before(self.tables[table],
                                          jnp.int32(horizon_ts))

    def n_rows(self, table: str) -> int:
        return int(self.tables[table]["count"])

    def snapshot(self) -> StoreSnapshot:
        """Cut an immutable point-in-time read view (O(#tables))."""
        return StoreSnapshot(self)


class ShardedOnlineStore(_BinlogMixin):
    """Key-sharded online store: the paper's tablet partitioning (§5, §7.2)
    mapped onto a ``jax.sharding.Mesh`` axis.

    Layout: every per-table ``StoreState`` leaf gains a leading shard dim —
    ``keys: (n_shards, capacity)`` etc. — and *all rows of a given
    partition key live on exactly one shard*, so window folds over a key
    never cross shards (the locality invariant the paper's key-partitioned
    workers rely on; arXiv:2305.20077 makes the same argument at
    datacenter scale).  With ``mesh`` given, the stacked pytree is placed
    one-shard-per-device and the query path runs under ``shard_map``
    (``CompiledScript.online_sharded_batch``); with ``mesh=None`` the same
    stacked layout runs as a vmap over logical shards on one device —
    bit-identical results either way.

    Routing: key -> route slot (splitmix64 hash mod ``n_route_slots``) ->
    shard (host-side assignment table).  The table starts as the static
    hash baseline and is recomputed from observed per-slot load by
    ``core.union.LoadBalancer`` greedy LPT on ``rebalance()``, which also
    migrates resident rows to their new owners.  Keys are always moved
    *whole* (LoadBalancer's hot-key splitting is not used here: splitting
    one key's rows across shards would break the ordered-fold locality
    that makes sharded results bit-exact).

    ``capacity`` is PER SHARD: total resident rows = n_shards * capacity,
    and a skewed key distribution needs per-shard headroom.

    Replication (``storage.replication``): slot s of the stacked layout
    is shard s's LEADER; ``shard_state``/``install_shard``/``wipe_shard``
    expose the per-shard slices follower replicas are seeded from and
    promoted into, and the binlog (every entry carries table, key, ts
    AND values) is the shipping stream that keeps followers bitwise
    convergent with their leader.
    """

    def __init__(self, capacity: int, n_shards: Optional[int] = None,
                 mesh=None, axis: str = "shard",
                 n_route_slots: int = 1024):
        from ..core.union import LoadBalancer

        if mesh is not None:
            if axis not in mesh.shape:
                raise ValueError(f"mesh has no axis {axis!r}")
            mesh_n = mesh.shape[axis]
            if n_shards is not None and n_shards != mesh_n:
                raise ValueError(f"n_shards={n_shards} != mesh axis "
                                 f"{axis!r} size {mesh_n}")
            n_shards = mesh_n
        if not n_shards or n_shards < 1:
            raise ValueError("need n_shards >= 1 or a mesh")
        self.capacity = capacity
        self.n_shards = int(n_shards)
        self.mesh = mesh
        self.axis = axis
        self.n_route_slots = n_route_slots
        # split_threshold=inf: hot-slot splitting must stay OFF so LPT's
        # load accounting matches the whole-key placement rebalance()
        # actually performs (see class docstring)
        self.balancer = LoadBalancer(n_route_slots, self.n_shards,
                                     split_threshold=float("inf"))
        self.assignment = self.balancer.assignment.copy()
        self._slot_counts = np.zeros(n_route_slots, np.float64)
        self.tables: Dict[str, StoreState] = {}
        self.col_specs: Dict[str, Dict[str, jnp.dtype]] = {}
        self.binlog: List[Tuple[str, int, int, Dict[str, float]]] = []
        self._binlog_offset = 0
        self._binlog_base = 0
        self.n_rebalances = 0

    # ----------------------------------------------------------- routing
    def route_slots(self, keys) -> np.ndarray:
        """Key -> route slot (hash-bounded key universe for balancing)."""
        from ..core.hll import splitmix64

        k = np.atleast_1d(np.asarray(keys)).astype(np.uint64)
        return (splitmix64(k) % np.uint64(self.n_route_slots)).astype(
            np.int64)

    def owner_of_keys(self, keys) -> np.ndarray:
        """Key -> owning shard under the current assignment."""
        return self.assignment[self.route_slots(keys)].astype(np.int64)

    # ------------------------------------------------------------ tables
    def _place(self, state: StoreState) -> StoreState:
        if self.mesh is None:
            return state
        from ..distributed.sharding import stacked_store_sharding

        return jax.device_put(state,
                              stacked_store_sharding(self.mesh, self.axis))

    def create_table(self, name: str, col_specs: Dict[str, jnp.dtype]):
        base = make_state(self.capacity, col_specs)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (self.n_shards,) + x.shape),
            base)
        self.tables[name] = self._place(stacked)
        self.col_specs[name] = dict(col_specs)

    def n_rows_per_shard(self, table: str) -> np.ndarray:
        return np.asarray(self.tables[table]["count"])

    def n_rows(self, table: str) -> int:
        return int(self.n_rows_per_shard(table).sum())

    # ------------------------------------------------------------ ingest
    def put(self, table: str, key: int, ts: int,
            values: Dict[str, float]) -> int:
        """Single-row insert: a 1-row ``put_many`` (same routing path)."""
        cols = {c: np.asarray([v], np.float32) for c, v in values.items()}
        return self.put_many(table, np.asarray([key], np.int32),
                             np.asarray([ts], np.int32), cols)

    def put_many(self, table: str, keys, ts,
                 cols: Dict[str, "np.ndarray"]) -> int:
        """Bulk insert routed by key: rows are grouped per owning shard
        (arrival order preserved within a shard) and merged with ONE
        vmapped sort-merge across all shards (``insert_many_stacked``).
        Non-owned slots of each shard's block carry INT_MAX padding, so
        they sort into the dead tail exactly like capacity padding.
        """
        keys = np.asarray(keys, np.int32)
        ts = np.asarray(ts, np.int32)
        n = keys.shape[0]
        if n == 0:
            return self._binlog_offset
        slots = self.route_slots(keys)
        owner = self.assignment[slots]
        counts = np.bincount(owner, minlength=self.n_shards)
        # the live row counts wait for the merges dispatched before them
        with span("ingest.wait"):
            live = self.n_rows_per_shard(table)
        over = np.flatnonzero(live + counts > self.capacity)
        if over.size:
            s = int(over[0])
            raise ValueError(
                f"bulk put overflows shard {s}: {int(live[s])} live + "
                f"{int(counts[s])} new > per-shard capacity "
                f"{self.capacity}")
        m = next_pow2(int(max(1, counts.max())))
        k_blk = np.full((self.n_shards, m), INT_MAX, np.int32)
        t_blk = np.full((self.n_shards, m), INT_MAX, np.int32)
        pos = np.empty(n, np.int64)
        for s in range(self.n_shards):
            sel = np.flatnonzero(owner == s)
            pos[sel] = np.arange(sel.size)
        k_blk[owner, pos] = keys
        t_blk[owner, pos] = ts
        vals = {}
        for name, dtype in self.col_specs[table].items():
            v = np.zeros((self.n_shards, m), dtype)
            if name in cols:
                v[owner, pos] = np.asarray(cols[name], dtype)
            vals[name] = jnp.asarray(v)
        self.tables[table] = insert_many_stacked(
            self.tables[table], jnp.asarray(k_blk), jnp.asarray(t_blk),
            vals, jnp.asarray(counts, jnp.int32))
        self._slot_counts += np.bincount(slots,
                                         minlength=self.n_route_slots)
        off = self._binlog_offset
        kl, tl = keys.tolist(), ts.tolist()
        self.binlog.extend(
            (table, kl[i], tl[i],
             {c: float(cols[c][i]) for c in cols}) for i in range(n))
        self._binlog_offset += n
        return off

    def bulk_load(self, table: str, keys, ts, cols: Dict[str, "np.ndarray"]
                  ) -> int:
        """LOAD DATA: route once, sort each shard once, overwrite."""
        keys = np.asarray(keys, np.int32)
        ts = np.asarray(ts, np.int32)
        n = keys.shape[0]
        arrival = np.arange(n)
        slots = self.route_slots(keys)
        owner = self.assignment[slots]
        state = self._build_state(table, keys, ts,
                                  {c: np.asarray(cols[c]) for c in
                                   self.col_specs[table] if c in cols},
                                  owner, arrival)
        self.tables[table] = state
        # after _build_state: a per-shard overflow must not leave
        # phantom load in the balancer (put_many orders the same way)
        self._slot_counts += np.bincount(slots,
                                         minlength=self.n_route_slots)
        order = np.lexsort((arrival, ts, keys))
        ko, tso = keys[order].tolist(), ts[order].tolist()
        # full-fidelity entries: a log replay must rebuild values too
        # (see OnlineStore.bulk_load / storage.replication)
        co = {c: np.asarray(cols[c])[order].tolist() for c in cols}
        self.binlog.extend(
            (table, ko[i], tso[i], {c: float(co[c][i]) for c in co})
            for i in range(n))
        self._binlog_offset += n
        return n

    def _build_state(self, table: str, keys, ts, cols, owner, arrival
                     ) -> StoreState:
        """Stacked state from host rows: per-shard (key, ts, arrival)
        lexsort — the same order per-shard sequential inserts produce."""
        counts = np.bincount(owner, minlength=self.n_shards)
        if counts.max(initial=0) > self.capacity:
            s = int(np.argmax(counts))
            raise ValueError(f"shard {s} gets {int(counts[s])} rows > "
                             f"per-shard capacity {self.capacity}")
        specs = self.col_specs[table]
        k_st = np.full((self.n_shards, self.capacity), INT_MAX, np.int32)
        t_st = np.full((self.n_shards, self.capacity), INT_MAX, np.int32)
        c_st = {c: np.zeros((self.n_shards, self.capacity), dt)
                for c, dt in specs.items()}
        for s in range(self.n_shards):
            sel = np.flatnonzero(owner == s)
            if not sel.size:
                continue
            order = sel[np.lexsort((arrival[sel], ts[sel], keys[sel]))]
            k_st[s, :order.size] = keys[order]
            t_st[s, :order.size] = ts[order]
            for c in c_st:
                if c in cols:
                    c_st[c][s, :order.size] = np.asarray(cols[c])[order]
        return self._place({
            "keys": jnp.asarray(k_st),
            "ts": jnp.asarray(t_st),
            "cols": {c: jnp.asarray(v) for c, v in c_st.items()},
            "count": jnp.asarray(counts, jnp.int32),
        })

    def evict(self, table: str, horizon_ts: int):
        """Per-shard batch TTL eviction + slot compaction (vmapped)."""
        self.tables[table] = evict_before_stacked(self.tables[table],
                                                  jnp.int32(horizon_ts))

    # --------------------------------------------------------- rebalance
    def rebalance(self) -> bool:
        """Hot-key rebalancing (§5.2 mapped to shards): fold accumulated
        per-slot load into the LoadBalancer EMA, recompute the slot->shard
        map with greedy LPT, and migrate resident rows whose owner
        changed.  Whole-key moves only (see class docstring).  Returns
        True if the assignment changed (callers owning per-shard derived
        state — pre-agg buckets — must migrate it too; see
        ``serve.engine.FeatureEngine.rebalance``).
        """
        self.balancer.observe(self._slot_counts)
        # counts are folded into the EMA exactly once: zero them NOW so a
        # retry after a failed migration doesn't double-count the load
        self._slot_counts[:] = 0.0
        new_assign = self.balancer.rebalance().copy()
        if np.array_equal(new_assign, self.assignment):
            return False
        # two-phase: build EVERY table's migrated state before committing
        # anything — a per-shard capacity overflow mid-migration must not
        # leave some tables routed by the new assignment while
        # self.assignment still routes by the old one
        new_tables: Dict[str, StoreState] = {}
        for table in self.tables:
            st = jax.device_get(self.tables[table])
            counts = np.asarray(st["count"])
            rows_k, rows_t, rows_c, rows_pos = [], [], {c: [] for c in
                                                        st["cols"]}, []
            for s in range(self.n_shards):
                c = int(counts[s])
                rows_k.append(np.asarray(st["keys"][s, :c]))
                rows_t.append(np.asarray(st["ts"][s, :c]))
                for col in rows_c:
                    rows_c[col].append(np.asarray(st["cols"][col][s, :c]))
                # global source position: preserves per-key arrival order
                # (all rows of one key live on one source shard)
                rows_pos.append(s * self.capacity + np.arange(c))
            keys = np.concatenate(rows_k)
            ts = np.concatenate(rows_t)
            cols = {c: np.concatenate(v) for c, v in rows_c.items()}
            pos = np.concatenate(rows_pos)
            owner = new_assign[self.route_slots(keys)] if keys.size else \
                np.zeros(0, np.int64)
            new_tables[table] = self._build_state(table, keys, ts, cols,
                                                  owner, pos)
        self.tables.update(new_tables)
        self.assignment = new_assign
        self.n_rebalances += 1
        return True

    # ------------------------------------------------------- replication
    # Reads always go to the leader slot: slot s of the stacked pytree IS
    # shard s's leader replica, and the serving path
    # (``online_sharded_batch``) only ever gathers from it.  Follower
    # replicas live OUTSIDE the stacked layout (storage.replication) and
    # enter it exclusively through ``install_shard`` at promotion.

    def shard_state(self, table: str, shard: int) -> StoreState:
        """Unstacked copy of one shard's slice of ``table`` — the
        leader's state, used to seed/resync follower replicas."""
        return jax.tree_util.tree_map(lambda x: x[shard],
                                      self.tables[table])

    def install_shard(self, shard: int,
                      tables: Dict[str, StoreState]) -> None:
        """Write per-shard states into stacked slot ``shard`` (follower
        promotion: the promoted replica becomes the leader for the
        shard's key range; routing is untouched — key -> slot stays,
        only the slot's contents are replaced)."""
        for name, st in tables.items():
            # through host memory: a scatter into a mesh-placed stacked
            # array with a replicated index has incompatible shardings,
            # and promotion is a cold-path host operation anyway
            def _put(full, part):
                out = np.asarray(jax.device_get(full)).copy()
                out[shard] = np.asarray(jax.device_get(part), out.dtype)
                return jnp.asarray(out)

            stacked = jax.tree_util.tree_map(_put, self.tables[name], st)
            self.tables[name] = self._place(stacked)

    def snapshot(self) -> StoreSnapshot:
        """Cut an immutable point-in-time read view: frozen tables AND
        frozen routing (see ``StoreSnapshot``)."""
        return StoreSnapshot(self)

    def wipe_shard(self, shard: int) -> None:
        """Fault injection: shard ``shard`` loses all resident rows (the
        dense analogue of a tablet node dying — its slot reads as an
        empty, freshly-provisioned store until a replica is promoted
        into it)."""
        empty = {name: make_state(self.capacity, self.col_specs[name])
                 for name in self.tables}
        self.install_shard(shard, empty)
