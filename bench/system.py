"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program (``repro``):
it turns generated columns into the program's tables and builds the
deployed ``FeatureEngine`` and its ``ServeLoop``.  Everything else in
``bench/`` works on plain NumPy columns.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.types import Column, ColumnType, Dictionary, Table, \
    TableSchema
from repro.serve.engine import FeatureEngine
from repro.serve.loop import AdmissionError, ServeLoop

__all__ = ["AdmissionError", "ServeLoop", "build_engine", "build_loop",
           "enable_compile_cache", "make_tables", "serving_pad_classes",
           "key_owners", "shift_tables", "store_devices", "warm_batches"]


def make_tables(schema: Mapping[str, Sequence], dicts: Mapping[str, Sequence],
                cols: Mapping[str, Mapping[str, np.ndarray]]
                ) -> Dict[str, Table]:
    """Program tables from generated columns (STRING columns as codes,
    with one shared dictionary per column name)."""
    shared = {}
    for name, values in dicts.items():
        d = Dictionary()
        d.encode_many(list(values))
        shared[name] = d
    out = {}
    for tname, columns in schema.items():
        ts = TableSchema(tname, tuple(Column(c, ColumnType[t])
                                      for c, t in columns))
        out[tname] = Table(ts, cols[tname],
                           dicts={c: shared[c] for c, _ in columns
                                  if c in shared})
    return out


def shift_tables(tables: Mapping[str, Table], order_col: str, by: int
                 ) -> Dict[str, Table]:
    """The same tables with every ``order_col`` timestamp moved by
    ``by``: new content (the engine plans it afresh) with the same
    window results."""
    return {n: Table(t.schema, dict(t.columns, **{
        order_col: t.columns[order_col] + by}), dicts=t.dicts)
        for n, t in tables.items()}


def build_engine(sql: str, tables: Mapping[str, Table], cfg,
                 devices: Sequence = ()) -> FeatureEngine:
    """The deployed engine.  A configuration with ``shards`` partitions
    its keys over that many tablets, one per device of the cell, on a
    1-D mesh of exactly those devices (``capacity`` is per shard);
    without it the store is one unsharded table per chip."""
    if "shards" not in cfg:
        return FeatureEngine(sql, dict(tables), capacity=cfg["capacity"])
    from jax.sharding import Mesh

    return FeatureEngine(sql, dict(tables), capacity=cfg["capacity"],
                         mesh=Mesh(np.asarray(devices), ("shard",)))


def store_devices(engine: FeatureEngine) -> set:
    """The devices that hold the store's arrays."""
    import jax

    return {d for x in jax.tree_util.tree_leaves(engine.store.tables)
            for d in x.sharding.device_set}


def build_loop(engine: FeatureEngine, mix) -> ServeLoop:
    return ServeLoop(engine, slo_ms=mix["slo_ms"],
                     max_wait_ms=mix["max_wait_ms"],
                     batch_size=mix["batch_size"],
                     max_queue=mix["max_queue"],
                     ingest_queue_rows=mix["ingest_queue_rows"])


def serving_pad_classes(engine: FeatureEngine, max_batch: int):
    """The request pad classes a batch of up to ``max_batch`` can reach,
    by the program's static retrace bound: the shapes to warm.  A
    sharded engine pads each shard's sub-batch to its busiest shard's
    count (``online_sharded_batch``)."""
    from repro.core.analysis.retrace import retrace_bound

    path = "online_sharded_batch" if engine.sharded else "online_batch"
    return retrace_bound(engine.cs, max_batch=max_batch)[
        "drivers"][path]["pad_classes"]


def key_owners(engine: FeatureEngine, keys) -> np.ndarray:
    """The shard that owns each key, by the store's routing."""
    return engine.store.owner_of_keys(np.asarray(keys))


def warm_batches(engine: FeatureEngine, keys: np.ndarray, classes
                 ) -> List[List[int]]:
    """Per pad class ``c``, the indices (into ``keys``, the warm-up
    requests' keys) of one batch that runs the executable of class
    ``c``: any ``c`` requests for an unsharded engine; for a sharded
    one, ``c`` requests of keys that one shard owns, so that its
    busiest shard gets exactly ``c``."""
    if not engine.sharded:
        return [list(range(c)) for c in classes]
    owner = key_owners(engine, keys)
    mine = np.flatnonzero(owner == np.bincount(owner).argmax())
    if len(mine) < max(classes):
        raise ValueError(f"{len(mine)} warm-up requests on the busiest "
                         f"shard, the largest class is {max(classes)}")
    return [mine[:c].tolist() for c in classes]
