"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program (``repro``):
it turns generated columns into the program's tables and builds the
deployed ``FeatureEngine`` and its ``ServeLoop``.  Everything else in
``bench/`` works on plain NumPy columns.
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.types import Column, ColumnType, Dictionary, Table, \
    TableSchema
from repro.serve.engine import FeatureEngine
from repro.serve.loop import AdmissionError, ServeLoop

__all__ = ["AdmissionError", "ServeLoop", "build_engine", "build_loop",
           "enable_compile_cache", "make_tables", "serving_pad_classes",
           "shift_tables"]


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


def build_engine(sql: str, tables: Mapping[str, Table], cfg) -> FeatureEngine:
    return FeatureEngine(sql, dict(tables), capacity=cfg["capacity"])


def build_loop(engine: FeatureEngine, mix) -> ServeLoop:
    return ServeLoop(engine, slo_ms=mix["slo_ms"],
                     max_wait_ms=mix["max_wait_ms"],
                     batch_size=mix["batch_size"],
                     max_queue=mix["max_queue"],
                     ingest_queue_rows=mix["ingest_queue_rows"])


def serving_pad_classes(engine: FeatureEngine, max_batch: int):
    """The request pad classes a batch of up to ``max_batch`` can reach,
    by the program's static retrace bound: the shapes to warm."""
    from repro.core.analysis.retrace import retrace_bound

    return retrace_bound(engine.cs, max_batch=max_batch)[
        "drivers"]["online_batch"]["pad_classes"]
