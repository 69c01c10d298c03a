"""Actions, orders and profile tables of the paper's Figure 1 scenario.

The row shapes are those of the paper's MicroBench-style stream tables:
``actions`` and ``orders`` share the columns (userid, ts, price,
quantity, category), ``profile`` holds (userid, ts, age, score).  Keys
are uniform.  Every timestamp in a run is unique, so window peers never
tie.

Sizes are fixed per configuration, not per seed.  The number of actions
and orders each user holds is drawn once from the configuration's
``shape_seed``; ``--seed`` only decides which user gets which pair of
counts, and everything else (timestamps, prices, categories, requests,
ingest rows).  So every seed folds the same multiset of per-key windows:
the same executables and the same work, in another order.

Live traffic is stamped after the history: ingest rows at
``live_ts0 + 2 j``, requests after every ingest row at odd offsets, so a
request's 100-day window holds exactly the ingest rows acknowledged
before its batch launched.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

CATEGORIES = ("shoes", "hats", "bags", "tops", "toys", "food", "books",
              "phones")

# column name -> type name, in schema order (types of the SQL dialect)
SCHEMA = {
    "actions": (("userid", "INT"), ("ts", "TIMESTAMP"), ("price", "FLOAT"),
                ("quantity", "INT"), ("category", "STRING")),
    "orders": (("userid", "INT"), ("ts", "TIMESTAMP"), ("price", "FLOAT"),
               ("quantity", "INT"), ("category", "STRING")),
    "profile": (("userid", "INT"), ("ts", "TIMESTAMP"), ("age", "FLOAT"),
                ("score", "FLOAT")),
}
# STRING columns and the names of their codes 0..n-1
DICTS = {"category": CATEGORIES}

Columns = Dict[str, np.ndarray]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _events(rng, users, ts) -> Columns:
    n = len(users)
    return {"userid": users.astype(np.int32), "ts": ts.astype(np.int64),
            "price": rng.uniform(1, 100, n).astype(np.float32),
            "quantity": rng.integers(0, 5, n).astype(np.int32),
            "category": rng.integers(0, len(CATEGORIES), n).astype(np.int32)}


def per_user_counts(cfg) -> np.ndarray:
    """(n_users, 2) actions and orders per key, from ``shape_seed``
    alone: the same multiset in every run."""
    rng = np.random.default_rng(cfg["shape_seed"])
    n_u = cfg["n_users"]
    return np.stack([np.bincount(rng.integers(0, n_u, cfg[k]),
                                 minlength=n_u)
                     for k in ("n_actions", "n_orders")], axis=1)


def history(cfg, seed: int) -> Dict[str, Columns]:
    """The bulk-loaded tables, columns by name."""
    rng = _rng(seed, 0)
    n_u, n_a, n_o = cfg["n_users"], cfg["n_actions"], cfg["n_orders"]
    counts = per_user_counts(cfg)[rng.permutation(n_u)]
    step = cfg["ts_step_ms"]
    grid = cfg["horizon_ms"] // step
    if grid < n_a + n_o:
        raise ValueError("horizon_ms / ts_step_ms leaves fewer unique "
                         "timestamps than rows")
    ts = 1 + step * rng.choice(grid, n_a + n_o, replace=False)
    users = np.arange(n_u)
    out = {"actions": _events(rng, np.repeat(users, counts[:, 0]),
                              ts[:n_a]),
           "orders": _events(rng, np.repeat(users, counts[:, 1]),
                             ts[n_a:])}
    k = cfg["profile_rows_per_user"]
    pu = np.repeat(users, k)
    out["profile"] = {
        "userid": pu.astype(np.int32),
        "ts": rng.integers(1, cfg["horizon_ms"] // 2, len(pu)),
        "age": rng.integers(18, 68, len(pu)).astype(np.float32),
        "score": (pu * 1.5).astype(np.float32)}
    return out


def live_ts0(cfg) -> int:
    return int(cfg["horizon_ms"]) + 1


def ingest_stream(cfg, seed: int, n_batches: int, batch_rows: int,
                  tables: List[str]) -> List[Columns]:
    """``n_batches`` batches of new events; batch j goes to
    ``tables[j % len(tables)]``, timestamps ``live_ts0 + 2 i`` in stream
    order."""
    rng = _rng(seed, 1)
    n = n_batches * batch_rows
    ev = _events(rng, rng.integers(0, cfg["n_users"], n),
                 live_ts0(cfg) + 2 * np.arange(n))
    return [{c: v[j * batch_rows:(j + 1) * batch_rows]
             for c, v in ev.items()} for j in range(n_batches)]


def requests(cfg, seed: int, n: int, n_ingest_rows: int,
             stream: int = 2) -> Columns:
    """``n`` fresh actions of uniformly drawn users, stamped after the
    history and after every ingest row."""
    rng = _rng(seed, stream)
    ts0 = live_ts0(cfg) + 2 * n_ingest_rows + 1
    return _events(rng, rng.integers(0, cfg["n_users"], n),
                   ts0 + 2 * np.arange(n))


def client_rows(cols: Columns) -> List[dict]:
    """Rows as a client sends them: Python scalars, categories by
    name."""
    names = list(cols)
    lists = [cols[c].tolist() for c in names]
    ci = names.index("category") if "category" in names else -1
    if ci >= 0:
        lists[ci] = [CATEGORIES[c] for c in lists[ci]]
    return [dict(zip(names, vals)) for vals in zip(*lists)]
