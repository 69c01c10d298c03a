"""Plain NumPy reference of the paper's Figure 1 feature script.

    SELECT
      distinct_count(category) OVER w_union_3s AS product_count,
      avg_cate_where(price, quantity > 1, category)
          OVER w_union_3s AS product_prices,
      sum(price) OVER w_action_100d AS spend_100d,
      topn_frequency(category, 3) OVER w_action_100d AS favourite_products,
      profile.age AS age,
      price * quantity AS order_value
    FROM actions
    LAST JOIN profile ORDER BY ts ON actions.userid = profile.userid
    WINDOW w_union_3s AS (UNION orders PARTITION BY userid ORDER BY ts
                          ROWS_RANGE BETWEEN 3s PRECEDING AND CURRENT ROW),
           w_action_100d AS (PARTITION BY userid ORDER BY ts
                             ROWS_RANGE BETWEEN 100d PRECEDING AND CURRENT ROW)

Each query row is the window's current row.  A window holds the current
row and every visible row of the same user whose timestamp lies in
``[ts - span, ts)``: OpenMLDB's ``ROWS_RANGE ... PRECEDING AND CURRENT
ROW`` frame, with the lower edge included.  Timestamps are unique, so
no row shares the current row's timestamp.  Functions, as OpenMLDB's
function reference states them, with the choices its text leaves open:

* ``distinct_count``: the number of distinct categories in the window.
* ``avg_cate_where(v, cond, cat)``: OpenMLDB returns a string
  ``"cat:avg,..."`` of the categories that meet ``cond``; here it is a
  dense vector over the category codes 0..7, the average of ``price``
  over rows with ``quantity > 1`` of that category, and 0 for a
  category with no such row.
* ``topn_frequency(cat, 3)``: the three most frequent category codes,
  most frequent first; equal counts go to the lower code; -1 fills the
  places past the number of distinct categories.
* ``sum(price)``: the exact sum (float64; every float32 price is a
  multiple of 2**-23, so the sums here are exact).
* ``LAST JOIN profile ORDER BY ts``: the profile row of the same user
  with the greatest ``ts`` not after the current row's, the later row of
  the table on equal ``ts``; ``age`` is 0 where no row matches.
* ``price * quantity``: one float32 product, as the column types state.

``lowp=True`` computes the same in bfloat16 (inputs and results rounded
to it): the control that a comparison has to fail.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

BASE_TABLE = "actions"     # the script's FROM: one feature row per row
ORDER_COLUMN = "ts"
KEY_COLUMN = "userid"
N_CAT = 8
TOP_N = 3
UNION_MS = 3_000
LONG_MS = 100 * 86_400_000

# feature -> how it is compared: "exact" (equal values) or "float"
FEATURES = {"product_count": "exact", "product_prices": "float",
            "spend_100d": "float", "favourite_products": "exact",
            "age": "exact", "order_value": "exact"}

Columns = Dict[str, np.ndarray]
_SHIFT = np.int64(1) << np.int64(33)


def _bf16(x) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)


class _Sorted:
    """One table's rows sorted by (user, ts, arrival), with ``seq`` the
    ingest position of each row (-1 for history: always visible)."""

    def __init__(self, parts: Sequence[Tuple[Columns, np.ndarray]],
                 cols: Sequence[str]):
        users = np.concatenate([p["userid"] for p, _ in parts])
        ts = np.concatenate([p["ts"] for p, _ in parts]).astype(np.int64)
        seq = np.concatenate([s for _, s in parts]).astype(np.int64)
        order = np.lexsort((np.arange(len(ts)), ts, users))
        self.comp = users[order].astype(np.int64) * _SHIFT + ts[order]
        self.ts = ts[order]
        self.seq = seq[order]
        self.cols = {c: np.concatenate([p[c] for p, _ in parts])[order]
                     for c in cols}

    def span(self, user: int, t_lo: int, t_hi: int, acked: int,
             hi_side: str = "left") -> np.ndarray:
        """Positions of ``user``'s visible rows with ts in [t_lo, t_hi)
        (``hi_side="right"``: ts <= t_hi)."""
        base = np.int64(user) * _SHIFT
        lo = np.searchsorted(self.comp, base + max(t_lo, 0), "left")
        hi = np.searchsorted(self.comp, base + t_hi, hi_side)
        pos = np.arange(lo, hi)
        return pos[self.seq[pos] < acked]


class Reference:
    """Figure 1 features over the history plus ingest rows.

    ``ingest``: ``(table, columns)`` batches in the order they are
    applied; a query with ``acked = n`` sees the first ``n`` of their
    rows, counted across batches.
    """

    def __init__(self, tables: Dict[str, Columns],
                 ingest: Optional[List[Tuple[str, Columns]]] = None,
                 lowp: bool = False):
        self.lowp = lowp
        parts: Dict[str, list] = {"actions": [], "orders": [],
                                  "profile": []}
        for name in parts:
            t = tables[name]
            parts[name].append((t, np.full(len(t["ts"]), -1)))
        at = 0
        for name, cols in ingest or ():
            n = len(cols["ts"])
            parts[name].append((cols, at + np.arange(n)))
            at += n
        ev = ("price", "quantity", "category")
        self.actions = _Sorted(parts["actions"], ev)
        self.orders = _Sorted(parts["orders"], ev)
        self.profile = _Sorted(parts["profile"], ("age",))

    def _price(self, p):
        return (_bf16(p).astype(np.float64) if self.lowp
                else np.asarray(p, np.float64))

    def features(self, q: Columns, acked: Optional[np.ndarray] = None
                 ) -> Dict[str, np.ndarray]:
        """Features of query rows ``q`` (columns userid, ts, price,
        quantity, category); query i sees ingest rows ``< acked[i]``."""
        n = len(q["ts"])
        if acked is None:
            acked = np.zeros(n, np.int64)
        out = {"product_count": np.zeros(n),
               "product_prices": np.zeros((n, N_CAT)),
               "spend_100d": np.zeros(n),
               "favourite_products": np.zeros((n, TOP_N)),
               "age": np.zeros(n), "order_value": np.zeros(n)}
        A, O = self.actions, self.orders
        for i in range(n):
            u, t, ack = int(q["userid"][i]), int(q["ts"][i]), int(acked[i])
            cat0 = int(q["category"][i])
            price0 = self._price(q["price"][i:i + 1])
            qty0 = int(q["quantity"][i])
            # union 3 s window over actions and orders
            pa = A.span(u, t - UNION_MS, t, ack)
            po = O.span(u, t - UNION_MS, t, ack)
            cats = np.concatenate([A.cols["category"][pa],
                                   O.cols["category"][po], [cat0]])
            price = np.concatenate([self._price(A.cols["price"][pa]),
                                    self._price(O.cols["price"][po]),
                                    price0])
            big = np.concatenate([A.cols["quantity"][pa],
                                  O.cols["quantity"][po], [qty0]]) > 1
            out["product_count"][i] = len(np.unique(cats))
            s = np.bincount(cats[big], weights=price[big], minlength=N_CAT)
            c = np.bincount(cats[big], minlength=N_CAT)
            out["product_prices"][i] = s / np.maximum(c, 1)
            # 100-day window over actions
            pl = A.span(u, t - LONG_MS, t, ack)
            out["spend_100d"][i] = (self._price(A.cols["price"][pl]).sum()
                                    + price0[0])
            cnt = np.bincount(np.append(A.cols["category"][pl], cat0),
                              minlength=N_CAT)
            top = np.argsort(-cnt, kind="stable")[:TOP_N]
            out["favourite_products"][i] = np.where(cnt[top] > 0, top, -1)
            # LAST JOIN profile: newest row with ts <= t
            pp = self.profile.span(u, 0, t, ack, hi_side="right")
            out["age"][i] = self.profile.cols["age"][pp[-1]] if len(pp) \
                else 0.0
            out["order_value"][i] = (
                np.float32(_bf16(q["price"][i]) * _bf16(qty0))
                if self.lowp else
                np.float32(q["price"][i]) * np.float32(qty0))
        if self.lowp:
            for k in ("product_prices", "spend_100d", "age"):
                out[k] = _bf16(out[k]).astype(np.float64)
        return out

    def window_rows(self, q: Columns, acked: Optional[np.ndarray] = None
                    ) -> np.ndarray:
        """(n, 2) rows in each query's union window and 100-day window,
        the current row included."""
        n = len(q["ts"])
        if acked is None:
            acked = np.zeros(n, np.int64)
        out = np.ones((n, 2), np.int64)
        for i in range(n):
            u, t, ack = int(q["userid"][i]), int(q["ts"][i]), int(acked[i])
            out[i, 0] += (len(self.actions.span(u, t - UNION_MS, t, ack))
                          + len(self.orders.span(u, t - UNION_MS, t, ack)))
            out[i, 1] += len(self.actions.span(u, t - LONG_MS, t, ack))
        return out


# columns each part of the script reads, 4 bytes each: the union window
# reads ts, price, quantity, category; the 100-day window ts, price,
# category; the LAST JOIN one profile row's ts and age; a request brings
# its five columns and takes back 15 feature values
_UNION_COLS, _LONG_COLS, _JOIN_COLS, _IN_COLS, _OUT_VALUES = 4, 3, 2, 5, 15


def request_bytes(window_rows: np.ndarray) -> np.ndarray:
    """Least bytes each request needs moved: its in-window rows times
    the columns read, the joined row, its inputs and its outputs."""
    return 4 * (window_rows[:, 0] * _UNION_COLS
                + window_rows[:, 1] * _LONG_COLS
                + _JOIN_COLS + _IN_COLS + _OUT_VALUES)
