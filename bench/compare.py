"""The comparisons that decide ``correct``.

* ``exact_mismatch``: rows in which a feature that the reference gives
  exactly (a count, a category code, a copied value, one float32
  product) differs from what the program returned.  Limit 0.
* ``float_gap``: the widest gap of a float feature (a sum, an average)
  from the reference's exact value, relative to ``max(|reference|, 1)``:
  prices lie in [1, 100), so a nonzero sum or average is at least 1.
  Its limit is set per configuration from runs of the program and of
  the lower-precision control (``PERF.md``).
* ``offline_mismatch``: served rows that are not bit for bit what the
  engine's own ``offline()`` gives for the same rows, the deployment's
  stated guarantee.  Limit 0.
* ``unanswered``: requests due in the window that were accepted and
  never answered.  Limit 0.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np


def _rows(a: np.ndarray) -> np.ndarray:
    return a.reshape(a.shape[0], -1)


def against_reference(got: Mapping[str, np.ndarray],
                      want: Mapping[str, np.ndarray],
                      kinds: Mapping[str, str],
                      detail: Dict[str, float] = None) -> Tuple[int, float]:
    """(exact_mismatch, float_gap) of ``got`` against the reference;
    ``detail`` collects each feature's own reading."""
    bad = None
    gap = 0.0
    detail = {} if detail is None else detail
    for name, kind in kinds.items():
        g = _rows(np.asarray(got[name], np.float64))
        w = _rows(np.asarray(want[name], np.float64))
        if g.shape != w.shape:
            raise ValueError(f"feature {name}: shape {g.shape} != "
                             f"reference {w.shape}")
        if kind == "exact":
            row_bad = np.any(g != w, axis=1)
            bad = row_bad if bad is None else bad | row_bad
            detail[name] = max(detail.get(name, 0), int(row_bad.sum()))
        else:
            d = np.abs(g - w) / np.maximum(np.abs(w), 1.0)
            d = np.where(np.isnan(d), np.inf, d)
            if d.size:
                gap = max(gap, float(d.max()))
                detail[name] = max(detail.get(name, 0.0), float(d.max()))
    return int(0 if bad is None else bad.sum()), gap


def bitwise_mismatch(a: Mapping[str, np.ndarray],
                     b: Mapping[str, np.ndarray]) -> int:
    """Rows in which any feature of ``a`` and ``b`` differs in a bit."""
    bad = None
    for name in b:
        x, y = _rows(np.asarray(a[name])), _rows(np.asarray(b[name]))
        row_bad = np.any(x.view(np.uint8).reshape(len(x), -1)
                         != y.view(np.uint8).reshape(len(y), -1), axis=1) \
            if x.dtype == y.dtype and x.shape == y.shape \
            else np.ones(len(y), bool)
        bad = row_bad if bad is None else bad | row_bad
    return int(0 if bad is None else bad.sum())


def as_returned(feats: Mapping[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Reference features in the dtype the program returns (float32):
    the control, put in the program's place."""
    return {k: np.asarray(v, np.float32) for k, v in feats.items()}


def stack_rows(rows, names) -> Dict[str, np.ndarray]:
    """Per-request feature dicts -> one array per feature."""
    return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in names}
