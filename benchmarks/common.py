"""Benchmark utilities: timing, CSV emission, JSON artifacts.

Every benchmark prints ``name,us_per_call,derived`` rows (harness
contract).  ``derived`` carries the figure-specific metric (speedup,
reduction %, tuples/sec, ...).

With ``--json`` (or ``BENCH_JSON=1``) the run additionally writes
``BENCH_<name>.json`` in the current directory: the parsed config, the
emitted rows, every ``timeit`` call's raw per-iteration samples, and
the per-row medians — the machine-readable form of the CSV stream, so
CI and docs can diff numbers without scraping stdout.

``bench_main(name, main)`` is the shared entry driver: uniform
``--quick`` / ``--tiny`` / ``--json`` parsing, CSV header, JSON
artifact, exit-code passthrough.
"""

from __future__ import annotations

import inspect
import json
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

# run-wide collector (one benchmark process == one artifact)
_rows: List[Dict[str, object]] = []
_samples: Dict[str, List[float]] = {}
_config: Dict[str, object] = {}


def set_config(**kw) -> None:
    """Record run parameters (sizes, flags) into the JSON artifact."""
    _config.update(kw)


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 5,
           label: Optional[str] = None) -> float:
    """Median wall-clock microseconds per call.  Raw per-iteration
    samples land in the JSON artifact under ``label`` (or an ordinal)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    _samples[label or f"timeit_{len(_samples)}"] = [float(t) for t in times]
    return float(np.median(times))


def record_samples(label: str, samples) -> None:
    """Store raw measurement samples (us) into the JSON artifact under
    ``label`` — for measurements not taken through ``timeit`` (e.g.
    interleaved A/B pairs)."""
    _samples[label] = [float(s) for s in samples]


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    _rows.append({"name": name, "us_per_call": float(us_per_call),
                  "derived": derived})


# BENCH_*.json artifact schema — the contract CI and docs rely on when
# diffing numbers.  validate_payload() enforces it on every write (and
# on any artifact handed back for re-reading).
BENCH_SCHEMA = {
    "bench": str,              # benchmark name (matches the filename)
    "config": dict,            # run parameters from set_config()
    "rows": list,              # [{name, us_per_call, derived}] CSV rows
    "medians": dict,           # {row name: us_per_call}
    "samples": dict,           # {label: [raw us per iteration]}
}
_ROW_SCHEMA = {"name": str, "us_per_call": (int, float), "derived": str}


def validate_payload(payload) -> List[str]:
    """Validate a BENCH_*.json payload; returns problem strings ([] ok).

    Checks the top-level shape (BENCH_SCHEMA), every row against
    _ROW_SCHEMA with finite non-negative timings, medians/rows
    agreement, and that samples are flat lists of finite floats.
    """
    probs: List[str] = []
    if not isinstance(payload, dict):
        return [f"payload is {type(payload).__name__}, expected dict"]
    for key, typ in BENCH_SCHEMA.items():
        if key not in payload:
            probs.append(f"missing key {key!r}")
        elif not isinstance(payload[key], typ):
            probs.append(f"{key!r} is {type(payload[key]).__name__}, "
                         f"expected {typ.__name__}")
    for extra in sorted(set(payload) - set(BENCH_SCHEMA)):
        probs.append(f"unknown key {extra!r}")
    if probs:
        return probs

    names = []
    for i, row in enumerate(payload["rows"]):
        if not isinstance(row, dict):
            probs.append(f"rows[{i}] is not an object")
            continue
        for key, typ in _ROW_SCHEMA.items():
            if key not in row:
                probs.append(f"rows[{i}] missing {key!r}")
            elif not isinstance(row[key], typ) or isinstance(row[key],
                                                             bool):
                probs.append(f"rows[{i}].{key} has type "
                             f"{type(row[key]).__name__}")
        us = row.get("us_per_call")
        if isinstance(us, (int, float)) and not isinstance(us, bool):
            if not np.isfinite(us) or us < 0:
                probs.append(f"rows[{i}].us_per_call = {us!r} is not a "
                             f"finite non-negative time")
        if isinstance(row.get("name"), str):
            names.append(row["name"])
    med = payload["medians"]
    if set(med) != set(names):
        probs.append(f"medians keys {sorted(set(med) ^ set(names))} "
                     f"disagree with row names")
    for label, samples in payload["samples"].items():
        if not isinstance(samples, list) or not all(
                isinstance(s, (int, float)) and not isinstance(s, bool)
                and np.isfinite(s) for s in samples):
            probs.append(f"samples[{label!r}] is not a flat list of "
                         f"finite numbers")
    return probs


def write_json(bench_name: str, path: Optional[str] = None) -> str:
    """Write ``BENCH_<bench_name>.json`` (cwd unless ``path``),
    schema-validated — a malformed artifact fails the run loudly
    instead of poisoning downstream diffs."""
    payload = {
        "bench": bench_name,
        "config": _config,
        "rows": _rows,
        "medians": {r["name"]: r["us_per_call"] for r in _rows},
        "samples": _samples,
    }
    probs = validate_payload(json.loads(json.dumps(payload)))
    if probs:
        raise ValueError("BENCH artifact fails schema: "
                         + "; ".join(probs))
    path = path or f"BENCH_{bench_name}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def bench_main(name: str, main: Callable, argv: Optional[List[str]] = None
               ) -> None:
    """Shared benchmark entry: parse --quick/--tiny/--json, print the
    CSV header, run ``main`` with whatever subset of (quick, tiny) it
    accepts, write the JSON artifact when asked, exit with its code."""
    import argparse

    ap = argparse.ArgumentParser(prog=f"bench_{name}")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--json", action="store_true",
                    help=f"also write BENCH_{name}.json")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    set_config(quick=args.quick, tiny=args.tiny)
    accepted = set(inspect.signature(main).parameters)
    kw = {k: getattr(args, k) for k in ("quick", "tiny") if k in accepted}
    print("name,us_per_call,derived")
    rc = main(**kw)
    if args.json or __import__("os").environ.get("BENCH_JSON"):
        write_json(name)
    sys.exit(rc)
