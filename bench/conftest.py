"""Fixtures of the benchmark's CPU tests: a toy copy of the benchmark's
tree, and the harness steered onto the CPU (the chip check and the peak
table patched here, in the tests, not through an option)."""

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent

TOY = {"n_users": 24, "n_actions": 1200, "n_orders": 600,
       "horizon_ms": 400_000, "capacity": 4096}


def make_toy_root(dst: Path, rate: float = 400.0) -> Path:
    """A checkout-like tree at ``dst``: ``bench/`` copied, a toy
    configuration ``toy`` (the dense configuration at toy sizes) and a
    ``BENCHMARK.json`` whose cells run it under every traffic mix."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*",
                                                  "testdata"))
    cdir = dst / "bench" / "configs" / "toy"
    shutil.copytree(BENCH / "configs" / "fig1_dense", cdir)
    cfg = json.loads((cdir / "config.json").read_text())
    cfg.update(TOY)
    (cdir / "config.json").write_text(json.dumps(cfg))
    for mix in ("serve", "serve_ingest"):
        p = dst / "bench" / "traffic" / f"{mix}.json"
        m = json.loads(p.read_text())
        m.update(rate_per_s=rate, batch_size=4, sample_requests=200,
                 drain_s=30)
        if m["ingest"]:
            m["ingest"].update(batch_rows=16, max_batches=64)
            m["ingest_queue_rows"] = 64
        p.write_text(json.dumps(m))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": f"toy.{t}", "config": "toy", "traffic": t, "chips": 1,
         "why": "CPU test"} for t in ("serve", "serve_ingest", "backfill")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w.replace("fig1_dense", "toy")
                              .replace("fig1_sparse", "toy")
                              for w in m["workloads"]]
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture
def cpu_harness(monkeypatch):
    """The harness with the CPU standing in for the chip."""
    import jax

    from bench import harness, peaks, system

    dev = jax.devices()[0]
    monkeypatch.setitem(peaks.PEAKS, dev.device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: jax.devices()[:chips])
    # no persistent compile cache written into the checkout by tests
    monkeypatch.setattr(system, "enable_compile_cache", lambda: "off")
    return harness


@pytest.fixture
def toy_root(tmp_path, cpu_harness, monkeypatch):
    root = make_toy_root(tmp_path)
    monkeypatch.setattr(cpu_harness, "ROOT", root)
    return root
