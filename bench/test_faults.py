"""A whole run on the CPU with the timed path broken underneath: each
fault a cell of this benchmark can have turns ``correct`` false."""

import json

import numpy as np
import pytest

from repro.serve.engine import FeatureEngine


def run_cell(harness, capsys, cell, seconds="2"):
    rc = harness.main(["--workload", cell, "--seed", "2147483659",
                       "--seconds", seconds, "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["toy.serve", "toy.serve_ingest"])
def test_sound_runs_are_correct(toy_root, cpu_harness, capsys, cell):
    line = run_cell(cpu_harness, capsys, cell)
    assert line["correct"] and line["failed"] == 0


def test_served_answer_altered(toy_root, cpu_harness, capsys, monkeypatch):
    real = FeatureEngine.request_batch

    def altered(self, rows, snapshot=None):
        out = real(self, rows, snapshot=snapshot)
        for f in out:
            f["spend_100d"] = f["spend_100d"] * np.float32(1.001)
        return out

    monkeypatch.setattr(FeatureEngine, "request_batch", altered)
    line = run_cell(cpu_harness, capsys, "toy.serve")
    assert not line["correct"]
    assert line["checks"]["float_gap"]["value"] > \
        line["checks"]["float_gap"]["limit"]


def test_served_one_ulp_off_offline(toy_root, cpu_harness, capsys,
                                    monkeypatch):
    """A served sum one ulp away from ``offline()``: within the float
    limit against the reference, and still a breach of the guarantee."""
    real = FeatureEngine.request_batch

    def nudged(self, rows, snapshot=None):
        out = real(self, rows, snapshot=snapshot)
        for f in out:
            f["spend_100d"] = np.nextafter(f["spend_100d"], np.float32(1e9))
        return out

    monkeypatch.setattr(FeatureEngine, "request_batch", nudged)
    line = run_cell(cpu_harness, capsys, "toy.serve_ingest")
    assert not line["correct"]
    checks = line["checks"]
    assert checks["float_gap"]["value"] <= checks["float_gap"]["limit"]
    assert checks["offline_mismatch"]["value"] > 0


def test_backfill_answer_altered(toy_root, cpu_harness, capsys, monkeypatch):
    real = FeatureEngine.offline

    def altered(self, tables=None):
        out = real(self, tables)
        out["product_count"] = out["product_count"] + 1
        return out

    monkeypatch.setattr(FeatureEngine, "offline", altered)
    line = run_cell(cpu_harness, capsys, "toy.backfill", seconds="1")
    assert not line["correct"]
    assert line["checks"]["exact_mismatch"]["value"] > 0


def test_acknowledged_write_lost(toy_root, cpu_harness, capsys,
                                 monkeypatch):
    real = FeatureEngine.ingest_many

    def lossy(self, table, rows):
        return real(self, table, rows[:-1])

    monkeypatch.setattr(FeatureEngine, "ingest_many", lossy)
    line = run_cell(cpu_harness, capsys, "toy.serve_ingest")
    assert not line["correct"]
