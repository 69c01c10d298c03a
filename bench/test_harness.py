"""The harness finds configurations, traffic mixes and per-layer
metrics by name, and refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def run_main(harness, capsys, *argv):
    rc = harness.main(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_no_tpu_no_result(tmp_path):
    """On the CPU the command exits nonzero and prints no result line,
    also from a tree that holds only BENCHMARK.json and bench/."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, tmp_path):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "fig1_dense.serve", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=root, env=env, capture_output=True,
            text=True, timeout=120)
        assert p.returncode != 0
        assert '"correct"' not in p.stdout


def test_added_files_found_by_name(toy_root, cpu_harness, capsys):
    """A new configuration, traffic mix and per-layer metric, added as
    files plus BENCHMARK.json entries, with no other edit."""
    bench = toy_root / "bench"
    shutil.copytree(bench / "configs" / "toy", bench / "configs" / "toy_b")
    cfg = json.loads((bench / "configs" / "toy_b" / "config.json")
                     .read_text())
    cfg["n_users"] = 16
    (bench / "configs" / "toy_b" / "config.json").write_text(
        json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "serve.json").read_text())
    mix.update(rate_per_s=150)
    (bench / "traffic" / "serve_slow.json").write_text(json.dumps(mix))
    (bench / "metrics" / "served.count.py").write_text(
        "def read(view):\n"
        "    return float(view.layer['loop_stats']['served'])\n")
    spec = json.loads((toy_root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="toy_b",
                                file="bench/configs/toy_b/config.json"))
    spec["workloads"].append({"name": "toy_b.serve_slow", "config": "toy_b",
                              "traffic": "serve_slow", "chips": 1,
                              "why": "CPU test"})
    spec["per_layer"].append({
        "name": "served.count", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serve loop",
        "moves": "p99_ms", "workloads": ["toy_b.serve_slow"]})
    for m in spec["end_to_end"]:
        if m["name"] == "p99_ms":
            m["workloads"].append("toy_b.serve_slow")
    (toy_root / "BENCHMARK.json").write_text(json.dumps(spec))

    rc, line = run_main(cpu_harness, capsys, "--workload",
                        "toy_b.serve_slow", "--seed", "77", "--seconds",
                        "1", "--trace", "1")
    assert rc == 0 and line["correct"]
    assert line["metrics"]["served.count"]["value"] >= line["attempted"]
    assert 100 <= line["attempted"] <= 200        # 150 requests/s, 1 s
    assert "p99_ms" not in line["metrics"]         # traced: per-layer only


def test_every_per_layer_metric_has_a_reader():
    """Each per-layer metric of each cell finds its reader by name, and
    moves an end-to-end metric that its cells report."""
    from bench.harness import Registry

    reg = Registry()
    for cell in reg.spec["workloads"]:
        e2e = {m["name"] for m in reg.metrics_for(cell["name"], "end_to_end")}
        for m in reg.metrics_for(cell["name"], "per_layer"):
            assert Path(reg.reader(m["name"]).__file__).is_file()
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_serve_ingest_tail_reader():
    """``p99_ms.ingest`` is the nearest-rank 99th percentile of every
    request's latency, and reads nothing where there are none."""
    from types import SimpleNamespace

    import numpy as np

    from bench.harness import load_module

    read = load_module(ROOT / "bench" / "metrics" / "p99_ms.ingest.py").read
    lat = np.random.default_rng(5).permutation(np.arange(1.0, 201.0))
    assert read(SimpleNamespace(layer={"latency_ms": lat})) == 198.0
    assert read(SimpleNamespace(layer={"latency_ms": np.array([7.5])})) \
        == 7.5
    assert read(SimpleNamespace(layer={"latency_ms": np.array([])})) is None
    assert read(SimpleNamespace(layer={})) is None
