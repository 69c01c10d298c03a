"""A configuration that states its key-sharded layout: one shard per chip
of the cell, the sharded engine on a mesh of exactly the cell's
devices, the sharded batch path's pad classes warmed; and a configuration
without the key builds the engine as before."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import system
from bench.conftest import TOY, make_toy_root
from bench.data import action_tables as data

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CELL = "toy.shard4.serve"   # the real cell, as make_toy_root names it

# the harness in a child that sees four CPU devices, steered onto them
# as the ``cpu_harness`` fixture steers it onto one
CHILD = """
import sys
from pathlib import Path
import jax
from bench import harness, peaks, system
kind = jax.devices()[0].device_kind
peaks.PEAKS[kind] = peaks.PEAKS["TPU v5 lite"]
harness.check_device = lambda chips: jax.devices()[:chips]
system.enable_compile_cache = lambda: "off"
harness.ROOT = Path(sys.argv[1])
if sys.argv[2] == "misrouted":
    # each request answered from shard 0, whichever shard owns its key
    import numpy as np
    from repro.storage.timestore import StoreSnapshot
    StoreSnapshot.owner_of_keys = lambda self, keys: np.zeros(
        len(np.atleast_1d(keys)), np.int64)
trace = "1" if sys.argv[2] == "traced" else "0"
sys.exit(harness.main(["--workload", sys.argv[3], "--seed", "2147483659",
                       "--seconds", "2", "--trace", trace]))
"""


def add_shard_cell(root: Path, shards: int = 4, chips: int = 4) -> None:
    """A toy copy of ``fig1_sparse_shard4`` under the toy serving mix."""
    bench = root / "bench"
    cfg = json.loads((BENCH / "configs" / "fig1_sparse_shard4" /
                      "config.json").read_text())
    cfg.update(TOY, shards=shards, capacity=1024)
    cdir = bench / "configs" / "toy_shard4"
    cdir.mkdir()
    (cdir / "config.json").write_text(json.dumps(cfg))
    (cdir / "script.sql").write_text(
        (BENCH / "configs" / "fig1_sparse_shard4" / "script.sql")
        .read_text())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "toy_shard4",
                              "traffic": "serve", "chips": chips,
                              "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def run_child(root: Path, case: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", CHILD, str(root), case, CELL],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout, json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture
def shard_root(tmp_path):
    root = make_toy_root(tmp_path)
    add_shard_cell(root)
    return root


def test_sharded_cell_on_four_devices(shard_root):
    out, line = run_child(shard_root, "sound")
    assert line["correct"] and line["failed"] == 0
    assert line["checks"]["offline_mismatch"]["value"] == 0
    assert line["device"]["count"] == 4
    # the cell's own tail metric: the serve mode's p99_ms, under its name
    assert set(line["metrics"]) == {"p99_ms.sharded", "setup_s"}
    assert "compiles_in_window=0 " in out
    assert "store arrays on 4 devices" in out
    # the warm-up ran online_sharded_batch's classes, not online_batch's
    assert "(static retrace bound): [1, 2, 4]" in out


def test_misrouted_requests_are_not_correct(shard_root):
    """The exchange between chips left out: a request answered from a
    shard that does not own its key."""
    _, line = run_child(shard_root, "misrouted")
    assert not line["correct"]
    assert line["checks"]["exact_mismatch"]["value"] > 0


def test_shards_other_than_chips_refused(toy_root, cpu_harness, capsys):
    add_shard_cell(toy_root, shards=2, chips=1)
    rc = cpu_harness.main(["--workload", CELL, "--seed", "1", "--seconds",
                           "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc == 1
    assert '"correct"' not in cap.out
    assert "states 2 shards" in cap.err


@pytest.mark.parametrize("config", ["fig1_dense", "fig1_sparse"])
def test_unsharded_config_builds_one_store(config):
    """Without ``shards`` the engine is built as before: one unsharded
    ``OnlineStore`` at the configuration's capacity, whatever devices
    the cell passes."""
    import jax

    from repro.storage.timestore import OnlineStore

    d = BENCH / "configs" / config
    cfg = dict(json.loads((d / "config.json").read_text()), **TOY)
    assert "shards" not in cfg
    tables = system.make_tables(data.SCHEMA, data.DICTS,
                                data.history(cfg, 5))
    engine = system.build_engine((d / "script.sql").read_text(), tables,
                                 cfg, jax.devices()[:1])
    assert not engine.sharded
    assert type(engine.store) is OnlineStore
    assert engine.store.capacity == cfg["capacity"]


def test_sharded_cell_traced(shard_root):
    """A traced run reads the routing metric and the serving metrics
    under their sharded names (those that need a device plane read
    nothing on the CPU and are left out)."""
    _, line = run_child(shard_root, "traced")
    assert line["correct"] and line["device"]["count"] == 4
    m = line["metrics"]
    assert 100 <= m["shard_route_skew"]["value"] <= 400
    assert 0 < m["batch_fill.sharded"]["value"] <= 100
    assert m["queue_wait_ms.p99.sharded"]["value"] >= 0
    assert not any(k.startswith("p99_ms") for k in m)


def _view(counts):
    return SimpleNamespace(trace=None, layer={} if counts is None else
                           {"shard_counts": np.asarray(counts)})


@pytest.mark.parametrize("counts, want", [
    ([[4, 4, 4, 4]], 100.0),                     # even
    ([[16, 0, 0, 0]], 400.0),                    # one shard owns them all
    ([[3, 1, 2, 2], [1, 1, 1, 1]], 100.0 * 4 * (3 + 1) / 12),
    ([[0, 0, 0, 0], [2, 2, 0, 0]], 200.0),       # an empty flush
    ([[5, 3], [1, 1]], 100.0 * 2 * 6 / 10),      # two shards
])
def test_shard_route_skew(counts, want):
    from bench.harness import load_module

    read = load_module(BENCH / "metrics" / "shard_route_skew.py").read
    assert read(_view(counts)) == pytest.approx(want)


def test_shard_route_skew_needs_routed_requests():
    from bench.harness import load_module

    read = load_module(BENCH / "metrics" / "shard_route_skew.py").read
    assert read(_view(None)) is None             # an unsharded cell
    assert read(_view(np.zeros((0, 4), int))) is None
    assert read(_view([[0, 0, 0, 0]])) is None


@pytest.mark.parametrize("name, want", [
    ("p99_ms", "p99_ms"),                        # its own
    ("p99_ms.sharded", "p99_ms"),                # qualifies a base
    ("a.b.c", "a.b"),                            # the longest base
    ("nothing.sharded", "nothing.sharded"),      # none: itself
])
def test_unqualified(name, want):
    from bench.harness import unqualified

    assert unqualified(name, {"p99_ms", "a.b", "a"}.__contains__) == want


def test_sharded_readers_are_their_bases():
    """Every per-layer metric of the sharded cell has a reader: its own
    file, or the file of the serving metric it qualifies."""
    from bench.harness import Registry

    reg = Registry()
    names = [m["name"] for m in reg.metrics_for("fig1_sparse.shard4.serve",
                                                "per_layer")]
    assert "shard_route_skew" in names and len(names) == 10
    for n in names:
        base = n[:-len(".sharded")] if n.endswith(".sharded") else n
        assert (reg.reader(n).__file__
                == str(BENCH / "metrics" / f"{base}.py"))
