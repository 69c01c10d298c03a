"""The benchmark's harness: one cell, one seed, one measured window.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds the rest by name:

* ``bench/configs/<config>/config.json``: the deployment (sizes, store,
  guarantees, the limits of its comparisons), beside ``script.sql``;
* ``bench/traffic/<traffic>.json``: the mix, whose ``mode`` names the
  window mode ``bench/modes/<mode>.py``;
* ``bench/data/<data>.py`` and ``bench/references/<reference>.py``, as
  the configuration names them;
* ``bench/metrics/<metric>.py``: the reader of each per-layer metric.

A metric named ``<base>.<qualifier>`` that has no reading or reader of
its own is ``<base>``, reported under its own name: so cells that need
their own bound, or whose per-layer metrics move that metric, split a
metric by data alone (``p99_ms.sharded`` is the serve mode's
``p99_ms``; ``batch_fill.sharded`` reads as ``batch_fill``).

Standard output ends with one JSON line; standard error ends with the
numbers that decided ``correct``, each beside its limit.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The cell cannot run here; no result is printed."""


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import a benchmark file by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_file_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unqualified(name: str, has) -> str:
    """``name`` where ``has(name)``, else the longest base of it
    (``a.b.c``, ``a.b``, ``a``) for which ``has`` holds; ``name`` where
    none does."""
    base = name
    while not has(base) and "." in base:
        base = base.rsplit(".", 1)[0]
    return base if has(base) else name


class Registry:
    """What ``BENCHMARK.json`` and the files under ``bench/`` name."""

    def __init__(self, root: Optional[Path] = None,
                 bench: Optional[Path] = None):
        self.root = Path(root or ROOT)
        self.bench = Path(bench or self.root / "bench")
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"no BENCHMARK.json at {self.root}")
        self.spec = json.loads(path.read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        d = self.bench / "configs" / name
        cfg = json.loads((d / "config.json").read_text())
        cfg["sql"] = (d / cfg["script"]).read_text()
        return cfg

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.bench / kind / f"{name}.py")

    def reader(self, metric: str) -> ModuleType:
        """The reader of per-layer metric ``metric``."""
        return self.module("metrics", unqualified(
            metric, lambda n: (self.bench / "metrics" / f"{n}.py").is_file()))

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Outcome:
    """What a window mode hands back."""
    metrics: Dict[str, float]             # end-to-end, by name
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]  # name -> (value, limit)
    memory_peak_bytes: int
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Context:
    """One run's settings, and the hooks a window mode calls."""

    def __init__(self, reg: Registry, cell: dict, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 devices: list):
        self.reg, self.cell = reg, cell
        self.cfg = reg.config(cell["config"])
        if self.cfg.get("shards", cell["chips"]) != cell["chips"]:
            raise BenchError(f"configuration {cell['config']!r} states "
                             f"{self.cfg['shards']} shards, one per chip, "
                             f"and the cell has {cell['chips']} chips")
        self.mix = reg.traffic(cell["traffic"])
        self.data = reg.module("data", self.cfg["data"])
        self.reference = reg.module("references", self.cfg["reference"])
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.devices = devices
        # also read the lower-precision control (bench/calibrate.py)
        self.control = False
        self.compiles = 0
        self._counting = False
        self._trace_dir: Optional[tempfile.TemporaryDirectory] = None

    # -- compilation inside the window ---------------------------------
    def _on_event(self, event: str, *_a, **_k) -> None:
        if self._counting and event.endswith("jaxpr_to_mlir_module_duration"):
            self.compiles += 1

    def install_compile_counter(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @contextlib.contextmanager
    def window(self):
        """The measured window and what follows it before the
        comparison: counts the programs lowered inside it and, in a
        traced run, traces it.  The mode marks the window itself with
        a ``bench.window`` span, which the trace reduction measures."""
        import jax

        self.compiles, self._counting = 0, True
        gc_pauses = []         # (generation, seconds) of each collection
        gc_start = 0.0

        def on_gc(phase, info):
            nonlocal gc_start
            if phase == "start":
                gc_start = time.perf_counter()
            else:
                gc_pauses.append((info["generation"],
                                  time.perf_counter() - gc_start))

        import gc
        gc.callbacks.append(on_gc)
        if self.trace:
            self._trace_dir = tempfile.TemporaryDirectory()
            jax.profiler.start_trace(self._trace_dir.name,
                                     profiler_options=trace_options())
        try:
            yield
        finally:
            self._counting = False
            gc.callbacks.remove(on_gc)
            if self.trace:
                jax.profiler.stop_trace()
            worst = max(gc_pauses, key=lambda g: g[1], default=(None, 0.0))
            print(f"gc in window: {len(gc_pauses)} collections, "
                  f"{sum(1 for g in gc_pauses if g[0] == 2)} of generation "
                  f"2, longest {worst[1] * 1e3} ms (generation {worst[0]})",
                  flush=True)

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce_trace(self):
        from bench import trace as tr

        try:
            return tr.Reduced(tr.load(tr.find_xplane(self._trace_dir.name)))
        finally:
            self._trace_dir.cleanup()

    def memory_peak_bytes(self) -> int:
        """The peak of the fullest chip; prints every chip's."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        print(f"memory_peak_bytes per device: {peaks}", flush=True)
        return int(max(peaks))


def trace_options():
    """Device operations and annotated host spans; no Python call
    tracing, which would slow the host code it measures several
    times over."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def check_device(chips: int):
    """The devices the cell runs on; raises where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"device_count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX's default backend is "
                         f"'{d0.platform}'")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def run_cell(ctx: Context) -> dict:
    """Run the cell's window mode and build the result line."""
    from bench.peaks import peaks_for

    kind = ctx.devices[0].device_kind
    try:
        peaks = peaks_for(kind)
    except KeyError as e:
        raise BenchError(str(e)) from None
    ctx.install_compile_counter()
    mode = ctx.reg.module("modes", ctx.mix["mode"])
    out: Outcome = mode.run(ctx)
    print(f"compiles_in_window={ctx.compiles} (0 expected)", flush=True)
    name = ctx.cell["name"]
    result: Dict[str, Any] = {}
    if ctx.trace:
        red = ctx.reduce_trace()
        print(f"trace layout: {json.dumps(red.layout)}", flush=True)
        print(f"trace programs (dispatches, paired device s, unpaired "
              f"device s): {json.dumps(red.programs())}", flush=True)
        view = LayerView(red, out.layer, peaks)
        metrics = {}
        for m in ctx.reg.metrics_for(name, "per_layer"):
            v = ctx.reg.reader(m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result_device_extra = {"busy_s": red.busy_s(),
                               "window_s": red.window_s}
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
    else:
        metrics = {}
        for m in ctx.reg.metrics_for(name, "end_to_end"):
            key = unqualified(m["name"], out.metrics.__contains__)
            if key not in out.metrics:
                raise BenchError(f"the run measured no {m['name']}")
            metrics[m["name"]] = {"value": out.metrics[key],
                                  "unit": m["unit"]}
        result_device_extra = {}
    correct = all(v <= lim for v, lim in out.checks.values())
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics,
            "device": dict({"platform": ctx.devices[0].platform,
                            "kind": kind, "count": len(ctx.devices),
                            "memory_peak_bytes": out.memory_peak_bytes},
                           **result_device_extra)}
    line.update(result)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


class LayerView:
    """What a per-layer metric reader reads: the reduced trace, the
    window mode's own records and the chip's peaks."""

    def __init__(self, reduced, layer: Dict[str, Any], peaks: dict):
        self.trace = reduced
        self.layer = layer
        self.peaks = peaks


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        reg = Registry()
        cell = reg.cell(args.workload)
        devices = check_device(int(cell["chips"]))
        ctx = Context(reg, cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices)
        line = run_cell(ctx)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in line["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
