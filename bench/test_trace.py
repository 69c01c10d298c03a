"""The trace reduction, on a small trace recorded on the CPU.

``testdata/cpu_trace.xplane.pb`` holds one ``bench.window`` span with
three ``bench.flush`` spans, each running a jitted matmul, and sleeps
between them.  On the CPU the operations run on the XLA client's
threads of the host plane; the test selects those as the "device", the
way a TPU trace's ``XLA Ops`` line is selected by default.  Regenerate
with
``JAX_PLATFORMS=cpu PYTHONPATH=. python bench/test_trace.py``.
"""

import time
from pathlib import Path

import pytest

from bench import trace as tr

TRACE = Path(__file__).with_name("testdata") / "cpu_trace.xplane.pb"
N_FLUSH = 3
SLEEP_S = 0.02


def cpu_ops(profile):
    """The CPU client's operation events, standing in for a device; the
    matmul, one per call, stands in for the program's module."""
    client = lambda n: n.startswith("tf_XLAPjRtCpuClient")  # noqa: E731
    return tr.Reduced(
        profile, plane=lambda n: n == "/host:CPU", line=client,
        module_line=client, module_event=lambda n: n == "dot_general.1",
        program_of=lambda n: "<lambda>",
        event=lambda n: not n.startswith(("ThreadpoolListener", "end:",
                                          "ThunkExecutor")))


@pytest.fixture(scope="module")
def reduced():
    return cpu_ops(tr.load(str(TRACE)))


def test_spans_found(reduced):
    assert len(reduced.spans_named("bench.flush")) == N_FLUSH
    assert reduced.window_s > N_FLUSH * SLEEP_S


def test_busy_lies_inside_the_flushes(reduced):
    pairs = reduced.busy_in_spans("bench.flush")
    assert all(0 < busy <= span for span, busy in pairs)
    in_flush = sum(busy for _, busy in pairs)
    # the sleeps between flushes run nothing
    assert reduced.busy_s() == pytest.approx(in_flush, rel=1e-6)
    assert 0 < reduced.busy_s() < reduced.window_s - N_FLUSH * SLEEP_S


def test_programs_launched_in_each_flush(reduced):
    rows = reduced.launched_in("bench.flush")
    assert len(rows) == N_FLUSH
    for span, device, until_done in rows:
        assert 0 < device and span <= until_done
    # nothing launched between the flushes
    assert sum(r[1] for r in rows) == pytest.approx(
        sum(e - s for v in reduced.modules.values() for _, s, e in v)
        * 1e-9)
    assert [d[0] for d in reduced.dispatches] == ["<lambda>"] * N_FLUSH
    assert reduced.programs()["<lambda>"][0] == N_FLUSH


def test_top_ops_and_idle_gaps(reduced):
    ops = reduced.top_ops()
    assert ops and any("dot" in name for name, _ in ops)
    assert all(0 < s <= reduced.busy_s() * (1 + 1e-9) for _, s in ops)
    gaps = reduced.idle_gaps()
    # the host was asleep in the window, outside any flush
    assert gaps[0][0] == "bench.window"
    idle = reduced.window_s - reduced.busy_s()
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)


def test_no_device_plane_reads_nothing():
    red = tr.Reduced(tr.load(str(TRACE)))   # TPU selectors, CPU trace
    assert red.busy == {} and red.busy_s() == 0.0


class _Ev:
    def __init__(self, name, start_ms, end_ms):
        self.name = name
        self.start_ns = start_ms * 1e6
        self.duration_ns = (end_ms - start_ms) * 1e6


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


class _Profile:
    def __init__(self, planes):
        self.planes = [_Plane(*p) for p in planes]


def test_queued_merge_is_its_dispatchers():
    """Two ingest merges queued on the device run on into the next
    flush; the flush's own program runs after them.  Each program counts
    for the span that dispatched it, not the one in which it starts."""
    device = [("jit_insert_many(7)", 10.5, 25), ("jit_insert_many(7)", 25, 40),
              ("jit__online_fn(3)", 40, 42), ("jit_copy(9)", 50, 51)]
    host = [("bench.window", 0, 100), ("bench.ingest", 10, 11),
            ("PjitFunction(insert_many)", 10.2, 10.4),
            ("bench.ingest", 11, 12),
            ("PjitFunction(insert_many)", 11.2, 11.4),
            ("PjitFunction(insert_many)", 11.25, 11.35),   # nested
            ("bench.flush", 12, 45),
            ("PjitFunction(_online_fn)", 13, 13.5)]
    red = tr.Reduced(_Profile([
        ("/device:TPU:0", [("XLA Modules", device), ("XLA Ops", device)]),
        ("/host:CPU", [("main/1", host)])]))
    (flush,) = red.launched_in("bench.flush")
    assert flush == pytest.approx((0.033, 0.002, 0.033))
    a, b = red.launched_in("bench.ingest")
    assert a == pytest.approx((0.001, 0.0145, 0.015))
    assert b == pytest.approx((0.001, 0.015, 0.029))
    progs = red.programs()
    assert progs["insert_many"] == pytest.approx([2, 0.0295, 0.0])
    # a module that no traced dispatch accounts for counts for no span
    assert progs["copy"] == pytest.approx([0, 0.0, 0.001])


@pytest.mark.parametrize("name,program", [
    ("jit__online_fn(123)", "_online_fn"), ("jit_insert_many", "insert_many"),
    ("fusion.3", "fusion.3")])
def test_program_of_module(name, program):
    assert tr.program_of_module(name) == program


@pytest.mark.parametrize("spans,lo,hi,union,covered", [
    ([("a", 0, 10), ("b", 5, 12), ("c", 20, 30)], 0, 100,
     [(0, 12), (20, 30)], 22),
    ([("a", 0, 10), ("b", 2, 3)], 5, 25, [(0, 10)], 5),
    ([("a", 10, 20), ("b", 30, 40)], 15, 35, [(10, 20), (30, 40)], 10),
    ([], 0, 10, [], 0),
])
def test_union_and_covered(spans, lo, hi, union, covered):
    merged = tr.union(spans)
    assert merged == union
    assert tr.covered(merged, lo, hi) == covered


def record(out: Path) -> None:
    """Record ``out`` on the CPU (what the file under testdata holds)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from bench.harness import trace_options

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=trace_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(N_FLUSH):
                time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.flush"):
                    f(x).block_until_ready()
            time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        out.parent.mkdir(exist_ok=True)
        shutil.copy(tr.find_xplane(d), out)


if __name__ == "__main__":
    record(TRACE)
    print(TRACE, TRACE.stat().st_size)
