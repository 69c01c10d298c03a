"""The program's own spans (``repro.*``, ``src/repro/spans.py``) and the
per-layer metrics that read them, on traces recorded on the CPU.

A tiny ``FeatureEngine`` runs one ``offline()`` call, two ``ServeLoop``
flushes, a loop's ingest apply and one direct ``ingest_many``, inside a
``bench.window`` span.  ``testdata/cpu_trace_spans.xplane.pb`` holds
such a trace; regenerate it with
``JAX_PLATFORMS=cpu PYTHONPATH=src:. python bench/test_program_spans.py``.
"""

import math
import shutil
import tempfile
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr
from bench.harness import LayerView, load_module, trace_options

BENCH = Path(__file__).resolve().parent
TRACE = BENCH / "testdata" / "cpu_trace_spans.xplane.pb"

SQL = """
SELECT sum(price) OVER w3s AS s3, count(price) OVER w3s AS c3,
       max(price) OVER w10 AS mx10
FROM actions
WINDOW w3s AS (UNION orders PARTITION BY userid ORDER BY ts
               ROWS_RANGE BETWEEN 3s PRECEDING AND CURRENT ROW),
       w10 AS (PARTITION BY userid ORDER BY ts
               ROWS BETWEEN 10 PRECEDING AND CURRENT ROW)
"""
BATCH = 4

# span -> (its parent, the stats it carries)
SPANS = {
    "repro.serve.flush": ("bench.window", set()),
    "repro.serve.encode": ("repro.serve.flush", set()),
    "repro.serve.fetch": ("repro.serve.flush", set()),
    "repro.ingest.apply": ("bench.window", set()),
    "repro.ingest.wait": ("repro.ingest.apply", set()),
    "repro.offline.call": ("bench.window", set()),
    "repro.offline.fingerprint": ("repro.offline.call", set()),
    "repro.offline.plan": ("repro.offline.call", set()),
    "repro.offline.plan.group": ("repro.offline.plan", {"rows", "slots"}),
    "repro.offline.plan.sort": ("repro.offline.plan.group", set()),
    "repro.offline.scatter": ("repro.offline.call", set()),
}

READERS = ("offline_plan_s", "offline_scatter_s", "offline_unit_fill",
           "flush_encode_ms", "flush_fetch_ms", "ingest_host_ms",
           "loop_wait_share")


def scenario(trace_dir=None):
    """Build the engine, warm every shape, then run the measured part
    (traced into ``trace_dir`` when given).  Returns the outputs and the
    offline plan's ``GroupLowering``s."""
    import jax

    from repro.data.synthetic import make_action_tables
    from repro.serve import FeatureEngine
    from repro.serve.clock import VirtualClock
    from repro.serve.loop import ServeLoop

    from bench.system import shift_tables

    tables = make_action_tables(n_actions=240, n_orders=120, n_users=6,
                                horizon_ms=120_000, seed=5)
    new = make_action_tables(n_actions=48, n_orders=24, n_users=6,
                             horizon_ms=120_000, seed=6)
    act = [new["actions"].row(i) for i in range(48)]
    ords = [new["orders"].row(i) for i in range(24)]
    req = [tables["actions"].row(i) for i in range(0, 240, 9)]
    eng = FeatureEngine(SQL, {k: tables[k] for k in ("actions", "orders")},
                        capacity=1024, batch_size=BATCH)
    for name in ("actions", "orders"):
        eng.bulk_load(name, tables[name])
    loop = ServeLoop(eng, clock=VirtualClock(), batch_size=BATCH,
                     max_wait_ms=5.0)

    def serve(rows):
        for r in rows:
            loop.submit(r)
        return loop.flush()

    # warm: every program the measured part runs compiles here
    eng.offline(tables)
    serve(req[:BATCH])
    loop.ingest("actions", act[:16])
    loop.ingest("orders", ords[:8])
    loop.drain_ingest()
    eng.ingest_many("orders", ords[8:16])
    jax.block_until_ready(eng.store.tables)

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            off = eng.offline(shift_tables(tables, "ts", 1))
            first = serve(req[BATCH:2 * BATCH])
            loop.ingest("actions", act[16:32])
            loop.step()
            eng.ingest_many("orders", ords[16:24])
            second = serve(req[2 * BATCH:3 * BATCH])
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    lws = next(iter(eng.cs._offline_plan_cache.values()))[0]
    return {"offline": off, "first": first, "second": second}, lws


def _events(profile):
    """(name, start, end, stats) of the ``bench.`` and ``repro.`` host
    events, per host line."""
    out = {}
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in ln.events
                   if e.name.startswith(("bench.", "repro."))]
            if evs:
                out[(p.name, ln.name)] = evs
    return out


def _view(profile):
    return LayerView(tr.Reduced(profile), {}, {})


def _unit_fill(lws):
    rows = sum(int(b.sizes.sum()) for gl in lws for b in gl.blocks)
    slots = sum(b.idx.size for gl in lws for b in gl.blocks)
    return 100.0 * rows / slots


@pytest.fixture(scope="module")
def recorded():
    d = tempfile.mkdtemp()
    try:
        out, lws = scenario(d)
        profile = tr.load(tr.find_xplane(d))
    finally:
        shutil.rmtree(d)
    return out, lws, profile


@pytest.fixture(scope="module")
def untraced():
    return scenario(None)


def test_every_span_nests_in_its_parent_on_one_thread(recorded):
    (evs,) = _events(recorded[2]).values()     # one host line holds all
    by_name = {}
    for name, s, e, st in evs:
        by_name.setdefault(name, []).append((s, e, st))
    for name, (parent, keys) in SPANS.items():
        assert by_name.get(name), f"no {name} span"
        for s, e, st in by_name[name]:
            assert any(ps <= s and e <= pe
                       for ps, pe, _ in by_name[parent]), (name, parent)
            assert keys <= set(st), (name, st)


def test_span_counts_and_args(recorded):
    (evs,) = _events(recorded[2]).values()
    named = lambda n: [st for name, _, _, st in evs if name == n]  # noqa
    assert len(named("repro.serve.flush")) == 2
    assert len(named("repro.serve.encode")) == 4    # encode, then pad
    assert len(named("repro.serve.fetch")) == 2
    # the loop's apply and the direct write: one span each, each with
    # one capacity check
    assert len(named("repro.ingest.apply")) == 2
    assert len(named("repro.ingest.wait")) == 2
    assert len(named("repro.offline.call")) == 1
    assert len(named("repro.offline.fingerprint")) == 1
    assert len(named("repro.offline.plan")) == 1
    groups = named("repro.offline.plan.group")
    assert len(named("repro.offline.plan.sort")) == len(groups) == 2
    assert [{k: g[k] for k in ("rows", "slots")} for g in groups] \
        == [gl.fill() for gl in recorded[1]]


def test_outputs_bitwise_equal_with_and_without_a_trace(recorded,
                                                        untraced):
    got, want = recorded[0], untraced[0]
    assert set(got["offline"]) == set(want["offline"])
    for k, v in want["offline"].items():
        np.testing.assert_array_equal(np.asarray(got["offline"][k]),
                                      np.asarray(v))
    for part in ("first", "second"):
        assert len(got[part]) == len(want[part]) == BATCH
        for a, b in zip(got[part].values(), want[part].values()):
            for k in b:
                np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_the_saved_trace(metric, untraced):
    view = _view(tr.load(str(TRACE)))
    v = load_module(BENCH / "metrics" / f"{metric}.py").read(view)
    assert v is not None and math.isfinite(v) and v >= 0
    if metric == "offline_unit_fill":
        assert v == pytest.approx(_unit_fill(untraced[1]))
        assert 0 < v <= 100


def test_readers_on_a_fresh_trace(recorded):
    view = _view(recorded[2])
    read = {m: load_module(BENCH / "metrics" / f"{m}.py").read(view)
            for m in READERS}
    assert read["offline_unit_fill"] == pytest.approx(
        _unit_fill(recorded[1]))
    (win,) = view.trace.spans_named("bench.window")
    window_s = (win[2] - win[1]) * 1e-9
    for m in ("offline_plan_s", "offline_scatter_s"):
        assert 0 < read[m] < window_s
    for m in ("flush_encode_ms", "flush_fetch_ms", "ingest_host_ms"):
        assert 0 < read[m] < window_s * 1e3
    assert 0 < read["loop_wait_share"] < 100
    (evs,) = _events(recorded[2]).values()
    spans = lambda n: [(s, e) for name, s, e, _ in evs if name == n]  # noqa
    applies, waits = spans("repro.ingest.apply"), spans("repro.ingest.wait")
    self_ns = (sum(e - s for s, e in applies)
               - sum(e - s for s, e in waits)) / len(applies)
    assert read["ingest_host_ms"] == pytest.approx(self_ns * 1e-6)
    total = lambda *ns: sum(e - s for n in ns for s, e in spans(n))  # noqa
    assert read["loop_wait_share"] == pytest.approx(
        100 * total("repro.serve.fetch", "repro.ingest.wait")
        / total("repro.serve.flush", "repro.ingest.apply"))


def test_readers_find_nothing_without_program_spans():
    """A trace of a program without ``repro.`` spans (the old trace)
    reads None, so such a program's run leaves the metrics out."""
    view = _view(tr.load(str(BENCH / "testdata" / "cpu_trace.xplane.pb")))
    for m in READERS:
        assert load_module(BENCH / "metrics" / f"{m}.py").read(view) is None


def test_readers_raise_without_the_host_line():
    """A reduced trace that no longer keeps its host line is a fault of
    the harness, not a program without spans: the readers raise."""
    class NoHostLine:
        w0, w1 = 0, 1

    view = LayerView(NoHostLine(), {}, {})
    for m in READERS:
        with pytest.raises(AttributeError):
            load_module(BENCH / "metrics" / f"{m}.py").read(view)


def record(out: Path) -> None:
    """Record ``out`` on the CPU (what the file under testdata holds)."""
    with tempfile.TemporaryDirectory() as d:
        scenario(d)
        out.parent.mkdir(exist_ok=True)
        shutil.copy(tr.find_xplane(d), out)


if __name__ == "__main__":
    record(TRACE)
    print(TRACE, TRACE.stat().st_size)
