"""The NumPy reference of the Figure 1 script against the engine, on the
CPU at toy size: served batches with an ingest batch applied between
two flushes, and ``offline()``.  The bfloat16 control fails the same
comparison."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import compare, system
from bench.data import action_tables as data
from bench.references import fig1

CFG = dict(json.loads((Path(__file__).parent / "configs" / "fig1_dense" /
                       "config.json").read_text()),
           n_users=12, n_actions=600, n_orders=300, horizon_ms=120_000,
           capacity=2048)
SQL = (Path(__file__).parent / "configs" / "fig1_dense" /
       "script.sql").read_text()
LIMIT = CFG["limits"]["float_gap"]
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def served():
    """Two flushes of 8 requests through a ServeLoop, an ingest batch of
    actions applied between them; acked rows read before each step."""
    hist = data.history(CFG, SEED)
    (batch,) = data.ingest_stream(CFG, SEED, 1, 16, ["actions"])
    req = data.requests(CFG, SEED, 16, 16)
    # the second flush asks for users the ingest batch wrote to
    req["userid"][8:] = batch["userid"][:8]
    tables = system.make_tables(data.SCHEMA, data.DICTS, hist)
    engine = system.build_engine(SQL, tables, CFG)
    for name, t in tables.items():
        engine.bulk_load(name, t)
    loop = system.build_loop(engine, dict(
        slo_ms=25, max_wait_ms=5, batch_size=8, max_queue=64,
        ingest_queue_rows=64))
    rows = data.client_rows(req)
    acked = np.zeros(16, np.int64)
    out = {}
    for half in (0, 1):
        ids = [loop.submit(r, now=0.0) for r in rows[8 * half:8 * half + 8]]
        acked[8 * half:8 * half + 8] = loop.stats["ingest_rows"]
        got = loop.step(now=0.0)
        out.update({i + 8 * half: got[rid] for i, rid in enumerate(ids)})
        if half == 0:
            loop.ingest("actions", data.client_rows(batch), now=0.0)
            assert loop.step(now=0.0) == {}        # applies, no flush
    feats = compare.stack_rows([out[i] for i in range(16)], fig1.FEATURES)
    return hist, batch, req, acked, feats, engine


def test_ingest_acknowledged_between_flushes(served):
    hist, batch, req, acked, feats, _ = served
    assert acked.tolist() == [0] * 8 + [16] * 8
    ref = fig1.Reference(hist, [("actions", batch)])
    want = ref.features(req, acked)
    assert compare.against_reference(feats, want, fig1.FEATURES)[0] == 0
    assert compare.against_reference(feats, want, fig1.FEATURES)[1] <= LIMIT
    # the bookkeeping matters: with nothing acknowledged the second
    # flush's 100-day sums would be wrong
    stale = ref.features(req, np.zeros(16, np.int64))
    assert not np.allclose(stale["spend_100d"][8:], want["spend_100d"][8:])


def test_offline_against_reference(served):
    hist, _, _, _, _, engine = served
    off = engine.offline()
    idx = np.arange(0, CFG["n_actions"], 5)
    q = {c: v[idx] for c, v in hist["actions"].items()}
    want = fig1.Reference(hist).features(q)
    bad, gap = compare.against_reference({k: v[idx] for k, v in off.items()},
                                         want, fig1.FEATURES)
    assert bad == 0 and gap <= LIMIT
    # the LAST JOIN's point-in-time choice is exercised: some rows see
    # no profile row yet, some see one of two different ages
    assert (want["age"] == 0).any() and len(np.unique(want["age"])) > 2


def test_lower_precision_control_fails(served):
    hist, batch, req, acked, feats, _ = served
    want = fig1.Reference(hist, [("actions", batch)]).features(req, acked)
    low = fig1.Reference(hist, [("actions", batch)], lowp=True)
    ctl = {k: np.asarray(v, np.float32)
           for k, v in low.features(req, acked).items()}
    bad, gap = compare.against_reference(ctl, want, fig1.FEATURES)
    assert bad > 0 and gap > LIMIT


def test_bitwise_mismatch_counts_rows():
    a = {"x": np.zeros((4, 2), np.float32), "y": np.ones(4, np.float32)}
    b = {"x": a["x"].copy(), "y": a["y"].copy()}
    assert compare.bitwise_mismatch(a, b) == 0
    b["x"][1, 1] = -0.0          # equal as a float, not as bits
    b["y"][3] = np.nextafter(np.float32(1), np.float32(2))
    assert compare.bitwise_mismatch(a, b) == 2
