"""Bring-up check of the feature engine on TPU chips.

Drives the paper's Figure 1 script (``examples/quickstart.py``: a UNION
window over actions + orders, a 100-day window, ``distinct_count``,
``avg_cate_where``, ``topn_frequency``, a LAST JOIN and a scalar) through
the calls a deployment makes — ``FeatureEngine.bulk_load``,
``ingest_many``, ``request_batch`` and ``offline`` — over 1.5 M rows of
10,000 keys generated from ``--seed``, and holds every served feature
bitwise (``array_equal``) to the engine's own ``offline()``.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # key-sharded, four chips

One chip: first the compiled unit-fold Pallas kernel alone against the
staged fold on every leaf family (``examples/leaf_families.py``); then
serve 256 held-back requests (each its key's last action) with the
staged fold and compare with ``offline()``; then serve them again, and
run ``offline()`` again, with ``fused_fold=True``, which runs the kernel
and must match bit for bit.  Four chips: the same requests through a store
sharded one shard per chip, compared with a one-chip engine in the same
process; ``offline_sharded`` compared with ``offline``; serving again
after a shard is killed and healed from its replica.

Exits nonzero, before printing a result, when JAX finds no TPU.  The
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from examples.leaf_families import SQL as LEAF_SQL  # noqa: E402
from examples.quickstart import SQL  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.types import Table  # noqa: E402
from repro.data.synthetic import make_action_tables  # noqa: E402
from repro.serve.engine import FeatureEngine  # noqa: E402

# ~150 rows of history per key, every row inside the 100-day window
SIZES = dict(n_actions=1_000_000, n_orders=500_000, n_users=10_000,
             horizon_ms=20_000_000)
N_REQUESTS = 256
BATCH = 64
N_FRESH = 4 * 1024          # orders rows ingested after the bulk load
INGEST_BATCH = 1024
# unit-fold kernel parity: (rows per unit, units, one query per unit?)
PARITY_SHAPES = ((37, 3, False), (200, 9, False), (300, 64, True))


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


@dataclasses.dataclass
class Case:
    tables: Dict[str, Table]        # everything: the offline reference
    history: Dict[str, Table]       # bulk-loaded part
    fresh: List[dict]               # orders rows ingested afterwards
    requests: List[dict]            # one held-back action per key
    req_idx: np.ndarray             # their rows in tables["actions"]


def _subset(t: Table, keep: np.ndarray) -> Table:
    return Table(t.schema, {c: v[keep] for c, v in t.columns.items()},
                 dicts=t.dicts)


def make_case(seed: int, n_requests: int = N_REQUESTS,
              n_fresh: int = N_FRESH, **sizes) -> Case:
    """Tables from ``seed``; hold back each of ``n_requests`` distinct
    keys' LAST action (so one ``offline()`` is the reference for all of
    them) and the newest ``n_fresh`` orders rows (ingested live)."""
    tables = make_action_tables(seed=seed, **(sizes or SIZES))
    act, orders = tables["actions"], tables["orders"]
    users = act.columns["userid"]
    last = np.full(int(users.max()) + 1, -1)
    np.maximum.at(last, users, np.arange(len(act)))
    have = np.flatnonzero(last >= 0)
    if len(have) < n_requests:
        raise ValueError(f"{len(have)} keys < {n_requests} requests")
    keys = np.random.default_rng(seed).choice(have, n_requests,
                                              replace=False)
    req_idx = np.sort(last[keys])
    keep_act = np.ones(len(act), bool)
    keep_act[req_idx] = False
    keep_ord = np.arange(len(orders)) < len(orders) - n_fresh
    history = dict(tables, actions=_subset(act, keep_act),
                   orders=_subset(orders, keep_ord))
    fresh = [orders.row(i) for i in np.flatnonzero(~keep_ord)]
    return Case(tables, history, fresh, [act.row(i) for i in req_idx],
                req_idx)


def _leaves(engine: FeatureEngine):
    return jax.tree_util.tree_leaves(engine.store.tables)


def load(engine: FeatureEngine, case: Case) -> Dict[str, float]:
    """Bulk-load the history, then ingest the fresh orders in batches;
    each timed phase ends when the store arrays are ready."""
    t0 = time.perf_counter()
    for name, t in case.history.items():
        engine.bulk_load(name, t)
    jax.block_until_ready(_leaves(engine))
    t1 = time.perf_counter()
    for i in range(0, len(case.fresh), INGEST_BATCH):
        engine.ingest_many("orders", case.fresh[i:i + INGEST_BATCH])
    jax.block_until_ready(_leaves(engine))
    t2 = time.perf_counter()
    leaves = _leaves(engine)
    return {"load_s": t1 - t0, "ingest_s": t2 - t1,
            "store_bytes": sum(x.nbytes for x in leaves),
            "store_devices": sorted({str(d) for x in leaves
                                     for d in x.devices()})}


def serve(engine: FeatureEngine, requests: Sequence[dict],
          batch: int = BATCH) -> Dict[str, object]:
    """A warm-up batch (compiles), then every request in batches; each
    timed call returns host arrays, so it ends after the device."""
    t0 = time.perf_counter()
    engine.request_batch(requests[:batch])
    compile_s = time.perf_counter() - t0
    out: List[dict] = []
    steady = []
    for i in range(0, len(requests), batch):
        t0 = time.perf_counter()
        out += engine.request_batch(requests[i:i + batch])
        steady.append(time.perf_counter() - t0)
    feats = {k: np.stack([r[k] for r in out]) for k in out[0]}
    return {"compile_s": compile_s, "steady_s": steady, "feats": feats}


def check_equal(what: str, got: Dict[str, np.ndarray],
                want: Dict[str, np.ndarray]) -> None:
    """Every feature ``array_equal``; raise naming the first that is
    not."""
    if set(got) != set(want):
        raise SmokeFailure(f"{what}: features {sorted(got)} != "
                           f"{sorted(want)}")
    for k in sorted(want):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.shape != b.shape or not np.array_equal(a, b):
            bad = (np.argwhere(a != b)[:3].tolist()
                   if a.shape == b.shape else "shape")
            raise SmokeFailure(f"{what}: feature '{k}' differs at {bad}")
    print(f"{what}: {len(want)} features bitwise equal")


def _report(tag: str, stats: Dict[str, object]) -> None:
    for k, v in stats.items():
        if k != "feats":
            print(f"{tag} {k}={v}")


def one_chip(case: Case, capacity: int, batch: int = BATCH) -> None:
    """Staged serving vs ``offline()``, then fused serving and fused
    ``offline()`` vs staged."""
    staged = FeatureEngine(SQL, case.tables, capacity=capacity)
    _report("staged", load(staged, case))
    platform = jax.devices()[0].platform
    held = {d.platform for x in _leaves(staged) for d in x.devices()}
    if held != {platform}:
        raise SmokeFailure(f"store arrays sit on {held}, not {platform}")
    res = serve(staged, case.requests, batch)
    _report("staged", res)
    t0 = time.perf_counter()
    off = staged.offline()
    print(f"offline_s={time.perf_counter() - t0} "
          f"rows={sum(len(t) for t in case.tables.values())}")
    check_equal("staged serving vs offline()", res["feats"],
                {k: v[case.req_idx] for k, v in off.items()})
    del staged

    fused = FeatureEngine(SQL, case.tables, capacity=capacity,
                          fused_fold=True)
    _report("fused", load(fused, case))
    res_f = serve(fused, case.requests, batch)
    _report("fused", res_f)
    check_equal("fused serving vs staged serving", res_f["feats"],
                res["feats"])
    has_kernel = "tpu_custom_call" in fused.fused_serving_hlo(
        case.requests[:batch])
    print(f"fused executable has tpu_custom_call: {has_kernel}")
    if platform == "tpu" and not has_kernel:
        raise SmokeFailure("fused serving did not run the Pallas kernel")
    t0 = time.perf_counter()
    off_f = fused.offline()
    print(f"fused offline_s={time.perf_counter() - t0}")
    check_equal("fused offline() vs staged offline()", off_f, off)


def _unit_env(seed: int, r: int, n_invalid: int) -> Dict[str, jnp.ndarray]:
    """One padded unit of ``LEAF_SQL``'s table: sorted timestamps,
    prices and items, and an invalid tail of garbage rows that must
    fold as identity."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, 20_000, r)).astype(np.int32)
    price = rng.normal(2.0, 1.5, r).astype(np.float32)
    item = rng.integers(0, 9, r).astype(np.int32)
    valid = np.arange(r) < r - n_invalid
    price[~valid] = 99.0
    return {"ts": jnp.asarray(ts), "price": jnp.asarray(price),
            "item": jnp.asarray(item), "__valid__": jnp.asarray(valid)}


def kernel_parity(shapes=PARITY_SHAPES) -> None:
    """The unit-fold kernel against the jitted staged fold, bitwise, on
    every leaf family: for each (rows per unit, units, one query per
    unit?) a block of units, half of them with an invalid tail."""
    from repro.core import compile_script
    from repro.core.lowering import windows as W
    from repro.kernels.unit_fold import ops as uf_ops

    cs = compile_script(LEAF_SQL, distinct_hll_p=None)
    (members,) = W.group_windows(cs.windows)
    specs = [m.node.spec for m in members]
    leaves = {}
    for m in members:
        for k, leaf in W.unique_leaves(m.aggs).items():
            leaves.setdefault(k, leaf)
    for r, u, one_query in shapes:
        envs = [_unit_env(100 * r + i, r, 7 if i % 2 else 0)
                for i in range(u)]
        env_b = {k: jnp.stack([e[k] for e in envs]) for k in envs[0]}
        q = (jnp.asarray([[r - 1 - i % 5] for i in range(u)], jnp.int32)
             if one_query else None)
        staged = jax.jit(lambda e, qq: W.fold_unit(members, e, queries=qq))
        want = [staged(envs[i], None if q is None else q[i])
                for i in range(u)]
        t0 = time.perf_counter()
        got = jax.jit(lambda e, qq: uf_ops.unit_fold(
            specs, leaves, e, qq, order_by="ts", use_pallas=True))(env_b, q)
        jax.block_until_ready(got)
        dt = time.perf_counter() - t0
        n = 0
        for i in range(u):
            for mi, m in enumerate(members):
                for k in W.unique_leaves(m.aggs):
                    if not np.array_equal(np.asarray(got[mi][k][i]),
                                          np.asarray(want[i][mi][k])):
                        raise SmokeFailure(
                            f"unit-fold kernel differs from the staged "
                            f"fold: {m.node.spec.name}/{k} unit {i}, "
                            f"rows={r}")
                    n += 1
        print(f"kernel parity rows={r} units={u} "
              f"queries={1 if one_query else r}: {n} leaf folds bitwise "
              f"equal (first_call_s={dt}, compile included)")


def four_chip(case: Case, capacity: int, n_chips: int,
              batch: int = BATCH) -> None:
    """Key-sharded serving over ``n_chips`` devices against a one-device
    engine, ``offline_sharded`` against ``offline``, and serving after
    a shard kill + heal."""
    from repro.core import compile_script, parse
    from repro.distributed.sharding import key_shard_mesh

    ok, why = compile_script(parse(SQL), tables=case.tables) \
        .sharded_eligible()
    if not ok:
        raise SmokeFailure(f"script is not shardable by key: {why}")
    single = FeatureEngine(SQL, case.tables, capacity=capacity)
    _report("single", load(single, case))
    ref = serve(single, case.requests, batch)
    _report("single", ref)

    mesh = key_shard_mesh(n_chips)
    per_shard = 1 << (2 * capacity // n_chips - 1).bit_length()
    sharded = FeatureEngine(SQL, case.tables, capacity=per_shard,
                            mesh=mesh, replication=1)
    stats = load(sharded, case)
    _report("sharded", stats)
    placed = {d for x in _leaves(sharded) for d in x.sharding.device_set}
    platform = jax.devices()[0].platform
    if len(placed) != n_chips or any(d.platform != platform
                                     for d in placed):
        raise SmokeFailure(f"sharded store sits on "
                           f"{sorted(map(str, placed))}, not one shard "
                           f"per {platform} device")
    print(f"sharded store spread over {len(placed)} {platform} devices")
    res = serve(sharded, case.requests, batch)
    _report("sharded", res)
    check_equal("sharded serving vs one-device serving", res["feats"],
                ref["feats"])

    t0 = time.perf_counter()
    off = single.offline()
    t1 = time.perf_counter()
    off_sh = sharded.offline()
    t2 = time.perf_counter()
    print(f"offline_s={t1 - t0} offline_sharded_s={t2 - t1}")
    check_equal("offline_sharded vs offline", off_sh, off)

    shard = int(sharded.store.owner_of_keys(
        np.asarray([case.requests[0]["userid"]]))[0])
    sharded.kill_shard(shard)
    healed = sharded.heal()
    print(f"killed shard {shard}; healed in "
          f"{[r.recovery_s for r in healed]} s")
    res = serve(sharded, case.requests, batch)
    _report("healed", res)
    check_equal("serving after kill + heal vs one-device serving",
                res["feats"], ref["feats"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the key-sharded four-chip phase")
    args = ap.parse_args(argv)

    devs = jax.devices()
    d0 = devs[0]
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"device_count={len(devs)}")
    if d0.platform != "tpu":
        print(f"no TPU found: JAX's default backend is '{d0.platform}'",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    case = make_case(args.seed)
    rows = max(len(t) for t in case.tables.values())
    capacity = 1 << (rows - 1).bit_length()
    print(f"data_s={time.perf_counter() - t0} capacity={capacity} "
          f"rows={ {k: len(t) for k, t in case.tables.items()} }")
    if args.chips == 1:
        kernel_parity()
        one_chip(case, capacity)
    else:
        four_chip(case, capacity, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
