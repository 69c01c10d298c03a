"""Readings that set the limits of ``correct``: the program's and the
lower-precision control's, over many seeds, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as ``bench/run.py`` does (set-up, a window
at the cell's own load, the comparison), and also puts the reference
computed in bfloat16 in the program's place.  It prints one JSON line
per seed: ``program`` and ``control``, each ``[exact_mismatch,
float_gap]``, and the other checks.  The benchmark's own runs never
compute the control.  Exits nonzero where JAX finds no TPU.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    reg = harness.Registry()
    cell = reg.cell(args.workload)
    try:
        devices = harness.check_device(int(cell["chips"]))
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = harness.Context(reg, cell, seed, args.seconds, False,
                              time.perf_counter(), devices)
        ctx.control = True
        mode = reg.module("modes", ctx.mix["mode"])
        out = mode.run(ctx)
        print(json.dumps({
            "seed": seed,
            "checks": {k: v for k, (v, _) in out.checks.items()},
            "program": [out.checks["exact_mismatch"][0],
                        out.checks["float_gap"][0]],
            "control": list(out.layer["control"]),
            "metrics": out.metrics}), flush=True)
        del out, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
