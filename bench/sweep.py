"""Find the highest request rate a serving cell sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 2000,3000,4000 --seconds 10

Runs the cell's window once per rate in one process and prints, per
rate, the requests shed, p99 over each half of the window and the
generator's lag.  Sustained: nothing shed and the second half's p99 no
worse than the first's by much (the queue does not grow).  The rate a
cell offers is then fixed in its traffic file; the benchmark never
searches for one.  Exits nonzero where JAX finds no TPU.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    reg = harness.Registry()
    cell = reg.cell(args.workload)
    try:
        devices = harness.check_device(int(cell["chips"]))
    except harness.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx = harness.Context(reg, cell, args.seed, args.seconds, False,
                              time.perf_counter(), devices)
        ctx.mix.update(rate_per_s=rate, sample_requests=64)
        out = reg.module("modes", ctx.mix["mode"]).run(ctx)
        print(json.dumps({"rate": rate, "attempted": out.attempted,
                          "failed": out.failed, "metrics": out.metrics,
                          "p99_halves": out.layer["p99_halves"]}),
              flush=True)
        del out, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
