#!/usr/bin/env python3
"""The control of a serving cell's `correct`: the reference computed in
bfloat16, the precision below the configuration's, put in the program's
place for the requests a run compares, and judged by the same checks and
verdict as a run.  Its readings set the upper end of the cell's limits;
it must come out as not correct.  Runs on the chip at the cell's own
sizes, and prints one JSON line per seed:

    python3 bench/control.py --workload dcgan32-gen --requests 200000 \\
        --seeds 1,2,3
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, serving, traffic  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, required=True,
                    help="requests a run's window serves")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.load_cell(args.workload, seed, 0.0, False, T_START)
        harness.enable_compile_cache()
        try:
            harness.claim_chips(ctx)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        model, tr = ctx.config["model"], ctx.traffic
        pool = np.asarray(harness.model_of(ctx).payload_pool(
            model, seed, int(tr["payload_pool"])))
        order = traffic.payload_order(tr, seed, args.requests)
        keep = serving.sample_mask(seed, args.requests,
                                   ctx.cell["check"]["share"])
        ids = np.flatnonzero(keep).tolist()
        errs = serving.control_error(ctx, ids,
                                     serving.payload_fn(pool, order))
        checks = serving.checks(ctx, errs, 0)
        for k, (v, lim) in checks.items():
            print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
        print(json.dumps({"seed": seed, "compared": len(ids),
                          "correct": serving.verdict(checks),
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
