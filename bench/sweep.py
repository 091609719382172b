#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its traffic at each of several
rates in one process and print, per rate, the latency quantiles, the
rate answered, the deepest waiting line and the quantiles of a launch's
host time (hand-over to answers on the host).  Run once, on the chip,
when a cell's rate is chosen:

    python3 bench/sweep.py --workload deeplabv3-aspp-serve --seed 1 \\
        --seconds 10 --rates 60,80,100,120
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness, serving, traffic  # noqa: E402
from bench.drivers import serve_open  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    ctx = harness.load_cell(args.workload, args.seed, args.seconds, False,
                            T_START)
    harness.enable_compile_cache()
    try:
        harness.claim_chips(ctx)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    s = serving.build(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(ctx.traffic, rate_per_s=rate)
        due = traffic.open_schedule(tr, args.seed, args.seconds)
        order = traffic.payload_order(tr, args.seed, len(due))
        keep = serving.sample_mask(args.seed, len(due), 0.0)
        first = len(s.launch_s)
        w = serve_open.window(s, due, order, keep, args.seconds)
        lat = w["latency_s"]
        launch = sorted(s.launch_s[first:])
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "answered": w["answered"], "sheds": w["sheds"],
            "deepest": w["deepest"],
            "answered_per_s": w["answered"] / w["t_end"],
            "overrun_s": w["t_end"] - args.seconds,
            "p50_ms": float(sorted(lat)[len(lat) // 2] * 1e3),
            "p95_ms": serving.p95(lat) * 1e3,
            "launch_p50_ms": launch[len(launch) // 2] * 1e3,
            "launch_p95_ms": serving.p95(launch) * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
