#!/usr/bin/env python3
"""Chip benchmark of the EcoFlow conv stack.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of this machine: builds
the cell's model from the seed, warms up every shape its traffic uses,
measures for `--seconds`, checks what the timed path produced against
the plain reference (`bench/models/<kind>.py` over `bench/reference.py`),
and prints one JSON line as the last line of standard output.
`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics from a profiler trace of the window.  Everything is found by name: the cell in
`bench/workloads/`, its configuration in `bench/configs/`, its traffic
in `bench/traffic/`, its model kind's module in `bench/models/`, its
driver in `bench/drivers/` and each per-layer metric's reader in
`bench/metrics/`.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]  # not bench/ itself

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
