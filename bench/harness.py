"""The benchmark's frame: finds a cell's files by name, holds the run to
the chip, hands the cell to its driver, reads the per-layer metrics and
prints the result line.

A driver (`bench/drivers/<driver>.py`) exposes `run(ctx) -> Outcome`.
It builds the cell, warms up, measures for `ctx.seconds` (traced when
`ctx.trace`), checks the timed path's answers against the reference and
returns what it measured.  A per-layer metric's reader
(`bench/metrics/<metric>.py`) exposes `read(outcome) -> float | None`;
None leaves the metric out of the line.  A model kind's module
(`bench/models/<kind>.py`, found by the configuration's `model.kind`)
holds all that is particular to that kind.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import functools
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
import types
from typing import Any, Dict, List, Optional, Tuple

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MODELS = BENCH / "models"


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


@dataclasses.dataclass
class Context:
    name: str
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    peaks: Dict = dataclasses.field(default_factory=dict)
    devices: List[Any] = dataclasses.field(default_factory=list)
    # JAX's compile and compile-cache events since start, by name
    compiles: Dict[str, float] = dataclasses.field(
        default_factory=collections.Counter)

    def setup_s(self) -> float:
        """Seconds from process start to now: call just before the first
        timed operation."""
        return time.monotonic() - self.t_start


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, Tuple[float, float]]
    correct: bool
    memory_peak_bytes: int
    window_s: float
    # what the per-layer readers read: counts, work, launch times, trace
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None


def load_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


def load_cell(name: str, seed: int, seconds: float, trace: bool,
              t_start: float) -> Context:
    cell = load_json(BENCH / "workloads" / f"{name}.json")
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return Context(name=name, cell=cell, config=config, traffic=traffic,
                   seed=seed, seconds=seconds, trace=trace,
                   t_start=t_start)


def cell_metrics(manifest: Dict, name: str, trace: bool
                 ) -> List[Dict]:
    """The metrics `BENCHMARK.json` has this cell report: its end-to-end
    metrics, or with a trace its per-layer ones."""
    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in manifest["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if m["moves"] in names and applies(m)]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the
    checkout, or where `JAX_COMPILATION_CACHE_DIR` says.  Every program
    is cached, however quickly it compiled, so that only a checkout's
    first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def count_compiles(ctx: Context) -> None:
    """Count JAX's compile-cache hits and misses and its compile and
    cache-read seconds into `ctx.compiles`, for the set-up report."""
    import jax.monitoring as mon

    def on_event(name, **_):
        ctx.compiles[name.rsplit("/", 1)[-1]] += 1

    def on_duration(name, secs, **_):
        if "compile" in name or "retrieval" in name:
            ctx.compiles[name.rsplit("/", 1)[-1]] += secs

    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)


def claim_chips(ctx: Context) -> None:
    """Hold the run to the TPU: no fallback to another platform."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is "
                     f"{devices[0].platform!r})")
    chips = int(ctx.cell["chips"])
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    ctx.peaks = peaks[kind]
    ctx.devices = devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of `devices`."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@contextlib.contextmanager
def profiled(enabled: bool, compiles: Optional[Dict[str, float]] = None):
    """Trace the block with the JAX profiler when `enabled`; yields a
    holder whose `.view` is the reduced trace once the block exits.
    Reports on standard error what JAX compiled inside the block, read
    from `compiles` (a `Context.compiles`): nothing should."""
    holder = types.SimpleNamespace(view=None)
    if compiles is not None:
        before = dict(compiles)
        try:
            with profiled(enabled) as inner:
                yield inner
        finally:
            new = {k: round(v - before.get(k, 0), 3)
                   for k, v in compiles.items() if v != before.get(k, 0)}
            print(f"window: compile events {new}", file=sys.stderr)
        return
    if not enabled:
        yield holder
        return
    import jax
    from bench import traces
    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # JAX's own host spans only
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
                yield holder
        finally:
            jax.profiler.stop_trace()
        holder.view = traces.load_dir(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _import_file(path: pathlib.Path, name: str) -> types.ModuleType:
    """Import `path` as module `name`; a missing file raises
    FileNotFoundError naming it."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return _import_file(BENCH / "metrics" / f"{metric}.py",
                        f"bench.metrics.{metric.replace('.', '_')}").read


def load_model(kind: str) -> types.ModuleType:
    """The module of a configuration's `model.kind`, `MODELS/<kind>.py`.
    A served kind's module gives `REQUEST_KIND` (the engine's request
    kind), `TINY` (a configuration small enough for the CPU),
    `params(model, seed)`, `payload_shape(model)`, `payload_pool(model,
    seed, n)`, `engine_kwargs(model, params)` (what `ConvServeEngine` is
    given), `forward(model, params, batch, num)` (the plain reference of
    one launch) and `ops(model, batch)` (its `flops.Op` list).  Each
    file is imported once a process."""
    return _model_file(MODELS / f"{kind}.py")


@functools.cache
def _model_file(path: pathlib.Path) -> types.ModuleType:
    return _import_file(path, f"bench.models.{path.stem.replace('.', '_')}")


def model_of(ctx: Context) -> types.ModuleType:
    """The module of the cell's configuration's model kind."""
    return load_model(ctx.config["model"]["kind"])


def device_record(ctx: Context, out: Outcome) -> Dict:
    import jax
    d = jax.devices()[0]
    rec = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": out.memory_peak_bytes}
    if ctx.trace and out.trace is not None:
        rec["busy_s"] = out.trace.busy_s(len(ctx.devices))
        rec["window_s"] = out.window_s
    return rec


def result_line(ctx: Context, out: Outcome, metrics: List[Dict]) -> Dict:
    values = {}
    for m in metrics:
        if ctx.trace:
            v = load_reader(m["name"])(out)
            if v is None:
                continue
        else:
            v = out.end_to_end[m["name"]]
        values[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": values,
            "device": device_record(ctx, out)}
    if ctx.trace and out.trace is not None:
        line["breakdown"] = out.trace.breakdown()
    line["checks"] = {k: {"value": _finite(v), "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def _finite(v: float) -> float:
    """JSON has no infinity: a check that found no finite answer reads
    as the largest double."""
    return v if math.isfinite(v) else sys.float_info.max


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    manifest = load_json(ROOT / "BENCHMARK.json")
    ctx = load_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start)
    metrics = cell_metrics(manifest, args.workload, ctx.trace)
    enable_compile_cache()
    count_compiles(ctx)
    try:
        claim_chips(ctx)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 3
    driver = importlib.import_module(f"bench.drivers.{ctx.cell['driver']}")
    out = driver.run(ctx)
    line = result_line(ctx, out, metrics)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out.correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
