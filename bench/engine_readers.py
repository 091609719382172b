"""The arithmetic of the per-layer metrics read from the serving
engine's own spans (`engine.*`, written by
`src/repro/serve/conv_engine.py` into the profiler's trace, on the
device trace's clock).  Each takes the run's `harness.Outcome` and
returns a number, or None where the window holds another number of
`engine.cohort` spans than the driver made launches (as where the
program writes no spans), which leaves the metric out of the line.
"""
from __future__ import annotations

from typing import List, Optional

from bench import traces

COHORT = "engine.cohort"
BATCH = "engine.batch"
FETCH = "engine.fetch"


def window_spans(out, name: str) -> Optional[List[traces.Ev]]:
    """The host spans called `name` inside the `bench.window` span, or
    None unless the window holds one `engine.cohort` span per launch."""
    t = out.trace
    if t is None or not t.has_device_ops():
        return None
    win = next((e for e in t.host if e.name == traces.WINDOW_SPAN), None)
    if win is None:
        return None
    inside = [e for e in t.host
              if win.start <= e.start and e.end <= win.end]
    cohorts = sum(1 for e in inside if e.name == COHORT)
    if cohorts == 0 or cohorts != out.layer["launches"]:
        return None
    return [e for e in inside if e.name == name]


def idle_under(out, spans: List[traces.Ev]) -> float:
    """Seconds in which the device idles while one of `spans` is open,
    averaged over the chips."""
    iv = traces.union(spans)
    chips = out.layer["chips"]
    return sum(traces.intersect_len(out.trace.idle_gaps(d), iv)
               for d in range(chips)) / chips / 1e9


def engine_idle(out) -> Optional[float]:
    """Share of the window in which the device idles while the engine
    serves a cohort (the union of `engine.cohort` spans), averaged over
    the chips as `device_idle` is.  `device_idle` less this is the idle
    time the engine did not cause: arrival waits and driver bookkeeping."""
    spans = window_spans(out, COHORT)
    if spans is None or out.window_s <= 0:
        return None
    return 100.0 * idle_under(out, spans) / out.window_s


def batch_build_ms(out) -> Optional[float]:
    """Mean host time of the engine's slot-batch build (`engine.batch`:
    the batch's allocation and its payload copies), in milliseconds."""
    spans = window_spans(out, BATCH)
    if not spans:
        return None
    return sum(e.dur for e in spans) / len(spans) / 1e6


def fetch_idle_ms(out) -> Optional[float]:
    """Device idle time under the engine's output fetch (`engine.fetch`,
    the `np.asarray` of a launch's output) per launch, in milliseconds."""
    spans = window_spans(out, FETCH)
    if not spans:
        return None
    return 1e3 * idle_under(out, spans) / out.layer["launches"]
