"""The readers of the serving engine's spans (`bench/engine_readers.py`,
through each metric's own file) on hand-made traces whose answers are
known exactly."""
from __future__ import annotations

import pytest

from bench import harness, traces
from bench.harness import Outcome

K = 1e3   # one unit of the hand-made traces, in ns


def ev(name, a, b):
    return traces.Ev(name, a * K, b * K)


def dev(*busy):
    return traces.Device(ops=[ev("%f = f32[8] fusion(f32[8] %x)", a, b)
                              for a, b in busy], modules=[])


def engine_host(window=(0, 100)):
    """Window [0, 100); two cohorts, [5, 45) and [45, 95), and one span
    set of a warm-up cohort before the window."""
    return [ev(traces.WINDOW_SPAN, *window),
            ev("engine.cohort", -30, -10), ev("engine.batch", -29, -25),
            ev("engine.fetch", -20, -12),
            ev("engine.cohort", 5, 45), ev("engine.take", 5, 6),
            ev("engine.launch", 6, 27), ev("engine.batch", 6, 9),
            ev("engine.dispatch", 9, 11), ev("engine.fetch", 11, 25),
            ev("engine.check", 25, 27), ev("engine.answer", 27, 30),
            ev("engine.cohort", 45, 95), ev("engine.batch", 45, 50),
            ev("engine.fetch", 52, 85)]


def hand_made():
    """Device 0 busy [10,20) u [40,50) u [80,90): idle [0,10), [20,40),
    [50,80), [90,100).  Under the cohorts: 5 + 20 + 30 + 5 = 60 units;
    under the fetches: 5 + 28 = 33.  Device 1 busy [0,50): idle under
    the cohorts 45, under the fetches 33."""
    return traces.TraceView([dev((10, 20), (40, 50), (80, 90)),
                             dev((0, 50))], engine_host())


def outcome(trace, launches=2, chips=1):
    return Outcome(attempted=4, failed=0, end_to_end={}, checks={},
                   correct=True, memory_peak_bytes=0, window_s=100e-6,
                   layer={"launches": launches, "chips": chips},
                   trace=trace)


READS = {   # metric -> (one chip, two chips)
    "engine_idle.p95": (60.0, 52.5),
    "engine_idle.tput": (60.0, 52.5),
    "batch_build_ms.p95": (4e-3, 4e-3),
    "fetch_idle_ms.tput": (16.5e-3, 16.5e-3),
}


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_on_a_hand_made_trace(metric):
    read = harness.load_reader(metric)
    one, two = READS[metric]
    assert read(outcome(hand_made())) == pytest.approx(one)
    assert read(outcome(hand_made(), chips=2)) == pytest.approx(two)


def test_engine_idle_is_within_device_idle():
    from bench import readers
    out = outcome(hand_made())
    assert readers.device_idle(out) == pytest.approx(70.0)
    assert harness.load_reader("engine_idle.p95")(out) <= \
        readers.device_idle(out)


def no_spans():
    """A program that writes no engine spans: the window alone."""
    return traces.TraceView([dev((10, 20))], engine_host()[:1])


def no_device_ops():
    return traces.TraceView([traces.Device([], [])], engine_host())


@pytest.mark.parametrize("metric", sorted(READS))
@pytest.mark.parametrize("case", [
    lambda: outcome(hand_made(), launches=3),   # counts differ
    lambda: outcome(hand_made(), launches=1),
    lambda: outcome(no_spans()),
    lambda: outcome(no_device_ops()),
    lambda: outcome(None),
    lambda: outcome(traces.TraceView([dev((10, 20))],
                                     engine_host()[1:])),   # no window
], ids=["more-launches", "fewer-launches", "no-spans", "no-device-ops",
        "no-trace", "no-window"])
def test_reader_stays_silent(metric, case):
    assert harness.load_reader(metric)(case()) is None
