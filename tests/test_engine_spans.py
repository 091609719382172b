"""`ConvServeEngine`'s profiler spans: one set per cohort, on the host
line that the benchmark's trace reduction (`bench/traces.py`) reads,
inside the benchmark's window span and so on the device trace's clock;
the `python.gc` span; and the bounded latency record behind `health()`.
"""
from __future__ import annotations

import gc
import glob
import os

import jax
import numpy as np
import pytest

from bench import harness, traces
from repro.models import gan
from repro.serve import conv_engine
from repro.serve.conv_engine import ConvRequest, ConvServeEngine
from repro.serve.faults import FaultEvent, FaultInjector, FaultSchedule

Z_DIM, BASE = 8, 8


@pytest.fixture(scope="module")
def gan_params():
    return gan.generator_init(jax.random.PRNGKey(0), z_dim=Z_DIM,
                              base=BASE, out_ch=3)


@pytest.fixture
def raw(monkeypatch):
    """Holds the `ProfileData` that `harness.profiled` reduces: its events
    keep the spans' keyword arguments, which `traces.Ev` drops."""
    held = {}

    def load_dir(d):
        from jax.profiler import ProfileData
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        held["pd"] = ProfileData.from_file(path)
        return traces.from_profile(held["pd"])

    monkeypatch.setattr(traces, "load_dir", load_dir)
    return held


def span_args(pd, name):
    """The arguments of each event called `name` on the host line that
    holds the window span, in time order."""
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = list(line.events)
            if any(e.name == traces.WINDOW_SPAN for e in evs):
                return [dict(e.stats) for e in
                        sorted(evs, key=lambda e: e.start_ns)
                        if e.name == name]
    return []


def requests(rng, n, first):
    return [ConvRequest(first + i, "gan_gen",
                        rng.standard_normal(Z_DIM).astype(np.float32))
            for i in range(n)]


def within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_one_span_set_per_cohort_inside_the_window(gan_params, rng, raw):
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=8)
    eng.warmup([("gan_gen", (Z_DIM,))], compile=True)
    with harness.profiled(True) as prof:
        for k in range(3):          # one run() a cohort, as the drivers do
            for r in requests(rng, 2, 10 * k):
                eng.submit(r)
            assert len(eng.run()) == 2
    view = prof.view
    assert eng.stats["launches"] == 3
    win = next(e for e in view.host if e.name == traces.WINDOW_SPAN)
    by = {name: [e for e in view.host if e.name == name]
          for name in ("engine.cohort", "engine.take", "engine.launch",
                       "engine.batch", "engine.dispatch", "engine.fetch",
                       "engine.check", "engine.answer")}
    assert {k: len(v) for k, v in by.items()} == dict.fromkeys(by, 3)
    for k, cohort in enumerate(sorted(by["engine.cohort"],
                                      key=lambda e: e.start)):
        assert within(cohort, win)
        for name in ("engine.take", "engine.launch", "engine.answer"):
            assert sum(within(e, cohort) for e in by[name]) == 1, name
        launch = next(e for e in by["engine.launch"] if within(e, cohort))
        for name in ("engine.batch", "engine.dispatch", "engine.fetch",
                     "engine.check"):
            assert sum(within(e, launch) for e in by[name]) == 1, name
    args = span_args(raw["pd"], "engine.launch")
    assert [(a["kind"], a["n"], a["uid0"]) for a in args] == [
        ("gan_gen", 2, 0), ("gan_gen", 2, 10), ("gan_gen", 2, 20)]
    assert [(a["rung"], a["attempt"]) for a in
            span_args(raw["pd"], "engine.dispatch")] == [("pallas", 1)] * 3


def test_a_fallback_adds_a_dispatch_span_with_its_rung(gan_params, rng, raw):
    inj = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "kernel_exception")]))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=8, injector=inj)
    with harness.profiled(True) as prof:
        res = eng.serve(requests(rng, 2, 0))
    assert len(res) == 2 and eng.stats["fallbacks"] == 1
    names = [e.name for e in prof.view.host]
    assert names.count("engine.launch") == 1
    assert names.count("engine.fetch") == 1    # the failed rung never fetched
    assert [(a["rung"], a["attempt"]) for a in
            span_args(raw["pd"], "engine.dispatch")] == [
        ("pallas", 1), ("xla_zero_free", 2)]


def test_collections_get_a_python_gc_span(gan_params, raw):
    ConvServeEngine(gan_params=gan_params)
    ConvServeEngine(gan_params=gan_params)
    assert sum(isinstance(cb, conv_engine._GcSpan)
               for cb in gc.callbacks) == 1      # registered once a process
    with harness.profiled(True) as prof:
        gc.collect()
    win = next(e for e in prof.view.host if e.name == traces.WINDOW_SPAN)
    spans = [e for e in prof.view.host if e.name == "python.gc"]
    assert spans and all(within(e, win) for e in spans)
    assert {"generation": 2} in span_args(raw["pd"], "python.gc")


def test_health_percentiles_cover_the_latest_answers(gan_params, rng,
                                                     monkeypatch):
    monkeypatch.setattr(conv_engine, "LATENCY_WINDOW", 4)
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=16)
    assert len(eng.serve(requests(rng, 6, 0))) == 6
    assert len(eng._latencies_us) == 4
    eng._latencies_us.extend([1.0, 2.0, 3.0, 4.0])
    h = eng.health()
    assert h["completed"] == 6
    assert h["p50_us"] == pytest.approx(2.5)
    assert h["p99_us"] == pytest.approx(3.97)


def test_a_unet_cohort_is_annotated_with_its_kind(rng, raw):
    from repro.models import unet
    params = unet.unet_init(jax.random.PRNGKey(1), num_downs=5, ngf=4)
    eng = ConvServeEngine(unet_params=params, slot_batch=2, queue_limit=4,
                          ladder=("pallas",))
    img = rng.uniform(-1, 1, (32, 32, 3)).astype(np.float32)
    with harness.profiled(True):
        eng.submit(ConvRequest(7, "unet", img))
        assert len(eng.run()) == 1
    assert [(a["kind"], a["n"], a["uid0"]) for a in
            span_args(raw["pd"], "engine.launch")] == [("unet", 1, 7)]
