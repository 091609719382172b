"""The trace -> metric reduction, on a small recorded chip trace and on
hand-made traces whose answers are known exactly."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import readers, traces
from bench.harness import Outcome

DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def load_recorded() -> traces.TraceView:
    d = json.loads((DATA / "aspp_trace.json").read_text())
    ev = lambda rows: [traces.Ev(*r) for r in rows]
    return traces.TraceView(
        [traces.Device(ev(x["ops"]), ev(x["modules"])) for x in d["devices"]],
        ev(d["host"]))


def op(name, a, b, kind="fusion"):
    text = {"pallas": f'%{name} = f32[8] custom-call(f32[8] %x), '
                      f'custom_call_target="tpu_custom_call"',
            "coll": f"%{name} = f32[8] all-reduce(f32[8] %x), to_apply=%add",
            "fusion": f"%{name} = f32[8] fusion(f32[8] %x), kind=kLoop"}[kind]
    return traces.Ev(text, a, b)


def hand_made() -> traces.TraceView:
    """Two devices, window [0, 100) ns-units scaled by 1e3.  Device 0:
    ops cover [10,30) u [25,40) u [60,70) = 40 units busy; two step
    programs [10,40) and [60,80) -> one gap of 20.  Device 1: busy 20."""
    k = 1e3
    d0 = traces.Device(
        ops=[op("kern.1", 10 * k, 30 * k, "pallas"),
             op("fusion.1", 25 * k, 40 * k),
             op("kern.1", 60 * k, 70 * k, "pallas")],
        modules=[traces.Ev("jit_step(1)", 10 * k, 40 * k),
                 traces.Ev("jit_step(1)", 60 * k, 80 * k),
                 traces.Ev("jit_small(2)", 85 * k, 86 * k)])
    # Device 1: a collective [50,90) overlapped by compute [40,60):
    # exposed 30 of step time 50.
    d1 = traces.Device(
        ops=[op("fusion.2", 40 * k, 60 * k), op("all-reduce.1", 50 * k,
                                                 90 * k, "coll")],
        modules=[traces.Ev("jit_step(1)", 40 * k, 90 * k)])
    host = [traces.Ev(traces.WINDOW_SPAN, 0, 100 * k),
            traces.Ev("bench.wait", 0, 10 * k),
            traces.Ev("np.asarray(jax.Array)", 40 * k, 60 * k)]
    return traces.TraceView([d0, d1], host)


def test_idle_union_and_busy():
    t = hand_made()
    assert t.busy_s(1) == pytest.approx(40e-6)
    # device 1: ops [40,90) -> 50 busy; mean of 40 and 50
    assert t.busy_s(2) == pytest.approx(45e-6)
    assert t.idle_gaps(0) == [(0, 10e3), (40e3, 60e3), (70e3, 100e3)]


def test_step_gaps_use_the_dominant_program():
    t = hand_made()
    assert t.step_gaps_s() == pytest.approx([20e-6])


def test_pallas_share_and_collective_exposure():
    t = hand_made()
    assert t.pallas_count() == 2
    assert t.pallas_s() == pytest.approx(30e-6)
    # only device 1 ran a collective: 30 exposed of 50 step time
    assert t.collective_exposed_share() == pytest.approx(0.6)


def test_breakdown_attributes_idle_time_to_host_spans():
    b = hand_made().breakdown()
    assert b["device_ops"][:2] == [["all-reduce.1", pytest.approx(40e-6)],
                                   ["kern.1", pytest.approx(30e-6)]]
    idle = dict(b["idle_gaps"])
    assert idle["bench.wait"] == pytest.approx(10e-6)
    assert idle["np.asarray(jax.Array)"] == pytest.approx(20e-6)
    assert idle[traces.WINDOW_SPAN] == pytest.approx(30e-6)


def test_recorded_chip_trace():
    """One second of the ASPP head served on one chip: 16 launches of
    three Pallas branches each, the device mostly idle between them."""
    t = load_recorded()
    assert t.pallas_count() == 48
    assert all("dconv_forward_pallas" in traces.op_label(e.name)
               for d in t.devices for e in d.ops if traces.is_pallas(e))
    window_s = (t.window[1] - t.window[0]) / 1e9
    busy = t.busy_s(1)
    assert 0.1 < busy / window_s < 0.2
    assert t.pallas_s() < busy
    gaps = t.step_gaps_s()
    assert len(gaps) == 15 and min(gaps) > 0
    assert t.collective_exposed_share() is None
    b = t.breakdown()
    assert b["device_ops"][0][0].startswith("dconv_forward_pallas")
    assert len(b["device_ops"]) == 10
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        window_s - busy, rel=1e-3)


def _outcome(trace, **layer):
    base = {"chips": 1, "launches": 16, "pallas_per_launch": 3,
            "pallas_roofline_s": 16 * 3 * 1e-3, "useful_flops": 1e12,
            "peak_flops": 197e12, "launch_host_s": 0.5, "slot_batch": 8,
            "stats": {"completed": 100, "launches": 16}}
    base.update(layer)
    return Outcome(attempted=128, failed=0, end_to_end={}, checks={},
                   correct=True, memory_peak_bytes=0, window_s=1.036,
                   layer=base, trace=trace)


def test_readers_on_the_recorded_trace():
    t = load_recorded()
    out = _outcome(t)
    idle = readers.device_idle(out)
    assert 80 < idle < 90
    roof = readers.conv_roofline(out)
    assert roof == pytest.approx(100 * 48e-3 / t.pallas_s())
    assert readers.mfu_launch(out) == pytest.approx(100 / 197 / 0.5)
    assert readers.mfu_window(out) == pytest.approx(100 / 197 / 1.036)
    assert readers.batch_fill(out) == pytest.approx(100 * 100 / 128)


def test_readers_stay_silent_with_nothing_to_read():
    t = load_recorded()
    # another count of Pallas launches than the window made
    assert readers.conv_roofline(_outcome(t, launches=15)) is None
    assert readers.conv_roofline(_outcome(None)) is None
    assert readers.device_idle(_outcome(None)) is None
    assert readers.mfu_launch(_outcome(None, launch_host_s=0.0)) is None
    assert readers.batch_fill(_outcome(None, stats={"launches": 0})) is None


def test_train_readers_on_a_hand_made_trace():
    out = _outcome(hand_made())
    assert readers.host_gap_ms(out) == pytest.approx(20e-3)
    assert readers.collective_exposed(out) == pytest.approx(60.0)
    assert readers.host_gap_ms(_outcome(None)) is None
    assert readers.collective_exposed(_outcome(load_recorded())) is None


def test_from_profile_reads_the_python_threads_line():
    """Device lines by name; host events from the line holding the
    window span, whatever the thread's line is called."""
    from types import SimpleNamespace as NS
    ev = lambda name, a, b: NS(name=name, start_ns=a * 1e3, end_ns=b * 1e3)
    op = f'%k.1 = f32[8] custom-call(f32[8] %x), {traces.PALLAS_MARK}'
    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_f(1)", 10, 50)]),
            NS(name="XLA Ops", events=[ev(op, 10, 50)]),
            NS(name="Async XLA Ops", events=[ev("%copy-start", 0, 90)])]),
        NS(name="/host:CPU", lines=[
            NS(name="pjrt-tpu-tasks/3", events=[ev("Linearize", 0, 5)]),
            NS(name="main/7", events=[ev(traces.WINDOW_SPAN, 0, 100),
                                      ev("np.asarray(jax.Array)", 50, 90)])]),
        NS(name="/host:metadata", lines=[])])
    t = traces.from_profile(pd)
    assert t.window == (0, 100e3)
    assert t.pallas_count() == 1 and t.busy_s(1) == pytest.approx(40e-6)
    assert [e.name for e in t.host] == [traces.WINDOW_SPAN,
                                        "np.asarray(jax.Array)"]
    assert dict(t.breakdown()["idle_gaps"]) == {
        traces.WINDOW_SPAN: pytest.approx(10e-6),
        "np.asarray(jax.Array)": pytest.approx(50e-6)}
