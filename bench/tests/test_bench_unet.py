"""The `unet` kind's module (`bench/models/unet.py`) pinned at its TINY
size, its work at the cell's size, and the two metrics only the pix2pix
cell reports: `strided_roofline.tput` and `glue_share.tput`, on
hand-made traces whose answers are known, and silent where the trace
holds nothing to read."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import flops, harness, reference, serving, traces
from bench.harness import Outcome
from bench.tests.test_bench_models import PEAKS, SEED, checksum

CELL_MODEL = json.loads((harness.BENCH / "configs" /
                         "pix2pix-unet256.json").read_text())["model"]


def test_unet_kind_makes_its_pinned_weights_payloads_and_work():
    """As `test_bench_models.PINS` pins the other kinds: at TINY and
    SEED, checksums of the weights, a 6-image pool and the
    bfloat16-operand reference over 4 images; a 4-slot launch's FLOPs
    and roofline seconds."""
    mod = harness.load_model("unet")
    model = mod.TINY
    params = mod.params(model, SEED)
    pool = np.asarray(mod.payload_pool(model, SEED, 6))
    num = reference.numerics({"numerics": {"matmul_operands": "bfloat16"}})
    out = serving.serve_forward(model, mod, num)(params, pool[:4])
    assert checksum(params) == pytest.approx(
        (24.937049258741524, 232.99882490847685), rel=1e-6)
    assert checksum(pool) == pytest.approx(
        (-62.37644648551941, 6153.749735141422), rel=1e-6)
    assert checksum(out) == pytest.approx(
        (-583.2724107669201, 497.1357311660727), rel=1e-6)
    assert pool.shape == (6, 32, 32, 3)
    assert pool.min() >= -1.0 and pool.max() <= 1.0
    ops = mod.ops(model, 4)
    assert flops.total_flops(ops) == 3801088.0
    assert flops.roofline_s(ops, PEAKS) == pytest.approx(
        6.292161172161172e-07, rel=1e-12)


def test_unet_work_at_the_cell_size():
    """Sixteen Pallas convs a launch; 12.10 GFLOP an image, 4.03 in the
    strided encoder and 8.06 in the transposed decoder."""
    ops = harness.load_model("unet").ops(CELL_MODEL, 1)
    assert [o.name for o in ops] == [f"down{k}" for k in range(1, 9)] + [
        f"up{k}" for k in range(8, 0, -1)]
    assert all(o.kernel == "pallas" for o in ops)
    down = flops.total_flops(ops[:8])
    assert down == pytest.approx(4.03e9, rel=2e-3)
    assert flops.total_flops(ops[8:]) == pytest.approx(8.06e9, rel=1e-3)
    up8, up1 = ops[8], ops[15]
    assert up8.flops == 2 * 16 * 512 * 512          # 1x1 -> 2x2
    assert up1.flops == 2 * 128 * 128 * 16 * 3 * 128


def op(name, a, b, pallas=True):
    text = (f'%{name} = f32[8] custom-call(f32[8] %x), '
            f'custom_call_target="tpu_custom_call"' if pallas else
            f"%{name} = f32[8] fusion(f32[8] %x), kind=kLoop")
    return traces.Ev(text, a, b)


def unet_trace(launches: int, enc_ns=10e3, dec_ns=20e3, glue_ns=5e3):
    """One device, window [0, launches * 1e6) ns: each launch 8 encoder
    and 8 decoder Pallas ops back to back, then one glue op that
    overlaps the last decoder op by 1 us."""
    ops, t = [], 0.0
    for k in range(launches):
        t = k * 1e6
        for j in range(8):
            ops.append(op(f"dconv_forward_pallas.{j}", t, t + enc_ns))
            t += enc_ns
        for j in range(8):
            ops.append(op(f"tconv_fused_pallas.{j}", t, t + dec_ns))
            t += dec_ns
        ops.append(op("concatenate_fusion.1", t - 1e3, t - 1e3 + glue_ns,
                      pallas=False))
    host = [traces.Ev(traces.WINDOW_SPAN, 0, launches * 1e6)]
    return traces.TraceView([traces.Device(ops, [])], host)


def _outcome(trace, **layer):
    base = {"chips": 1, "launches": 3, "pallas_per_launch": 16,
            "slot_batch": 16, "stats": {"launches": 3}}
    base.update(layer)
    return Outcome(attempted=48, failed=0, end_to_end={}, checks={},
                   correct=True, memory_peak_bytes=0, window_s=0.003,
                   layer=base, trace=trace)


@pytest.fixture
def strided(monkeypatch):
    read = harness.load_reader("strided_roofline.tput")
    monkeypatch.setitem(read.__globals__, "peaks", lambda: PEAKS)
    return read


def test_strided_roofline_reads_the_encoder_launches(strided):
    down = [o for o in harness.load_model("unet").ops(CELL_MODEL, 16)
            if o.name.startswith("down")]
    want = 100.0 * 3 * flops.roofline_s(down, PEAKS) / (3 * 8 * 10e3 / 1e9)
    assert strided(_outcome(unet_trace(3))) == pytest.approx(want)


def test_strided_roofline_is_silent_with_nothing_to_read(strided):
    assert strided(_outcome(None)) is None
    # another count of encoder launches than launches x 8
    assert strided(_outcome(unet_trace(3), launches=2)) is None
    # no launch of the strided kernel (as in a cell that runs none)
    t = unet_trace(3)
    t.devices[0].ops[:] = [e for e in t.devices[0].ops
                           if "dconv_forward_pallas" not in e.name]
    assert strided(_outcome(t)) is None


def test_glue_share_reads_busy_time_outside_pallas():
    read = harness.load_reader("glue_share.tput")
    # a launch: 80 + 160 us of Pallas, the glue 5 us of which 1 overlaps
    assert read(_outcome(unet_trace(3))) == pytest.approx(100 * 4 / 244)
    assert read(_outcome(unet_trace(3, glue_ns=1e3))) == 0.0


def test_glue_share_is_silent_with_nothing_to_read():
    read = harness.load_reader("glue_share.tput")
    assert read(_outcome(None)) is None
    assert read(_outcome(unet_trace(3), launches=4)) is None
    assert read(_outcome(unet_trace(3), pallas_per_launch=3)) is None
    empty = traces.TraceView([traces.Device([], [])], [])
    assert read(_outcome(empty)) is None
