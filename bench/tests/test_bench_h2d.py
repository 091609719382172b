"""The `h2d_mb.p95` reader: the serving engine's `h2d_bytes` counter per
launch, silent where the engine keeps no such counter; and the open-loop
driver on the engine's device-slot path, on the CPU."""
from __future__ import annotations

import pytest

from bench import harness
from bench.drivers import serve_open
from bench.harness import Outcome
from bench.tests.test_bench_drivers import OPEN, tiny_ctx

read = harness.load_reader("h2d_mb.p95")


def outcome(stats):
    return Outcome(attempted=4, failed=0, end_to_end={}, checks={},
                   correct=True, memory_peak_bytes=0, window_s=1.0,
                   layer={"launches": 2, "chips": 1, "stats": stats})


def test_reads_megabytes_per_launch():
    stats = {"launches": 4, "completed": 11, "h2d_bytes": 3 * 8_921_088}
    assert read(outcome(stats)) == pytest.approx(3 * 8.921088 / 4)


@pytest.mark.parametrize("stats", [
    {"launches": 4, "completed": 11},              # the counter is absent
    {"h2d_bytes": 8_921_088, "completed": 11},     # no launch count
    {"launches": 0, "h2d_bytes": 0},
    {}], ids=["no-counter", "no-launches", "zero-launches", "no-stats"])
def test_silent_with_nothing_to_read(stats):
    assert read(outcome(stats)) is None


def test_open_loop_on_the_device_slot_path(monkeypatch):
    """The device-slot path answers correctly through the driver, and the
    reader sees the window's cohorts' own payloads, fewer than full slot
    batches: set-up's launch is not counted."""
    from repro.serve import conv_engine
    monkeypatch.setattr(conv_engine, "DEVICE_SLOT_MIN_BYTES", 1)
    ctx = tiny_ctx("aspp", OPEN)
    out = serve_open.run(ctx)
    assert out.correct, out.checks
    stats = out.layer["stats"]
    payload = 4 * 8 * 9 * 9                      # float32 9x9x8 map
    assert stats["h2d_bytes"] == payload * out.layer["answered"]
    assert read(out) == pytest.approx(
        stats["h2d_bytes"] / stats["launches"] / 1e6)
