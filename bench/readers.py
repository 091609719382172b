"""The arithmetic of the per-layer metrics.  Each metric's own file in
`bench/metrics/` names one of these; each takes the run's
`harness.Outcome` and returns a number, or None where the run holds
nothing to read (no trace, no Pallas launch, a count that does not
match), which leaves the metric out of the line.  Shares are percent.
"""
from __future__ import annotations

from typing import Optional


def device_idle(out) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device: 1 - (union of device-op intervals) / window, averaged over
    the chips."""
    t = out.trace
    chips = out.layer["chips"]
    if t is None or not t.has_device_ops() or out.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s(chips) / out.window_s)


def conv_roofline(out) -> Optional[float]:
    """The Pallas conv launches' share of their roofline: the least time
    the chip could take for their useful work (each launch bounded by
    FLOPs at peak or bytes at HBM bandwidth) over the device time the
    trace gives them.  Silent where the trace holds another number of
    Pallas launches than the window made, as when a conv has left the
    Pallas path."""
    t, lay = out.trace, out.layer
    if t is None or "pallas_roofline_s" not in lay:
        return None
    chips = lay["chips"]
    want = out.layer["launches"] * out.layer["pallas_per_launch"] * chips
    n, secs = t.pallas_count(), t.pallas_s()
    if n == 0 or n != want or secs <= 0:
        return None
    return 100.0 * out.layer["pallas_roofline_s"] / (secs / chips)


def mfu_window(out) -> Optional[float]:
    """Useful (zero-free) FLOPs of the work completed over chips x peak x
    the window."""
    lay = out.layer
    if out.window_s <= 0 or not lay.get("useful_flops"):
        return None
    return 100.0 * lay["useful_flops"] / (
        lay["chips"] * lay["peak_flops"] * out.window_s)


def mfu_launch(out) -> Optional[float]:
    """Useful FLOPs of the requests answered over chips x peak x the
    summed host time of the launches (dispatch to answers on the host):
    the step's share of the peak while a launch is in flight."""
    lay = out.layer
    if not lay.get("launch_host_s") or not lay.get("useful_flops"):
        return None
    return 100.0 * lay["useful_flops"] / (
        lay["chips"] * lay["peak_flops"] * lay["launch_host_s"])


def batch_fill(out) -> Optional[float]:
    """Answered requests over launched slots, from the engine's own
    counts: completed / (launches x slot_batch)."""
    stats, slots = out.layer["stats"], out.layer["slot_batch"]
    if not stats.get("launches"):
        return None
    return 100.0 * stats["completed"] / (stats["launches"] * slots)


def host_gap_ms(out) -> Optional[float]:
    """Mean idle gap between consecutive executions of the step program
    on the device, in milliseconds."""
    gaps = out.trace.step_gaps_s() if out.trace is not None else []
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def collective_exposed(out) -> Optional[float]:
    """Share of the step programs' device time in which a collective
    runs with no other op on that device."""
    share = (out.trace.collective_exposed_share()
             if out.trace is not None else None)
    return None if share is None else 100.0 * share
