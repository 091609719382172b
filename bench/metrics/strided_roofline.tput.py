"""Roofline share of the strided forward launches in the pix2pix cell:
the least time the chip could take for the U-Net encoder's `down*`
convs at the cell's slot batch, times the launches, over the device time
of the `dconv_forward_pallas` launches, the only kernel of that name the
cell runs.  Silent where the trace holds another number of them than
launches x encoder levels x chips.  The model is this cell's
configuration, read by name: a reader is handed only the run's outcome.
Moves serve_throughput."""
from __future__ import annotations

from typing import Optional

from bench import flops, harness, traces

CONFIG = "pix2pix-unet256"
KERNEL = "dconv_forward_pallas"


def peaks() -> dict:
    """The peaks of the device the run held (`harness.claim_chips` has
    checked that `bench/peaks.json` names it)."""
    import jax
    table = harness.load_json(harness.BENCH / "peaks.json")
    return table[jax.devices()[0].device_kind]


def read(out) -> Optional[float]:
    t, lay = out.trace, out.layer
    if t is None or not lay.get("launches"):
        return None
    model = harness.load_json(
        harness.BENCH / "configs" / f"{CONFIG}.json")["model"]
    down = [o for o in harness.load_model(model["kind"]).ops(
        model, lay["slot_batch"]) if o.name.startswith("down")]
    evs = [e for d in t.devices for e in d.ops if traces.is_pallas(e)
           and traces.op_label(e.name).startswith(KERNEL)]
    chips = lay["chips"]
    secs = sum(e.dur for e in evs) / 1e9
    if len(evs) != lay["launches"] * len(down) * chips or secs <= 0:
        return None
    return 100.0 * lay["launches"] * flops.roofline_s(down, peaks()) / (
        secs / chips)
