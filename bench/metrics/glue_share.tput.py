"""Share of the device's busy time in the pix2pix cell spent in ops that
are not Pallas launches (`traces.is_pallas`): the skip concatenations,
the space-to-depth packing, pads, relayouts and phase assembly between
the convs.  Busy time is the union of the window's op intervals, as
`device_idle` reads it; the glue is the part of it no Pallas launch
covers, summed over the chips.  Silent where the trace holds another
number of Pallas launches than the window made.  Moves
serve_throughput."""
from __future__ import annotations

from typing import Optional

from bench import traces


def read(out) -> Optional[float]:
    t, lay = out.trace, out.layer
    if t is None or not t.has_device_ops() or "pallas_per_launch" not in lay:
        return None
    chips = lay["chips"]
    want = lay["launches"] * lay["pallas_per_launch"] * chips
    if want == 0 or t.pallas_count() != want:
        return None
    busy = pallas = 0.0
    for d in t.devices[:chips]:
        if not d.ops:
            continue
        lo, hi = t.window or (min(e.start for e in d.ops),
                              max(e.end for e in d.ops))
        busy += traces.covered(traces.union(d.ops), lo, hi)
        pallas += traces.covered(
            traces.union([e for e in d.ops if traces.is_pallas(e)]), lo, hi)
    return 100.0 * (busy - pallas) / busy if busy > 0 else None
