"""Payload megabytes the serving engine put on the device per launch in
the ASPP cell, from its own counter: h2d_bytes / launches / 1e6.  Silent
where the engine keeps no such counter.  Moves serve_p95_ms."""
from __future__ import annotations

from typing import Optional


def read(out) -> Optional[float]:
    stats = out.layer.get("stats", {})
    if "h2d_bytes" not in stats or not stats.get("launches"):
        return None
    return stats["h2d_bytes"] / stats["launches"] / 1e6
