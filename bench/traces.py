"""Reduction of a JAX profiler trace to the per-layer numbers.

A TPU trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`) has a
plane `/device:TPU:<n>` per chip with the lines `XLA Modules` (one event
per program execution) and `XLA Ops` (one event per HLO instruction,
named by its full HLO text), and a plane `/host:CPU` whose `python`
line holds the Python thread's annotations: the benchmark's own spans
(`bench.*`) and JAX's (`PjitFunction(...)`, `np.asarray(jax.Array)`).
Device and host events share one clock.

A Pallas kernel is an `XLA Ops` event whose HLO is a
`custom_call_target="tpu_custom_call"`; its instruction is named after
the kernel (`%dconv_forward_pallas.5`).  A collective is an op whose
opcode is an all-reduce, all-gather, reduce-scatter, all-to-all or
collective-permute (or their -start/-done halves).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(-start|-done)?\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
MIN_GAP_NS = 1_000.0


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    ops: List[Ev]
    modules: List[Ev]


def union(evs: Sequence[Ev]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by `evs`."""
    out: List[List[float]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def covered(iv: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in iv)


def intersect_len(a: Sequence[Tuple[float, float]],
                  b: Sequence[Tuple[float, float]]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def op_label(name: str) -> str:
    """An HLO op event's instruction name: `%dconv_forward_pallas.5 = ...`
    -> `dconv_forward_pallas.5`."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_pallas(e: Ev) -> bool:
    return PALLAS_MARK in e.name


def is_collective(e: Ev) -> bool:
    return COLLECTIVE.search(e.name) is not None


class TraceView:
    """The events of one traced window, and the arithmetic on them."""

    def __init__(self, devices: List[Device], host: List[Ev]):
        self.devices = devices
        self.host = host
        spans = [e for e in host if e.name == WINDOW_SPAN]
        self.window: Optional[Tuple[float, float]] = (
            (spans[0].start, spans[0].end) if spans else None)

    def _bounds(self, dev: Device) -> Tuple[float, float]:
        if self.window:
            return self.window
        return (min(e.start for e in dev.ops), max(e.end for e in dev.ops))

    def has_device_ops(self) -> bool:
        return any(d.ops for d in self.devices)

    def busy_s(self, chips: int) -> float:
        """Seconds in which an op ran, within the window, averaged over
        the first `chips` devices."""
        devs = self.devices[:chips]
        tot = 0.0
        for d in devs:
            if d.ops:
                lo, hi = self._bounds(d)
                tot += covered(union(d.ops), lo, hi)
        return tot / max(1, len(devs)) / 1e9

    def pallas_count(self) -> int:
        return sum(1 for d in self.devices for e in d.ops if is_pallas(e))

    def pallas_s(self) -> float:
        """Device seconds of every Pallas launch, summed over devices."""
        return sum(e.dur for d in self.devices for e in d.ops
                   if is_pallas(e)) / 1e9

    def step_gaps_s(self) -> List[float]:
        """Idle gaps between consecutive executions of each device's
        dominant program (the one with the most device time): the time
        from one step program's end to the next one's start."""
        gaps: List[float] = []
        for d in self.devices:
            if not d.modules:
                continue
            tot: Dict[str, float] = collections.Counter()
            for m in d.modules:
                tot[m.name] += m.dur
            main = max(tot, key=tot.get)
            runs = sorted((m for m in d.modules if m.name == main),
                          key=lambda m: m.start)
            gaps += [(b.start - a.end) / 1e9 for a, b in zip(runs, runs[1:])]
        return gaps

    def collective_exposed_share(self) -> Optional[float]:
        """Share of the step programs' time in which a collective runs
        with no other op on that device, averaged over the devices that
        ran a collective."""
        shares = []
        for d in self.devices:
            coll = union([e for e in d.ops if is_collective(e)])
            if not coll or not d.modules:
                continue
            comp = union([e for e in d.ops if not is_collective(e)])
            exposed = sum(b - a for a, b in coll) - intersect_len(coll, comp)
            shares.append(exposed / sum(m.dur for m in d.modules))
        return sum(shares) / len(shares) if shares else None

    def idle_gaps(self, dev: int = 0) -> List[Tuple[float, float]]:
        """The device's idle intervals within the window."""
        d = self.devices[dev]
        if not d.ops:
            return []
        lo, hi = self._bounds(d)
        out, t = [], lo
        for a, b in union(d.ops):
            if a > t:
                out.append((t, min(a, hi)))
            t = max(t, b)
        if hi > t:
            out.append((t, hi))
        return [(a, b) for a, b in out if b - a >= MIN_GAP_NS]

    def host_labels(self, times: Sequence[float]) -> List[str]:
        """The innermost host span on the Python thread at each of the
        sorted `times` (spans on one thread nest, so a stack finds it)."""
        evs = sorted(self.host, key=lambda e: (e.start, -e.end))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(evs) and evs[i].start <= t:
                stack.append(evs[i])
                i += 1
            while stack and stack[-1].end <= t:
                stack.pop()
            out.append(next((e.name for e in reversed(stack)
                             if e.start <= t < e.end), "(no host span)"))
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        """The device ops that took most time (seconds summed over
        devices), and the device's idle time by what the host was doing
        in the middle of each gap."""
        ops: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for e in d.ops:
                ops[op_label(e.name)] += e.dur / 1e9
        idle: Dict[str, float] = collections.Counter()
        if self.devices:
            gaps = self.idle_gaps(0)
            labels = self.host_labels([(a + b) / 2 for a, b in gaps])
            for (a, b), label in zip(gaps, labels):
                idle[label] += (b - a) / 1e9
        def largest(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": largest(ops), "idle_gaps": largest(idle)}


def from_profile(pd) -> TraceView:
    """A `jax.profiler.ProfileData` reduced to the events used here.  The
    host events are those of the Python thread: the line of `/host:CPU`
    that holds the `bench.window` span (with the Python tracer off the
    line is named after the thread), else the line named `python`."""
    devices: Dict[int, Device] = {}
    host: List[Ev] = []
    evs = lambda line: [Ev(e.name, e.start_ns, e.end_ns) for e in line.events]
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device([], []))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops += evs(line)
                elif line.name == "XLA Modules":
                    dev.modules += evs(line)
        elif plane.name == "/host:CPU":
            lines = {line.name: evs(line) for line in plane.lines}
            host = next((v for v in lines.values()
                         if any(e.name == WINDOW_SPAN for e in v)),
                        lines.get("python", []))
    return TraceView([devices[k] for k in sorted(devices)], host)


def load_dir(d: str) -> TraceView:
    """The trace the profiler wrote under `d`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return from_profile(ProfileData.from_file(paths[0]))
