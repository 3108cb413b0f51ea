"""Reduction of a profiler trace to device busy time, kernel time and the
run's breakdown.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:...``) carry a line of XLA operations ("XLA Ops"), each
event one operation run, named by its HLO text (``%name = ... op(...)``).
A loop or conditional is an event that encloses the events of its body; the
reduction keeps the leaf events, which do the work.  The host plane carries
the harness's spans (``jax.profiler.TraceAnnotation`` names that start with
``bench.``) on the same clock.  From them:

* busy time: the length of the union of the leaf operations' intervals
  inside the traced window, per device, averaged over the devices (time a
  loop spends between its body's operations counts as idle);
* kernel time: the summed durations of the leaf operations whose HLO text a
  pattern matches, with their count;
* the breakdown: the ten HLO instructions that took most device time, and
  the ten longest idle gaps, each named by the innermost harness span the
  host was in at the gap's middle.

The traced window is the ``bench.window`` span where the host recorded one,
else the interval from the first operation's start to the last one's end.

The device and host clocks of a trace can disagree by about a millisecond
(the recorded test trace puts each program on the device 1.1 ms before the
host launched it).  Where a device plane's program runs ("XLA Modules") pair
one to one, in order, with the host's launches
(``PJRT_LoadedExecutable_Execute``), the device's events are moved later by
the smallest shift that starts no program before its launch.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OP_LINES = ("XLA Ops",)          # the device line of per-operation events
MODULE_LINE = "XLA Modules"      # the device line of whole-program runs
LAUNCH = "PJRT_LoadedExecutable_Execute"   # the host event that launches one


@dataclass
class Reduction:
    window: tuple[float, float]            # ns, on the trace's clock
    busy_ns: float                         # union of device op intervals, mean over devices
    devices: int
    ops: list = field(default_factory=list)     # (start, end, HLO text) of every leaf device op
    spans: list = field(default_factory=list)   # (start, end, name) of host bench.* spans
    gaps: list = field(default_factory=list)    # (start, end) idle intervals of device 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel(self, pattern: str) -> tuple[int, float]:
        """(count, seconds) of the leaf device ops inside the window whose
        HLO text matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        t0, t1 = self.window
        n, tot = 0, 0.0
        for s, e, text in self.ops:
            if e > t0 and s < t1 and rx.search(text):
                n += 1
                tot += min(e, t1) - max(s, t0)
        return n, tot * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` HLO instructions with the most device time in the window."""
        agg: dict[str, float] = {}
        t0, t1 = self.window
        for s, e, text in self.ops:
            if e > t0 and s < t1:
                name = op_name(text)
                agg[name] = agg.get(name, 0.0) + (min(e, t1) - max(s, t0))
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        out = []
        for s, e in sorted(self.gaps, key=lambda g: g[0] - g[1])[:k]:
            out.append([self.span_at((s + e) / 2), (e - s) * 1e-9])
        return out

    def span_at(self, t: float) -> str:
        """Innermost harness span that holds time ``t`` (``"none"`` if none)."""
        best, width = "none", float("inf")
        for s, e, name in self.spans:
            if s <= t <= e and e - s < width and name != WINDOW_SPAN:
                best, width = name, e - s
        return best


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def op_name(text: str) -> str:
    """``"%fusion.12 = f32[...] fusion(...)"`` -> ``"fusion.12"``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _leaves(events):
    """The events of one line that enclose no other event of the line."""
    events = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= ev[1]:
            out.append(ev)
    return out


def _union(intervals):
    """Sorted, merged copy of ``intervals`` [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(path: str) -> Reduction:
    """Read the ``.xplane.pb`` at ``path`` and reduce it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    per_device, ops, spans, launches, devices = [], [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev, mods = [], []
            for ln in plane.lines:
                if ln.name in OP_LINES:
                    dev += _leaves([(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                                    for ev in ln.events])
                elif ln.name == MODULE_LINE:
                    mods += [ev.start_ns for ev in ln.events]
            if dev:
                devices.append((dev, sorted(mods)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
    launches.sort()
    for dev, mods in devices:
        shift = 0.0
        if mods and len(mods) == len(launches):
            shift = max(0.0, max(h - d for d, h in zip(mods, launches)))
        dev = [(s + shift, e + shift, text) for s, e, text in dev]
        per_device.append([(s, e) for s, e, _ in dev])
        ops += dev
    if not per_device:
        raise ValueError(f"{path}: no device operations in the trace")
    win = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if win:
        t0, t1 = win[0]
    else:
        t0 = min(s for dev in per_device for s, _ in dev)
        t1 = max(e for dev in per_device for _, e in dev)
    busy, gaps = [], []
    for i, dev in enumerate(per_device):
        merged = [(max(s, t0), min(e, t1)) for s, e in _union(dev) if e > t0 and s < t1]
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            edges = [t0] + [x for se in merged for x in se] + [t1]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return Reduction(window=(t0, t1), busy_ns=float(np.mean(busy)), devices=len(per_device),
                     ops=ops, spans=spans, gaps=gaps)
