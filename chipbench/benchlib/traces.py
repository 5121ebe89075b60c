"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy
time, module and kernel time, and the idle gaps by what the host did.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per executed operation, named by the text of
its HLO instruction (``%staleness_agg.1 = f32[...] custom-call(...)``; a
Pallas kernel is a custom call named after its jitted wrapper, a loop is
one ``while`` event that spans the ops it runs); the line ``XLA Modules``
holds one event per program run, named ``jit_<function>(<id>)``. Busy time is
the union of the op intervals; idle share is one minus busy over the
window. Host spans come from the host plane's threads (``/host:CPU``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Ops and module runs per device plane, and host spans."""

    ops: dict[str, list[Event]] = field(default_factory=dict)
    modules: dict[str, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)

    def device_planes(self) -> list[str]:
        return sorted(self.ops)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


#: ops that contain other ops (a loop, a call): left out of the top list
_CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(op_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    mods.extend(Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            tr.ops[plane.name] = ops
            tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events if e.duration_ns > 0)
    return tr


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union_ns(events: list[Event]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if cur_e is None or e.start_ns > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start_ns, e.end_ns
        else:
            cur_e = max(cur_e, e.end_ns)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = tr.device_planes()
    if not planes:
        return 0.0
    return sum(union_ns(clip(tr.ops[p], lo, hi)) for p in planes) \
        / len(planes) / 1e9


def module_s(tr: Trace, prefix: str, lo: float, hi: float) -> float:
    """Device seconds of the runs of programs whose name starts with
    ``prefix`` (``jit_<function>``), summed over planes."""
    return sum(e.dur_ns for p in tr.device_planes()
               for e in clip(tr.modules[p], lo, hi)
               if e.name.startswith(prefix)) / 1e9


def op_s(tr: Trace, pattern: str, lo: float, hi: float) -> float:
    """Device seconds of ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(e.dur_ns for p in tr.device_planes()
               for e in clip(tr.ops[p], lo, hi) if rx.search(e.name)) / 1e9


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10):
    """[[op name, seconds], ...] of the ops that took most device time,
    ops of one name summed, averaged over planes."""
    acc: dict[str, float] = {}
    planes = tr.device_planes()
    for p in planes:
        for e in clip(tr.ops[p], lo, hi):
            if not _CONTAINERS.match(e.name):
                acc[e.name] = acc.get(e.name, 0.0) + e.dur_ns
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(planes), 1)] for k, v in top]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10):
    """The longest gaps between device ops on the first device plane, each
    named by the innermost host span that covers the gap's middle."""
    planes = tr.device_planes()
    if not planes:
        return []
    ops = sorted(clip(tr.ops[planes[0]], lo, hi), key=lambda e: e.start_ns)
    gaps, cur = [], lo
    for e in ops:
        if e.start_ns > cur:
            gaps.append((cur, e.start_ns))
        cur = max(cur, e.end_ns)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:n]:
        mid = (s + t) / 2
        cover = [h for h in tr.host if h.start_ns <= mid <= h.end_ns]
        name = (min(cover, key=lambda h: h.dur_ns).name if cover
                else "no host span")
        out.append([name, (t - s) / 1e9])
    return out


def span(tr: Trace, name: str) -> tuple[float, float]:
    """(start, end) in trace time of the host span named ``name``."""
    hits = [h for h in tr.host if h.name == name]
    if not hits:
        raise KeyError(f"no host span {name!r} in the trace")
    h = max(hits, key=lambda h: h.dur_ns)
    return h.start_ns, h.end_ns
