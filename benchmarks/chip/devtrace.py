"""The reduction from a profiler trace to device intervals and host spans.

``jax.profiler`` writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``;
``jax.profiler.ProfileData`` reads it.  A device's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation that
ran, and its ``XLA Modules`` line one event per executable (``jit_<name>``).
Host spans that the benchmark opens with ``jax.profiler.TraceAnnotation``
are events on the host plane, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]  # (start_ns, end_ns)

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    """What one traced run recorded: per device its ops and executables,
    and the host spans named by the benchmark."""
    ops: Dict[str, List[Event]]       # device plane name -> ops
    modules: Dict[str, List[Event]]   # device plane name -> executables
    host: Dict[str, List[Interval]]   # span name -> intervals

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))

    def busy(self, device: str) -> List[Interval]:
        events = self.ops.get(device) or self.modules.get(device, [])
        return union((e.start, e.end) for e in events)

    def module_events(self, name_part: str, device: str) -> List[Event]:
        """Executables whose name holds ``name_part`` (``jit_<fn>``)."""
        return [e for e in self.modules.get(device, [])
                if name_part in e.name]

    def op_time(self, device: str) -> Dict[str, int]:
        """Summed duration of each device operation's events."""
        out: Dict[str, int] = {}
        for e in self.ops.get(device, []):
            out[e.name] = out.get(e.name, 0) + (e.end - e.start)
        return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, host_spans: Sequence[str],
         device_prefix: str = DEVICE_PLANE) -> Trace:
    """Read a trace; keep device ops/modules and the named host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: Dict[str, List[Interval]] = {n: [] for n in host_spans}
    wanted = set(host_spans)
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                evs = dest.setdefault(plane.name, [])
                for e in line.events:
                    s = int(e.start_ns)
                    evs.append(Event(e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith(HOST_PLANE) and wanted:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = int(e.start_ns)
                        host[e.name].append((s, s + int(e.duration_ns)))
    return Trace(ops=ops, modules=modules, host=host)


def idle_breakdown(trace: Trace, device: str, lo: int, hi: int,
                   top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of ``device`` in [lo, hi), each named by the
    host span that overlaps it most ("other" where none does)."""
    out = []
    for g in gaps(trace.busy(device), lo, hi):
        best, name = 0, "other"
        for span, ivs in trace.host.items():
            o = sum(overlap(g, iv) for iv in ivs)
            if o > best:
                best, name = o, span
        out.append((name, (g[1] - g[0]) / 1e9))
    out.sort(key=lambda x: -x[1])
    return out[:top]


def top_ops(trace: Trace, device: str, top: int = 10
            ) -> List[Tuple[str, float]]:
    ranked = sorted(trace.op_time(device).items(), key=lambda kv: -kv[1])
    return [(n, t / 1e9) for n, t in ranked[:top]]
