"""Reduction of a JAX profiler trace to the numbers the readers take.

``load(dir)`` reads the ``*.xplane.pb`` the profiler wrote with
``jax.profiler.ProfileData`` and keeps three things:

- the device operations: every event on a TPU plane's ``XLA Ops`` line,
  with its name, start and duration, and the program it ran in (the
  ``XLA Modules`` event around it, e.g. ``jit__ks_mask_jit``);
- the benchmark's own host spans (``jax.profiler.TraceAnnotation``), by
  name;
- the traced window: the first to the last of those host spans.

Busy time is the union of the device operations' intervals, averaged over
the devices.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    device: str
    name: str
    start_ns: float
    duration_ns: float
    module: str = ""            # the program (jitted function) it ran in

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start_ns: float
    end_ns: float


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """Device operations and host spans of one traced window."""

    def __init__(self, ops: Sequence[DeviceOp], spans: Sequence[HostSpan],
                 window: Optional[Tuple[float, float]] = None):
        self.ops = list(ops)
        self.spans = list(spans)
        if window is None:
            pts = [(s.start_ns, s.end_ns) for s in self.spans] or \
                [(o.start_ns, o.end_ns) for o in self.ops] or [(0.0, 0.0)]
            window = (min(p[0] for p in pts), max(p[1] for p in pts))
        self.window = window
        self.devices = sorted({o.device for o in self.ops})

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def _clipped(self, device: Optional[str] = None):
        lo, hi = self.window
        for o in self.ops:
            if device is not None and o.device != device:
                continue
            s, e = max(o.start_ns, lo), min(o.end_ns, hi)
            if e > s:
                yield s, e

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(union_ns(self._clipped(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by program (the op's name where it has none)."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            label = o.module or o.name
            tot[label] = tot.get(label, 0.0) + o.duration_ns / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest stretches with no device operation on the first
        device, each named by the benchmark's host span around it
        (``unattributed`` where none covers it)."""
        dev = self.devices[0] if self.devices else None
        found = gaps(self._clipped(dev), *self.window)
        found.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in found[:n]:
            label = "unattributed"
            for sp in self.spans:
                if sp.start_ns <= s and e <= sp.end_ns:
                    label = sp.name
                    break
            out.append((label, (e - s) / 1e9))
        return out

    def breakdown(self) -> Dict:
        return {"device_ops": [list(x) for x in self.top_ops()],
                "idle_gaps": [list(x) for x in self.idle_gaps()]}


_MODULE_ID = re.compile(r"\(\d+\)$")


def load(trace_dir: str, window_host_span: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``.  The window
    runs from the first to the last host span named ``window_host_span``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            modules = sorted((float(ev.start_ns), float(ev.duration_ns),
                              _MODULE_ID.sub("", ev.name))
                             for ev in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            for ev in lines.get(OPS_LINE, []):
                t = float(ev.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                inside = i >= 0 and t < modules[i][0] + modules[i][1]
                ops.append(DeviceOp(plane.name, ev.name, t,
                                    float(ev.duration_ns),
                                    modules[i][2] if inside else ""))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_host_span:
                        spans.append(HostSpan(ev.name, float(ev.start_ns),
                                              float(ev.start_ns
                                                    + ev.duration_ns)))
    window = None
    if spans:
        window = (min(s.start_ns for s in spans),
                  max(s.end_ns for s in spans))
    return Trace(ops, spans, window)
