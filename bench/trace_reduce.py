"""From a profiler trace to the numbers the per-layer metrics read.

``read_xspace`` keeps three things of JAX's ``.xplane.pb``: the
operations each TPU ran (its ``XLA Ops`` line), and the benchmark's own
host spans (``bench.*`` TraceAnnotations).  ``reduce`` turns them into:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* ``kernel_s[name]``: the summed device time of events that carry a
  kernel's name;
* ``device_ops``: the operations that took most device time, each by
  its self time (less the operations nested in it);
* ``idle_gaps``: device idle time by the host span that covers it
  (the pieces of each gap that no inner span covers go to the window).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

DEVICE_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def find_xspace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_xspace(path: str, n_devices: int) -> Dict[str, object]:
    """``{"device": [[events of TPU 0], ...], "host": [events]}`` with
    every time in nanoseconds on the profiler's one clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            evs = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    evs.extend((e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": [device.get(i, []) for i in range(n_devices)],
            "host": host}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """The operation's own name: TPU traces name an op by its HLO text,
    ``%fusion.12 = bf16[...] fusion(%param.3, ...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_family(event_name: str) -> str:
    """Group numbered instances of one operation: ``fusion.12`` ->
    ``fusion``."""
    return re.sub(r"[.\-_]\d+$", "", op_name(event_name))


def self_times(evs: Sequence[Event]) -> List[Tuple[str, int]]:
    """``(name, self ns)`` per event: its duration less that of the
    events nested in it (a ``while`` op holds its body's ops)."""
    out: List[List] = []
    stack: List[List] = []              # [end, index into out]
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return [(n, t) for n, t in out]


def _clip(evs: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in evs
            if s + d > lo and s < hi]


class HostSpans:
    """The benchmark's host spans as a timeline: the longest span (the
    window) around flat inner spans that do not overlap each other."""

    def __init__(self, spans: Sequence[Event]):
        spans = sorted(spans, key=lambda e: e[1])
        self.outer = max(spans, key=lambda e: e[2])[0] if spans \
            else "outside bench spans"
        longest = max((e[2] for e in spans), default=0)
        self.inner = [e for e in spans if e[2] < longest]
        self.starts = [e[1] for e in self.inner]

    def split(self, a: int, b: int) -> List[Tuple[str, int]]:
        """``[a, b)`` cut by the inner spans: ``(label, ns)`` pieces,
        the time no inner span covers labelled with the outer span."""
        out, covered = [], 0
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        while i < len(self.inner) and self.inner[i][1] < b:
            name, s, d = self.inner[i]
            ov = min(b, s + d) - max(a, s)
            if ov > 0:
                out.append((name, ov))
                covered += ov
            i += 1
        if b - a > covered:
            out.append((self.outer, b - a - covered))
        return out


def window_of(trace: Dict[str, object], name: str) -> Tuple[int, int]:
    """``(start_ns, end_ns)`` of the host span ``name`` (the traced
    window), on the trace's clock."""
    spans = [e for e in trace["host"] if e[0] == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    s = max(spans, key=lambda e: e[2])
    return s[1], s[1] + s[2]


def reduce(trace: Dict[str, object], start_ns: int, end_ns: int,
           kernels: Sequence[str] = (), top: int = 10) -> Dict[str, object]:
    """Numbers of the traced window ``[start_ns, end_ns]``."""
    devices: List[List[Event]] = trace["device"]
    host = HostSpans(trace["host"])
    busy, gaps = [], collections.Counter()
    gap_n = collections.Counter()
    ops = collections.Counter()
    kern = {k: 0.0 for k in kernels}
    for evs in devices:
        merged = union(_clip(evs, start_ns, end_ns))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        prev = start_ns
        for s, e in merged + [(end_ns, end_ns)]:
            if s > prev:
                for label, ns in host.split(prev, s):
                    gaps[label] += ns / 1e9
                    gap_n[label] += 1
            prev = max(prev, e)
        inside = [(n, max(s, start_ns), min(s + d, end_ns) - max(s, start_ns))
                  for n, s, d in evs if s + d > start_ns and s < end_ns]
        for name, t in self_times(inside):
            ops[op_family(name)] += t / 1e9
        for name, _, d in inside:
            own = op_name(name)
            for k in kernels:
                if k in own:
                    kern[k] += d / 1e9
    n = max(len(devices), 1)
    return {
        "window_s": (end_ns - start_ns) / 1e9,
        "busy_s": sum(busy) / n,
        "kernel_s": {k: v / n for k, v in kern.items()},
        "device_ops": [[k, v / n] for k, v in ops.most_common(top)],
        "idle_gaps": [[f"{k} ({gap_n[k]} gaps)", v / n]
                      for k, v in gaps.most_common(top)],
    }
