"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-op device time and the longest idle gaps with what the host did.

    python bench/device_trace.py <trace dir or .xplane.pb>  # what it holds

* The window is the host's ``bench.window`` annotation.
* Device ops are the events of the ``XLA Ops`` line of each
  ``/device:<kind>:<n>`` plane, clipped to the window, leaving out the
  ops that hold others (a ``while`` loop spans every op of its body). A
  device's busy time is the union of their intervals; ``busy_s`` is its
  mean over the devices used.
* An op is named by its HLO instruction and opcode, as
  ``fused_tabular_update.11 (custom-call)``; the event's full HLO text
  is kept in its stats.
* An idle gap is a stretch of the window in which the first device runs
  no op. It is named by the innermost host event (any host thread) that
  covers its middle: what the host was doing while the device waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:([A-Z_]+):(\d+)$")
HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds
    end: float
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    #: op name -> (device seconds, events), each a mean over the devices
    ops: Dict[str, Tuple[float, float]]
    #: op name -> the stats of its first event (HLO names and the like)
    op_stats: Dict[str, dict]
    #: idle gaps on the first device: (middle, seconds)
    gaps: List[Tuple[float, float]]
    devices: int
    host: List[Event] = dataclasses.field(default_factory=list, repr=False)

    def top_ops(self, n: int) -> list:
        rows = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:n]
        return [[name, sec] for name, (sec, _) in rows]

    def top_gaps(self, n: int) -> list:
        """The ``n`` longest gaps, each named by what the host did."""
        return [[_host_doing(self.host, mid), sec] for mid, sec in
                sorted(self.gaps, key=lambda g: -g[1])[:n]]

    def match(self, pattern: str) -> Tuple[float, float]:
        """(device seconds, events) of the ops whose name or HLO stats
        match ``pattern``, each a mean over the devices."""
        rx = re.compile(pattern)
        sec = cnt = 0.0
        for name, (s, c) in self.ops.items():
            texts = [name] + [str(v) for v in self.op_stats[name].values()]
            if any(rx.search(t) for t in texts):
                sec += s
                cnt += c
        return sec, cnt


def short_name(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3 (fusion)``."""
    m = HLO.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        stats = {k: v for k, v in e.stats}
        name = short_name(e.name)
        if name != e.name:
            stats["hlo"] = e.name
        out.append(Event(name, start, start + e.duration_ns * 1e-9, stats))
    return out


def leaves(events: List[Event]) -> List[Event]:
    """The events that hold no other event of the same line."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            top, holds = stack.pop()
            if not holds:
                out.append(top)
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(e for e, holds in stack if not holds)
    return out


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(planes, devices: int) -> TraceSummary:
    """``planes``: (plane name, [(line name, [Event])]) pairs."""
    host, dev = [], []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if m:
            ops = leaves([e for lname, evs in lines if lname == OPS_LINE
                          for e in evs])
            dev.append((int(m.group(2)), ops))
        elif pname.startswith("/host:"):
            host.extend(e for _, evs in lines for e in evs)
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} host event")
    w0, w1 = min(e.start for e in win), max(e.end for e in win)
    dev = [ops for _, ops in sorted(dev, key=lambda d: d[0])][:devices]
    if not dev or not any(dev):
        raise ValueError("the trace holds no device op in the window")
    busy, ops, stats = [], {}, {}
    first_union = None
    for d_ops in dev:
        clipped = [(max(e.start, w0), min(e.end, w1), e) for e in d_ops
                   if e.end > w0 and e.start < w1]
        u = union([(a, b) for a, b, _ in clipped])
        if first_union is None:
            first_union = u
        busy.append(sum(b - a for a, b in u))
        for a, b, e in clipped:
            sec, cnt = ops.get(e.name, (0.0, 0.0))
            ops[e.name] = (sec + (b - a) / len(dev), cnt + 1.0 / len(dev))
            stats.setdefault(e.name, e.stats)
    gaps, t = [], w0
    for a, b in first_union + [(w1, w1)]:
        if a > t:
            gaps.append(((t + a) / 2, a - t))
        t = max(t, b)
    return TraceSummary(window_s=w1 - w0, busy_s=sum(busy) / len(busy),
                        ops=ops, op_stats=stats, gaps=gaps,
                        devices=len(dev), host=host)


def _host_doing(host: List[Event], t: float) -> str:
    best = None
    for e in host:
        if e.start <= t <= e.end and e.name != WINDOW:
            if best is None or e.end - e.start < best.end - best.start:
                best = e
    return best.name if best else "host: no event"


def planes_of(profile) -> list:
    return [(p.name, [(ln.name, _events(ln)) for ln in p.lines])
            for p in profile.planes]


def reduce_dir(path: str, devices: int) -> TraceSummary:
    return reduce(planes_of(load(find_xplane(path))), devices)


def describe(path: str, per_line: int = 5) -> None:
    for pname, lines in planes_of(load(find_xplane(path))):
        print(f"plane {pname!r}: {len(lines)} lines")
        for lname, evs in lines:
            print(f"  line {lname!r}: {len(evs)} events")
            for e in evs[:per_line]:
                print(f"    {e.name!r} {e.start:.6f}+{e.end - e.start:.6f}"
                      f" {dict(list(e.stats.items())[:6])}")


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 5)
