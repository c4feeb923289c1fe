"""Device time of the program's named training stages, from the same
profiler trace (``.xplane.pb``) that ``device_trace`` reduces.

    python bench/stage_trace.py <trace dir or .xplane.pb>  # the stages

* A device op's stage is the ``fleet.<stage>`` scope in its ``tf_op``
  (the op's ``op_name``: the name stack with the program's
  ``jax.named_scope``s). ``jax.profiler.ProfileData`` does not expose
  event metadata, so ``tf_op`` is read from the plane's
  ``event_metadata`` with a small protobuf wire-format reader (the
  standard library alone) and paired with ``ProfileData``'s events,
  which come in the same order. Each event is attributed on its own: a
  short name such as ``fusion.16`` can repeat across modules.
* The ops are those ``device_trace`` counts: the leaf events of the
  ``XLA Ops`` line of each device plane, clipped to the ``bench.window``
  host event, as a mean over the devices used. A capture without that
  event (an operator's own) is read from the first ``fleet.run`` span's
  start to the last one's end.
* An op inside the scan's ``while`` event is in the scan body: it
  counts for its stage, or, with no stage, for the remainder. An op
  outside any ``while`` is paid once a call (the ``fleet.prologue``,
  the layout copies around the scan, the host's key split).
* Steps and calls are the program's own ``fleet.run`` host spans inside
  the window: their ``steps`` and their number.

A trace of a program without these names gives no steps, and each
reader then reads nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
import tempfile
from typing import Dict, List, Optional

import device_trace

STAGES = ("act", "respond", "scenario", "update", "telemetry")
STAGE = re.compile(r"fleet\.(%s)\b" % "|".join(STAGES))
RUN_SPAN = "fleet.run"
#: the directories ``run.py`` captures its traced window in
CAPTURES = "bench-trace-*"


# -- protobuf wire format: just enough of tsl's xplane.proto ---------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message: an int for a varint, the
    (start, end) of a length-delimited field, None for fixed widths."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries) -> list:
    """The values (field 2) of a protobuf map's entries."""
    return [v for a, b in entries for f, v in _fields(buf, a, b) if f == 2]


def raw_planes(path: str) -> List[tuple]:
    """Per plane of the file, in order: (name, [[metadata id of each
    event] of each line], {event metadata id: tf_op})."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = []
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:                                  # XSpace.planes
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                ev_meta.append(v)
            elif f == 5:
                st_meta.append(v)
        if not device_trace.DEVICE_PLANE.match(name):
            out.append((name, [], {}))
            continue
        stat_names = {}
        for a, b in _map_values(buf, st_meta):        # XStatMetadata
            fs = dict(_fields(buf, a, b))
            stat_names[fs.get(1, 0)] = _text(buf, fs[2]) if 2 in fs else ""
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        tf_ops = {}
        for a, b in _map_values(buf, ev_meta):        # XEventMetadata
            mid, op = 0, ""
            for f, v in _fields(buf, a, b):
                if f == 1:
                    mid = v
                elif f == 5:                          # XStat
                    st = dict(_fields(buf, *v))
                    if st.get(1) in tf_op_ids:
                        op = (_text(buf, st[5]) if 5 in st
                              else stat_names.get(st.get(7), ""))
            tf_ops[mid] = op
        ids = []
        for a, b in lines:                            # XLine
            evs = [v for f, v in _fields(buf, a, b) if f == 4]
            ids.append([next((v for f, v in _fields(buf, *e) if f == 1), 0)
                        for e in evs])
        out.append((name, ids, tf_ops))
    return out


# -- the reduction ---------------------------------------------------------

@dataclasses.dataclass
class StageSummary:
    window_s: float
    busy_s: float
    #: stage -> device seconds under its scope inside the scan body
    stage_s: Dict[str, float]
    #: device seconds of ops outside the scan's ``while``
    call_s: float
    #: device seconds of scan-body ops under no stage
    rest_s: float
    #: the remainder's ops by name, each (seconds, tf_op)
    rest_ops: Dict[str, tuple]
    steps: int
    calls: int

    def per_step_ms(self, *stages: str) -> Optional[float]:
        if not self.steps:
            return None
        return 1e3 * sum(self.stage_s.get(s, 0.0) for s in stages) \
            / self.steps

    def per_call_ms(self) -> Optional[float]:
        return 1e3 * self.call_s / self.calls if self.calls else None

    def covered_s(self) -> float:
        return self.call_s + sum(self.stage_s.values())


def stage_of(tf_op: str) -> Optional[str]:
    m = STAGE.search(tf_op)
    return m.group(1) if m else None


def _device_ops(planes, raw, devices: int) -> list:
    """[(device, [(Event, tf_op)])] of the ``XLA Ops`` lines."""
    out = []
    for (pname, lines), (rname, ids, tf_ops) in zip(planes, raw):
        m = device_trace.DEVICE_PLANE.match(pname)
        if not m:
            continue
        if rname != pname or len(ids) != len(lines):
            raise ValueError(f"plane {pname!r}: the raw planes and "
                             f"ProfileData's disagree")
        evs = []
        for (lname, events), line_ids in zip(lines, ids):
            if lname != device_trace.OPS_LINE:
                continue
            if len(line_ids) != len(events):
                raise ValueError(f"{pname} {lname}: {len(line_ids)} raw "
                                 f"events against {len(events)}")
            evs += [(e, tf_ops.get(i, "")) for e, i in zip(events, line_ids)]
        out.append((int(m.group(2)), evs))
    return [ops for _, ops in sorted(out, key=lambda d: d[0])][:devices]


def reduce(planes, raw, devices: int) -> StageSummary:
    """``planes`` as ``device_trace.planes_of`` gives them, ``raw`` as
    ``raw_planes`` reads the same file."""
    host = [e for pname, lines in planes if pname.startswith("/host:")
            for _, evs in lines for e in evs]
    win = [e for e in host if e.name == device_trace.WINDOW] or \
        [e for e in host if e.name == RUN_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {device_trace.WINDOW!r} "
                         f"or {RUN_SPAN!r} host event")
    w0, w1 = min(e.start for e in win), max(e.end for e in win)
    runs = [e for e in host if e.name == RUN_SPAN
            and e.start >= w0 and e.end <= w1]
    dev = _device_ops(planes, raw, devices)
    if not dev:
        raise ValueError("the trace holds no device plane")
    stage_s: Dict[str, float] = {}
    call_s = rest_s = busy = 0.0
    rest_ops: Dict[str, tuple] = {}
    share = 1.0 / len(dev)
    for d_ops in dev:
        tf_op = {id(e): op for e, op in d_ops}
        events = [e for e, _ in d_ops]
        scans = sorted((e.start, e.end) for e in events
                       if e.name.endswith("(while)"))
        clipped = [(max(e.start, w0), min(e.end, w1), e)
                   for e in device_trace.leaves(events)
                   if e.end > w0 and e.start < w1]
        busy += share * sum(b - a for a, b in device_trace.union(
            [(a, b) for a, b, _ in clipped]))
        for a, b, e in clipped:
            sec = share * (b - a)
            in_scan = any(s0 <= e.start and e.end <= s1 for s0, s1 in scans)
            stage = stage_of(tf_op[id(e)]) if in_scan else None
            if stage:
                stage_s[stage] = stage_s.get(stage, 0.0) + sec
            elif not in_scan:
                call_s += sec
            else:
                rest_s += sec
                s, _ = rest_ops.get(e.name, (0.0, ""))
                rest_ops[e.name] = (s + sec, tf_op[id(e)])
    return StageSummary(window_s=w1 - w0, busy_s=busy, stage_s=stage_s,
                        call_s=call_s, rest_s=rest_s, rest_ops=rest_ops,
                        steps=sum(int(e.stats.get("steps", 0))
                                  for e in runs),
                        calls=len(runs))


def reduce_file(path: str, devices: int) -> StageSummary:
    planes = device_trace.planes_of(device_trace.load(path))
    return reduce(planes, raw_planes(path), devices)


def log_cover(s: StageSummary) -> None:
    """What the stages and the per-call ops cover of the busy time, and
    the unscoped remainder, on standard error."""
    cover = s.covered_s() / s.busy_s if s.busy_s > 0 else 0.0
    stages = {k: round(v, 6) for k, v in sorted(s.stage_s.items())}
    top = [(n, round(v, 6), op) for n, (v, op) in
           sorted(s.rest_ops.items(), key=lambda kv: -kv[1][0])[:5]]
    print(f"[bench] stages: {s.steps} steps in {s.calls} calls; {stages} "
          f"s, once a call {s.call_s:.6f} s; they cover {100 * cover:.3f}% "
          f"of busy {s.busy_s:.6f} s; unscoped in the scan "
          f"{s.rest_s:.6f} s, largest {top}", file=sys.stderr, flush=True)


_CACHE: Dict[tuple, Optional[StageSummary]] = {}


def _capture_of(ctx):
    """(path, planes) of the ``.xplane.pb`` that ``ctx.trace`` was
    reduced from, or None. ``run.py`` passes the readers the reduced
    trace but not its path; the capture is the newest ``bench-trace-*``
    directory in the temp directory whose window is ``ctx.trace``'s."""
    found = glob.glob(os.path.join(tempfile.gettempdir(), CAPTURES, "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        planes = device_trace.planes_of(device_trace.load(path))
        win = [e for pname, lines in planes if pname.startswith("/host:")
               for _, evs in lines for e in evs
               if e.name == device_trace.WINDOW]
        if win and abs(max(e.end for e in win) - min(e.start for e in win)
                       - ctx.trace.window_s) < 1e-9:
            return path, planes
    return None


def summary(ctx) -> Optional[StageSummary]:
    """The stage summary of the run's traced window, read once a run;
    None, with the reason on standard error, where it cannot be read."""
    key = (id(ctx.trace), ctx.trace.window_s)
    if key not in _CACHE:
        got = None
        try:
            found = _capture_of(ctx)
            if found is None:
                print("[bench] stages: the traced window's capture was not "
                      "found", file=sys.stderr, flush=True)
            else:
                path, planes = found
                got = reduce(planes, raw_planes(path), ctx.trace.devices)
                log_cover(got)
        except (OSError, ValueError, IndexError) as e:
            print(f"[bench] stages: unreadable: {e!r}", file=sys.stderr,
                  flush=True)
        _CACHE[key] = got
    return _CACHE[key]


if __name__ == "__main__":
    s = reduce_file(device_trace.find_xplane(sys.argv[1]), 1)
    log_cover(s)
    print({"update_ms": s.per_step_ms("update"),
           "telemetry_ms": s.per_step_ms("telemetry"),
           "env_ms": s.per_step_ms("act", "respond", "scenario"),
           "call_ms": s.per_call_ms()})
