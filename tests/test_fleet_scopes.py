"""Where FleetQLearning's training time goes, as the program names it.

Every op of the fused scan body sits under exactly one of five device
scopes (``fleet.act``, ``fleet.respond``, ``fleet.scenario``,
``fleet.update``, ``fleet.telemetry``) and the greedy gather before the
scan under ``fleet.prologue``; a profiler capture carries these names in
each device op's ``tf_op``. Each ``run`` call is a ``fleet.run`` host
span with its ``steps``, ``cells`` and ``update`` path. Checked on the CPU: the compiled
module's op metadata, and a CPU profiler capture.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.fleet import FleetConfig, FleetQConfig, FleetQLearning, \
    SyntheticSource
from repro.obs.spans import SpanRecorder, span

STAGES = ("act", "respond", "scenario", "update", "telemetry")
STAGE = re.compile(r"fleet\.(%s)\b" % "|".join(STAGES))
#: an op of the program's own body function: its name stack goes below
#: the scan body's call (``…/while/body/closed_call/…``); the scan's
#: trip counter and its stacking of the per-step outputs sit right at
#: ``…/while/body/<op>`` and belong to no stage
PROGRAM_OP = re.compile(r"while/body/[^/;]+/")


def _agent(impl, cells=64, **kw):
    src = SyntheticSource(FleetConfig(cells=cells, users=2,
                                      arrival_rate=1.0, p_r2w=0.05,
                                      p_w2r=0.1))
    return FleetQLearning(src, cfg=FleetQConfig(), seed=3, impl=impl, **kw)


def _computations(text):
    """name -> instruction lines of each computation of an HLO module."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            cur.append(line.strip())
    return comps


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


def _compiled(impl):
    ag = _agent(impl)
    return ag._run.lower(ag.q, ag.metrics, ag.counts, ag.scen, ag.eps,
                         jax.random.PRNGKey(0), 5).compile().as_text()


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_scan_body_ops_carry_exactly_one_stage_scope(impl):
    text = _compiled(impl)
    comps = _computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    scans = [ln for ln in comps[entry] if " while(" in ln
             and _op_name(ln).endswith("/while")]
    assert len(scans) == 1
    body = re.search(r"body=%?([\w.\-]+)", scans[0]).group(1)
    program = [(ln.split(" = ")[0], _op_name(ln)) for ln in comps[body]
               if PROGRAM_OP.search(_op_name(ln))]
    assert len(program) > 20
    seen = set()
    for name, op_name in program:
        stages = set(STAGE.findall(op_name))
        assert len(stages) == 1, (name, op_name)
        seen |= stages
    assert seen == set(STAGES)
    # the greedy gather before the scan, and nothing of the body, is
    # in the prologue
    prologue = [ln for ln in comps[entry] if "fleet.prologue/" in
                _op_name(ln)]
    assert any("gather" in _op_name(ln) for ln in prologue)
    assert not any("fleet.prologue" in _op_name(ln) for ln in comps[body])


def _capture(path, fn):
    jax.profiler.start_trace(str(path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    assert len(pb) == 1
    prof = jax.profiler.ProfileData.from_file(pb[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             {k: v for k, v in e.stats})
            for p in prof.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def test_run_emits_fleet_run_span_in_a_profiler_capture(tmp_path):
    """No recorder: ``run(5)`` still shows as ``fleet.run`` with its
    steps, cells and update path (``ref`` on the CPU), the blocking
    reads as ``fleet.run.fetch`` inside."""
    ag = _agent("pallas")
    ag.run(5)                                     # compile outside
    events = _capture(tmp_path, lambda: ag.run(5))
    runs = [e for e in events if e[0] == "fleet.run"]
    fetch = [e for e in events if e[0] == "fleet.run.fetch"]
    assert len(runs) == 1 and len(fetch) == 1
    assert runs[0][3] == {"steps": 5, "cells": 64, "update": "ref"}
    assert runs[0][1] <= fetch[0][1] <= fetch[0][2] <= runs[0][2]


def test_run_spans_on_a_recorder_and_unchanged_results():
    """With a recorder the same spans land in the Chrome JSON, and the
    trajectory is bit-identical to an agent without one."""
    rec = SpanRecorder()
    a, b = _agent("pallas", spans=rec), _agent("pallas")
    ms_a, _ = a.run(6)
    ms_b, _ = b.run(6)
    np.testing.assert_array_equal(ms_a, ms_b)
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    names = [e["name"] for e in rec.events]
    assert names == ["fleet.run.fetch", "fleet.run"]
    assert rec.events[1]["args"] == {"steps": 6, "cells": 64,
                                      "update": "ref"}


def test_span_without_recorder_enters_a_trace_annotation(tmp_path):
    assert isinstance(span(None, "x", k=1), jax.profiler.TraceAnnotation)

    def annotated():
        with span(None, "outer.none", k=7):
            with span(None, "inner.none"):
                pass

    events = _capture(tmp_path, annotated)
    got = {e[0]: e for e in events if e[0].endswith(".none")}
    assert set(got) == {"outer.none", "inner.none"}
    assert got["outer.none"][3] == {"k": 7}
    assert got["outer.none"][1] <= got["inner.none"][1]


@pytest.mark.parametrize("n_edges", [None, 16])
def test_contention_scope_only_where_cells_share_edges(n_edges):
    """With a topology the shared-edge sums sit under ``fleet.contention``
    inside ``fleet.respond``; isolated cells have no such ops."""
    src = SyntheticSource(FleetConfig(cells=64, users=2, p_r2w=0.05,
                                      p_w2r=0.05, n_edges=n_edges))
    ag = FleetQLearning(src, cfg=FleetQConfig(), seed=3, impl="ref")
    text = ag._run.lower(ag.q, ag.metrics, ag.counts, ag.scen, ag.eps,
                         jax.random.PRNGKey(0), 5).compile().as_text()
    names = [_op_name(ln) for ln in text.splitlines()
             if "fleet.contention" in _op_name(ln)]
    if n_edges is None:
        assert names == []
    else:
        assert any("segment_sum" in n or "scatter-add" in n for n in names)
        assert all("fleet.respond/fleet.contention/" in n for n in names)
