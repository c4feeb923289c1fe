"""``bench/device_trace.py`` on a trace recorded on a TPU v5e: the
tabular cell at 4,096 cells, 14 calls of 20 steps in a 0.53 s window
(``data/tabular_4096.xplane.pb``).

The expected numbers were counted by hand from the raw events: the
``bench.window`` host event, and the 280 ``fused_tabular_update``
custom-call events of the ``XLA Ops`` line of ``/device:TPU:0``."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import device_trace as bench_trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tabular_4096.xplane.pb")
WINDOW_NS = 529_222_064
KERNEL_NS = 394_180_317
KERNEL_EVENTS = 280          # 14 calls x 20 steps
KERNEL = r"^fused_tabular_update(\.\d+)? \(custom-call\)$"


@pytest.fixture(scope="module")
def planes():
    return bench_trace.planes_of(bench_trace.load(DATA))


@pytest.fixture(scope="module")
def summary(planes):
    return bench_trace.reduce(planes, 1)


def test_window_is_the_host_annotation(summary):
    assert summary.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-9)


def test_kernel_time_and_count(summary):
    seconds, events = summary.match(KERNEL)
    assert events == KERNEL_EVENTS
    assert seconds == pytest.approx(KERNEL_NS * 1e-9, abs=1e-9)


def test_busy_is_the_union_of_leaf_ops(planes, summary):
    """Busy time by a plain sweep over every op but the scan's ``while``
    (which spans its whole body), clipped to the window."""
    ops = [e for name, lines in planes if name == "/device:TPU:0"
           for lname, evs in lines if lname == "XLA Ops" for e in evs
           if "(while)" not in e.name]
    w0 = min(e.start for name, lines in planes if name == "/host:CPU"
             for _, evs in lines for e in evs if e.name == "bench.window")
    w1 = w0 + WINDOW_NS * 1e-9
    busy, end = 0.0, w0
    for e in sorted(ops, key=lambda e: e.start):
        a, b = max(e.start, end), min(e.end, w1)
        if b > a:
            busy += b - a
            end = b
    assert summary.busy_s == pytest.approx(busy, rel=1e-9)
    seconds, _ = summary.match(KERNEL)
    assert seconds < summary.busy_s < summary.window_s
    idle = 1 - summary.busy_s / summary.window_s
    assert 0.10 < idle < 0.16     # 4,096 cells: short ops, host gaps show


def test_breakdown_names_ops_and_gaps(summary):
    ops = summary.top_ops(10)
    assert ops[0][0].startswith("fused_tabular_update")
    assert len(ops) == 10 and all(s > 0 for _, s in ops)
    gaps = summary.top_gaps(10)
    assert len(gaps) == 10
    assert all(isinstance(w, str) and w for w, _ in gaps)
    assert gaps[0][1] >= gaps[-1][1] > 0
    # the gaps and the busy time fill the window
    assert sum(s for _, s in summary.gaps) + summary.busy_s == \
        pytest.approx(summary.window_s, rel=1e-9)


def test_leaves_drop_events_that_hold_others():
    E = bench_trace.Event
    outer, a, b, c = E("while", 0, 10), E("a", 1, 2), E("b", 3, 4), \
        E("c", 11, 12)
    assert [e.name for e in bench_trace.leaves([outer, a, b, c])] == \
        ["a", "b", "c"]


def test_short_names():
    assert bench_trace.short_name(
        "%copy.65 = f32[8,2]{1,0:T(8,128)} copy(f32[8,2]{0,1} %q.1)") == \
        "copy.65 (copy)"
    assert bench_trace.short_name(
        "%k.3 = (f32[8]{0:T(128)}, s32[8,1]{1,0:T(8,128)S(1)}) "
        "custom-call(s32[8]{0} %a), custom_call_target=\"x\"") == \
        "k.3 (custom-call)"
