"""``bench/stage_trace.py`` and the readers of the training stages.

Two traces recorded on a TPU v5e at 4,096 cells, 14 calls of 20 steps
in a 0.53 s window (``bench/tests/record_trace.py``):

* ``data/tabular_scoped_4096.xplane.pb``, with the program's
  ``fleet.*`` scopes and ``fleet.run`` spans. The expected numbers were
  counted by hand from the raw events in picoseconds (the ``tf_op`` of
  each event's metadata, each op's interval against the 14 ``while``
  events and the window); ``ProfileData`` gives each event in whole
  nanoseconds, so each sum may differ by up to 1 ns an event;
* ``data/tabular_4096.xplane.pb``, from before the program named its
  stages: it holds ``tf_op``s but no ``fleet.*`` scope and no
  ``fleet.run`` span, so every reader reads nothing there, as on a
  parent commit without the names.
"""
import os
import shutil
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import device_trace  # noqa: E402
import spec  # noqa: E402
import stage_trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED = os.path.join(DATA, "tabular_4096.xplane.pb")
SCOPED = os.path.join(DATA, "tabular_scoped_4096.xplane.pb")
STEPS, CALLS = 280, 14
#: stage -> (picoseconds inside the window, events), counted by hand
HAND = {"act": (3_436_290_954, 4480), "respond": (5_575_011_484, 3920),
        "scenario": (67_748_742, 560), "telemetry": (32_246_890_708, 4480),
        "update": (394_609_778_452, 1120),
        "call": (20_339_781_326, 1732),      # outside the 14 whiles
        "rest": (442_964_454, 1400)}         # inside, under no stage
#: reader -> (the stages it sums, what it divides by)
READ = {"update_ms.train": (("update",), STEPS),
        "telemetry_ms.train": (("telemetry",), STEPS),
        "env_ms.train": (("act", "respond", "scenario"), STEPS),
        "call_ms.train": (("call",), CALLS)}
READERS = ("update_ms.train", "telemetry_ms.train", "env_ms.train",
           "call_ms.train")
#: counted from the raw events: the 280 kernel events' durations, all of
#: them inside the scan's ``while`` events
UNSCOPED_KERNEL_NS = 394_180_317


def _ctx(path):
    trace = device_trace.reduce_dir(path, 1)
    return spec.ReaderContext(trace=trace, work={}, window={}, system=None,
                              device_kind="TPU v5 lite")


@pytest.fixture
def captured(tmp_path, monkeypatch):
    """Put a recorded trace where ``run.py`` leaves its capture."""
    monkeypatch.setattr(stage_trace.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    stage_trace._CACHE.clear()

    def put(src):
        d = tmp_path / "bench-trace-x" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copyfile(src, d / "host.xplane.pb")
        return _ctx(src)
    yield put
    stage_trace._CACHE.clear()


@pytest.mark.parametrize("name", READERS)
def test_readers_on_the_scoped_trace(captured, name):
    ctx = captured(SCOPED)
    stages, per = READ[name]
    ps = sum(HAND[k][0] for k in stages)
    events = sum(HAND[k][1] for k in stages)
    got = spec.load_module("metrics", name).read(ctx)
    assert got == pytest.approx(ps * 1e-9 / per, abs=events * 1e-6 / per)


def test_scoped_trace_stages_cover_the_busy_time():
    s = stage_trace.reduce_file(SCOPED, 1)
    assert (s.steps, s.calls) == (STEPS, CALLS)
    for stage, (ps, events) in HAND.items():
        sec = {"call": s.call_s, "rest": s.rest_s}.get(stage,
                                                      s.stage_s.get(stage))
        assert sec == pytest.approx(ps * 1e-12, abs=events * 1e-9), stage
    busy = device_trace.reduce_dir(SCOPED, 1).busy_s
    assert s.busy_s == pytest.approx(busy, rel=1e-12)
    assert s.covered_s() / busy > 0.99
    assert s.covered_s() + s.rest_s == pytest.approx(busy, rel=1e-6)


def test_tf_ops_pair_with_the_device_events():
    raw = stage_trace.raw_planes(UNSCOPED)
    planes = device_trace.planes_of(device_trace.load(UNSCOPED))
    assert [r[0] for r in raw] == [p[0] for p in planes]
    ops = stage_trace._device_ops(planes, raw, 1)[0]
    kernel = [op for e, op in ops if e.name.startswith(
        "fused_tabular_update")]
    assert len(kernel) == 280
    assert all(op.startswith("jit(run)/while/body/closed_call/jit("
                             "fused_tabular_update)/pallas_call")
               for op in kernel)


def test_unscoped_trace_has_no_steps_and_its_kernel_is_remainder():
    s = stage_trace.reduce_file(UNSCOPED, 1)
    assert s.steps == 0 and s.calls == 0 and s.stage_s == {}
    assert s.per_step_ms("update") is None and s.per_call_ms() is None
    kernel = sum(v for n, (v, _) in s.rest_ops.items()
                 if n.startswith("fused_tabular_update"))
    assert kernel == pytest.approx(UNSCOPED_KERNEL_NS * 1e-9, abs=1e-9)
    # every leaf op is outside the scan or its remainder
    assert s.call_s + s.rest_s == pytest.approx(s.busy_s, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_program_names(captured, name):
    ctx = captured(UNSCOPED)
    assert spec.load_module("metrics", name).read(ctx) is None
    assert stage_trace.summary(ctx).steps == 0      # found, and read


def test_no_capture_reads_nothing(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(stage_trace.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    stage_trace._CACHE.clear()
    ctx = types.SimpleNamespace(trace=types.SimpleNamespace(
        window_s=0.5, devices=1))
    assert stage_trace.summary(ctx) is None
    assert "not found" in capsys.readouterr().err
    stage_trace._CACHE.clear()


def test_stage_of_names():
    assert stage_trace.stage_of(
        "jit(run)/while/body/closed_call/fleet.update/jit("
        "fused_tabular_update)/pallas_call:") == "update"
    assert stage_trace.stage_of(
        "jit(run)/while/body/closed_call/fleet.act/max;while/body/"
        "closed_call") == "act"
    assert stage_trace.stage_of("jit(run)/fleet.prologue/gather") is None
    assert stage_trace.stage_of("fleet.run") is None
    assert stage_trace.stage_of("") is None


# -- a trace built by hand: every number below is known exactly -----------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(name, line, events, ev_meta, st_meta):
    """``events``: (metadata id, start ns, end ns, [(stat id, int)])."""
    evs = [(4, _msg((1, m), (2, a * 1000), (3, (b - a) * 1000),
                    *[(4, _msg((1, k), (4, v))) for k, v in stats]))
           for m, a, b, stats in events]
    return _msg((2, name), (3, _msg((2, line), (3, 0), *evs)),
                *[(4, _msg((1, k), (2, v))) for k, v in ev_meta.items()],
                *[(5, _msg((1, k), (2, _msg((1, k), (2, v)))))
                  for k, v in st_meta.items()])


def _meta(mid, hlo, tf_op=None, ref=None):
    stats = [] if tf_op is None else [(5, _msg((1, 1), (5, tf_op)))]
    if ref is not None:
        stats = [(5, _msg((1, 1), (7, ref)))]
    return _msg((1, mid), (2, hlo), *stats)


BODY = "jit(run)/while/body/closed_call/"
DEVICE_META = {
    1: _meta(1, "%while.1 = (s32[]) while(%t)", "jit(run)/while"),
    2: _meta(2, "%fused_tabular_update.3 = f32[8]{0} custom-call(%a)",
             BODY + "fleet.update/jit(fused_tabular_update)/pallas_call"),
    3: _meta(3, "%fusion.4 = s32[8]{0} fusion(%a)",
             BODY + "fleet.telemetry/scatter-add"),
    4: _meta(4, "%fusion.5 = f32[8]{0} fusion(%a)",
             BODY + "fleet.respond/mul"),
    5: _meta(5, "%fusion.6 = s32[8]{0} fusion(%a)", ref=9),  # fleet.act
    6: _meta(6, "%fusion.7 = s32[8]{0} fusion(%a)",
             BODY + "fleet.scenario/add"),
    7: _meta(7, "%add.8 = s32[] add(%i, %c)", "jit(run)/while/body/add"),
    8: _meta(8, "%copy.9 = f32[8]{0} copy(%q)"),
    9: _meta(9, "%fusion.10 = f32[8]{0} fusion(%q)",
             "jit(run)/fleet.prologue/gather"),
}
#: ns; window 1,000-11,000; two calls in it, each a prologue, a copy, a
#: scan of two steps and a copy; one op half out of the window
DEVICE_EVENTS = [
    (8, 500, 900), (9, 1500, 1600), (8, 1600, 1900),
    (1, 2000, 5000),
    (5, 2000, 2100), (4, 2100, 2200), (6, 2200, 2250), (2, 2250, 3150),
    (3, 3150, 3450), (7, 3450, 3460),
    (5, 3500, 3600), (4, 3600, 3700), (6, 3700, 3750), (2, 3750, 4650),
    (3, 4650, 4950), (7, 4950, 4960),
    (8, 5000, 5200),
    (9, 6000, 6100), (1, 6200, 10000),
    (5, 6200, 6300), (4, 6300, 6400), (6, 6400, 6450), (2, 6450, 7350),
    (3, 7350, 7650), (7, 7650, 7660),
    (5, 7700, 7800), (4, 7800, 7900), (6, 7900, 7950), (2, 7950, 8850),
    (3, 8850, 9150), (7, 9150, 9160),
    (8, 10900, 11100),
]
HOST_EVENTS = [(1, 1000, 11000, []), (2, 1400, 5300, [(1, 2)]),
               (2, 5900, 10950, [(1, 2)]), (2, 11500, 12000, [(1, 9)])]


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    path = tmp_path_factory.mktemp("built") / "built.xplane.pb"
    space = _msg(
        (1, _plane("/host:CPU", "python", HOST_EVENTS,
                   {1: _msg((1, 1), (2, "bench.window")),
                    2: _msg((1, 2), (2, "fleet.run"))}, {1: "steps"})),
        (1, _plane("/device:TPU:0", "XLA Ops",
                   [(m, a, b, []) for m, a, b in DEVICE_EVENTS],
                   DEVICE_META,
                   {1: "tf_op", 9: BODY + "fleet.act/jit(_uniform)/xor"})))
    path.write_bytes(space)
    return stage_trace.reduce_file(str(path), 1)


def test_built_trace_by_stage(built):
    assert built.window_s == pytest.approx(10_000e-9)
    assert (built.steps, built.calls) == (4, 2)      # the third is outside
    ns = {k: round(v * 1e9) for k, v in built.stage_s.items()}
    assert ns == {"update": 4 * 900, "telemetry": 4 * 300,
                  "act": 4 * 100, "respond": 4 * 100, "scenario": 4 * 50}
    # prologues 2 x 100, copies 300 + 200 + 100 (clipped at the window's
    # end); the copy before the window is left out
    assert round(built.call_s * 1e9) == 200 + 300 + 200 + 100
    assert round(built.rest_s * 1e9) == 4 * 10       # the trip counter
    assert list(built.rest_ops) == ["add.8 (add)"]
    assert built.per_step_ms("update") == pytest.approx(900e-6)
    assert built.per_step_ms("act", "respond", "scenario") == \
        pytest.approx(250e-6)
    assert built.per_call_ms() == pytest.approx(400e-6)
    assert built.covered_s() + built.rest_s == pytest.approx(built.busy_s)
