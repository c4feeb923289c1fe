"""Async serving bridge (ISSUE-9): conservation identities under
overload, deadline-aware admission, timeout/reroute fault injection,
drain-timeout flush, and the route(bridge=...) end-to-end path against
real engines."""
import time

import numpy as np
import pytest

from repro.obs.spans import SpanRecorder, validate_chrome_trace
from repro.serving import BridgeConfig, Request, ServingBridge


class StubEngine:
    """serve_batch-compatible stand-in: stamps the same fields as
    ``ServingEngine.serve_batch`` without a model. ``wall_s`` holds the
    engine busy so queues back up deterministically."""

    def __init__(self, wall_s: float = 0.0):
        self.wall_s = wall_s
        self.calls = 0

    def serve_batch(self, reqs, toks, spans=None, t_drain=None):
        self.calls += 1
        if self.wall_s:
            time.sleep(self.wall_s)
        t_drain = time.perf_counter() if t_drain is None else t_drain
        raw = max(self.wall_s, 1e-4)
        for i, r in enumerate(reqs):
            r.output = np.asarray(toks[i][:1])
            r.response_time = raw
            r.queue_time = max(0.0, t_drain - r.arrival_time)
            r.serve_time = raw
            r.deadline_met = \
                (r.queue_time + r.response_time) * 1e3 <= r.deadline_ms
        return reqs


def _req(rid, **kw):
    return Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                   max_new_tokens=1, **kw)


def _assert_conserved(st):
    assert st["submitted"] == st["admitted"] + st["shed"]["overflow"] \
        + st["shed"]["deadline"]
    assert st["served"] + st["shed"]["total"] == st["submitted"]
    assert len(st["shed_requests"]) == st["shed"]["total"]


def test_bridge_overload_sheds_and_conserves():
    """A bounded queue under overload sheds instead of growing; every
    counter balances and every shed request is itemized."""
    eng = StubEngine(wall_s=0.05)
    cfg = BridgeConfig(max_batch=2, max_wait_ms=0.0, max_queue=4,
                       drain_timeout_s=30.0)
    with ServingBridge({"S": {"d0": eng}}, cfg) as br:
        for i in range(40):
            br.submit(_req(i), "S", "d0")
        assert br.drain()
        st = br.stats()
    assert st["submitted"] == 40
    assert st["shed"]["overflow"] > 0          # overload actually shed
    assert st["served"] == st["admitted"]      # clean drain: no leftovers
    _assert_conserved(st)
    assert all(s["reason"] == "overflow" for s in st["shed_requests"])
    # served requests carry e2e stamps (queue grows as the queue backs up)
    assert eng.calls >= st["served"] / cfg.max_batch


def test_bridge_deadline_admission():
    """A request whose SLO budget is exhausted at submit is shed as
    shed_deadline (False from submit), not queued."""
    with ServingBridge({"S": {"d0": StubEngine()}}, BridgeConfig()) as br:
        late = _req(0, deadline_ms=5.0,
                    arrival_time=time.perf_counter() - 1.0)  # 1000ms ago
        assert br.submit(late, "S", "d0") is False
        assert br.submit(_req(1, deadline_ms=1e6), "S", "d0") is True
        assert br.submit(_req(2), "S", "d0") is True          # inf deadline
        assert br.drain()
        st = br.stats()
    assert st["shed"]["deadline"] == 1 and st["served"] == 2
    _assert_conserved(st)
    assert st["shed_requests"][0] == {"rid": 0, "tier": "S",
                                      "variant": "d0", "reason": "deadline"}


def test_bridge_unknown_tier_raises():
    with ServingBridge({"S": {"d0": StubEngine()}}, BridgeConfig()) as br:
        with pytest.raises(KeyError):
            br.submit(_req(0), "E", "d0")


def test_bridge_timeout_reroutes_once_then_serves():
    """Fault injection: a hung tier's batch times out; its requests are
    rerouted once to the fallback tier, served there, and every event
    lands in the span stream."""
    spans = SpanRecorder()
    hung, fast = StubEngine(wall_s=1.0), StubEngine()
    cfg = BridgeConfig(max_batch=4, max_wait_ms=0.0, engine_timeout_s=0.1)
    with ServingBridge({"S": {"d0": hung}, "E": {"d0": fast}}, cfg,
                       spans=spans) as br:
        for i in range(3):
            br.submit(_req(i), "S", "d0")
        assert br.drain()
        st = br.stats()
    assert st["timeouts"] >= 1 and st["rerouted"] == 3
    assert st["served"] == 3 and st["shed"]["total"] == 0
    _assert_conserved(st)
    # rerouted requests were served by the fallback engine
    assert fast.calls >= 1
    names = {e["name"] for e in spans.events}
    assert {"bridge.timeout", "bridge.reroute"} <= names
    validate_chrome_trace(spans.chrome_trace())


def test_bridge_timeout_sheds_without_fallback():
    """The same fault with rerouting disabled: requests shed as
    shed_timeout and the drain still completes."""
    spans = SpanRecorder()
    cfg = BridgeConfig(max_batch=4, max_wait_ms=0.0, engine_timeout_s=0.1,
                       reroute={})
    with ServingBridge({"S": {"d0": StubEngine(wall_s=1.0)}}, cfg,
                       spans=spans) as br:
        for i in range(3):
            br.submit(_req(i), "S", "d0")
        assert br.drain()
        st = br.stats()
    assert st["shed"]["timeout"] == 3 and st["served"] == 0
    _assert_conserved(st)
    assert {e["name"] for e in spans.events} >= {"bridge.timeout",
                                                "bridge.shed"}


def test_bridge_drain_timeout_flushes():
    """A drain past its budget flushes queued + in-flight requests as
    shed_drain (returns False) so the identities still balance."""
    cfg = BridgeConfig(max_batch=2, max_wait_ms=0.0, engine_timeout_s=30.0)
    with ServingBridge({"S": {"d0": StubEngine(wall_s=2.0)}}, cfg) as br:
        for i in range(6):
            br.submit(_req(i), "S", "d0")
        assert br.drain(timeout_s=0.2) is False
        st = br.stats()
    assert st["shed"]["drain"] > 0 and st["served"] == 0
    _assert_conserved(st)


def test_bridge_oversize_submit_splits_batches():
    """More queued requests than max_batch split into several engine
    calls (RequestBatcher.pack), never truncate."""
    eng = StubEngine(wall_s=0.01)
    cfg = BridgeConfig(max_batch=3, max_wait_ms=50.0, max_queue=64)
    with ServingBridge({"S": {"d0": eng}}, cfg) as br:
        for i in range(8):
            br.submit(_req(i), "S", "d0")
        assert br.drain()
        st = br.stats()
    assert st["served"] == 8
    _assert_conserved(st)
    assert all(b["requests"] <= cfg.max_batch for b in br.batch_log)
    assert sum(b["requests"] for b in br.batch_log) == 8


def test_route_bridge_reuse_per_call_accounting():
    """A ServingBridge reused across route() calls accounts each call
    separately: served/batches/compute are per call, not cumulative."""
    from types import SimpleNamespace

    import jax

    from repro.fleet import FleetConfig, init_fleet
    from repro.fleet.api import FleetOrchestrator, StaticPolicy

    scen = init_fleet(jax.random.PRNGKey(0),
                      FleetConfig(cells=4, users=3, arrival_rate=None))
    n_active = int(np.asarray(scen.active).sum())
    eng = StubEngine(wall_s=0.01)
    eng.model = SimpleNamespace(cfg=SimpleNamespace(vocab_size=32))
    engines = {"S": {"d0": eng}}
    orch = FleetOrchestrator(StaticPolicy(3, "device"))
    with ServingBridge(engines, BridgeConfig(max_batch=4)) as br:
        r1 = orch.route(scen=scen, dispatch=engines, bridge=br,
                        max_new_tokens=1, batch_size=4)
        r2 = orch.route(scen=scen, dispatch=engines, bridge=br,
                        max_new_tokens=1, batch_size=4)
    for r in (r1, r2):
        assert len(r.served) == n_active
        per = r.timings["per_tier_variant"]["S/d0"]
        assert per["requests"] == n_active
        # per-call batches cover exactly this call's requests
        assert 1 <= r.batches <= -(-n_active // 4) + 1
    # cumulative bridge stats still conserve over BOTH calls
    st = r2.bridge
    assert st["submitted"] == 2 * n_active
    assert st["served"] + st["shed"]["total"] == st["submitted"]


class FailingEngine(StubEngine):
    """An engine whose every call raises, as a compile or out-of-memory
    failure on the device would."""

    def serve_batch(self, reqs, toks, spans=None, t_drain=None):
        self.calls += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")


def test_bridge_counts_engine_exceptions():
    """An engine that raises is rerouted like a timeout, but the
    exception is counted in stats() with its type and message, and
    route(bridge=...) surfaces it in RouteResult.summary()."""
    from types import SimpleNamespace

    import jax

    from repro.fleet import FleetConfig, init_fleet
    from repro.fleet.api import FleetOrchestrator, StaticPolicy

    scen = init_fleet(jax.random.PRNGKey(0),
                      FleetConfig(cells=2, users=3, arrival_rate=None))
    n_active = int(np.asarray(scen.active).sum())
    bad, good = FailingEngine(), StubEngine()
    bad.model = SimpleNamespace(cfg=SimpleNamespace(vocab_size=32))
    engines = {"S": {"d0": bad}, "E": {"d0": good}}
    res = FleetOrchestrator(StaticPolicy(3, "device")).route(
        scen=scen, dispatch=engines, bridge=BridgeConfig(max_batch=8),
        max_new_tokens=1, batch_size=8)
    st = res.summary()["bridge"]
    assert st["engine_errors"], "the raising engine went uncounted"
    err = st["engine_errors"][0]
    assert (err["tier"], err["variant"], err["type"]) == ("S", "d0",
                                                          "RuntimeError")
    assert "out of device memory" in err["message"]
    assert sum(e["requests"] for e in st["engine_errors"]) == n_active
    # still rerouted: every request was served by the fallback tier
    assert st["rerouted"] == n_active and st["served"] == n_active
    assert {r.tier for r in res.served} == {"E"}
    _assert_conserved(st)
