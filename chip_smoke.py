"""Bring-up smoke of the system's main path on a TPU.

    python chip_smoke.py             # one chip: the phases below, in order
    python chip_smoke.py --chips 4   # the sharded fleet on four chips, only

One chip, one process. Everything is built from the committed code and
seeds; a failed check raises, which exits non-zero before the result
line. The phases:

* device  — ``jax.devices()`` must be TPU; there is no CPU fallback.
* tabular — ``FleetQLearning(impl="pallas")`` trains 65,536 five-user
  cells drawn from the paper's Table-5 link mixes: a (65,536, 36, 243)
  f32 Q-table, 2.29 GB, for a few hundred steps inside the jitted scan,
  through the compiled ``tabular_rl`` kernel. Then the kernel trains
  4,096 cells for 50 steps next to ``impl="ref"`` from the same seed.
* dqn     — ``FleetDQN(impl="pallas")``, default config (hidden 128,
  ``topk=5``) with the QoS goal at 85%, on 16,384 five-user cells of a
  Zipf-skewed shared-edge topology, a few hundred steps; its decisions
  are checked against ``impl="ref"`` on the same params and scenario.
* served  — the edge-ladder model at its full config width (variants
  d0, d4, d7) behind ``FleetOrchestrator.route(bridge=True)``: waves of
  a held-out 64-cell fleet routed cold by the trained DQN, through the
  ``ServingBridge`` into warmed ``ServingEngine``s.
* sharded (``--chips 4`` only) — ``FleetQLearning`` on
  ``shard.fleet_mesh()`` over four chips with a shard-local topology,
  against the same seed on one device.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

USERS = 5
#: |Q_kernel - Q_ref| bound on the tabular parity check: the kernel and
#: XLA may round the TD multiply-add differently, one ulp per update
Q_ATOL = Q_RTOL = 1e-6


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends compiling (backend compile, persistent-cache
    reads included) and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.cache_hits

    def since(self, mark) -> str:
        return (f"compile {self.seconds - mark[0]:.2f} s "
                f"({self.cache_hits - mark[1]} persistent-cache hits)")


def require_tpu():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"chip_smoke.py needs a TPU, and JAX found none: "
            f"{len(devs)} {devs[0].platform} device(s) of kind "
            f"{devs[0].device_kind!r}")
    return devs


def device_bytes() -> str:
    st = jax.devices()[0].memory_stats() or {}
    return (f"{st.get('bytes_in_use', 0) / 1e9:.2f} GB in use, "
            f"peak {st.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def _timed_runs(agent, steps: int, calls: int = 3):
    """``calls`` scans of ``steps // calls`` steps; the first compiles.
    Returns (first-call s, steady s per step, fleet-mean ms traces)."""
    n = steps // calls
    t0 = time.perf_counter()
    traces = [agent.run(n)[0]]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(calls - 1):
        traces.append(agent.run(n)[0])
    steady = (time.perf_counter() - t0) / (n * (calls - 1))
    return first, steady, np.concatenate(traces)


def _check_kernel_impl(agent, impl: str, phase: str) -> None:
    check(agent._op_impl == impl,
          f"{phase}: impl={impl!r} resolved to {agent._op_impl!r}")
    if impl == "pallas":      # a chip run must never get interpret mode
        path = getattr(agent, "path", None)       # the tabular agent's
        interpret = (path.interpret if path is not None
                     else agent._op_kwargs["interpret"])
        check(not interpret, f"{phase}: the kernel runs interpreted")


# ---------------------------------------------------------------- phases --
def phase_tabular(clock, cells=65536, steps=300, parity_cells=4096,
                  parity_steps=50, impl="pallas", seed=0):
    from repro.fleet import FleetConfig, FleetQLearning, mixed_table5_fleet

    def agent(n, which, key):
        scen = mixed_table5_fleet(jax.random.PRNGKey(key), n, users=USERS)
        return FleetQLearning(scen, FleetConfig(cells=n, users=USERS),
                              seed=seed, impl=which)

    mark = clock.mark()
    tab = agent(cells, impl, seed)
    _check_kernel_impl(tab, impl, "tabular")
    check(tab.q.shape == (cells, 36, 243), f"Q-table {tab.q.shape}")
    log("tabular", f"impl={tab._op_impl} "
        f"interpret={tab.path.interpret} cells={cells} "
        f"Q {tab.q.shape} f32 = {tab.q.nbytes / 1e9:.2f} GB; "
        f"device {device_bytes()}")
    first, steady, ms = _timed_runs(tab, steps)
    check(tab.steps == steps, f"ran {tab.steps} of {steps} steps")
    check(np.isfinite(ms).all() and bool(jnp.isfinite(tab.q).all()),
          "non-finite fleet response or Q-table")
    log("tabular", f"{tab.steps} scan steps: first call {first:.2f} s, "
        f"then {steady * 1e3:.3f} ms/step (host clock, blocked); "
        f"{clock.since(mark)}; fleet-mean response {ms[0]:.1f} -> "
        f"{ms[-1]:.1f} ms; device {device_bytes()}")
    del tab
    gc.collect()

    mark = clock.mark()
    k, r = agent(parity_cells, impl, seed + 1), agent(parity_cells, "ref",
                                                      seed + 1)
    k.run(parity_steps)
    r.run(parity_steps)
    qk, qr = np.asarray(k.q), np.asarray(r.q)
    dec_eq = np.array_equal(np.asarray(k.greedy_decisions()),
                            np.asarray(r.greedy_decisions()))
    cnt_eq = np.array_equal(np.asarray(k.counts), np.asarray(r.counts))
    diff = float(np.abs(qk - qr).max())
    log("tabular", f"parity {impl} vs ref, {parity_cells} cells x "
        f"{parity_steps} steps: decisions equal={dec_eq}, counts "
        f"equal={cnt_eq}, Q bit-equal={np.array_equal(qk, qr)}, "
        f"max |dQ|={diff:.3g} (bound atol=rtol={Q_ATOL}); "
        f"{clock.since(mark)}")
    check(dec_eq and cnt_eq, "tabular kernel decisions/counts differ "
          "from the reference")
    check(np.allclose(qk, qr, atol=Q_ATOL, rtol=Q_RTOL),
          f"tabular kernel Q-table off the reference by {diff}")


def dqn_config(cells: int):
    from repro.fleet import FleetConfig
    return FleetConfig(cells=cells, users=USERS, n_edges=max(cells // 64, 2),
                       assignment="skewed", skew=1.5,
                       capacity_tiers=(1.0, 2.0, 4.0), p_r2w=0.05,
                       p_w2r=0.15, arrival_rate=0.7)


def phase_dqn(clock, cells=16384, steps=300, impl="pallas", seed=0,
              hidden=None, replay_capacity=None):
    from repro.fleet import FleetDQN, FleetDQNConfig, SyntheticSource
    kw = {k: v for k, v in (("hidden", hidden),
                            ("replay_capacity", replay_capacity)) if v}
    cfg = FleetDQNConfig(accuracy_threshold=85.0, **kw)
    mark = clock.mark()
    dqn = FleetDQN(SyntheticSource(dqn_config(cells)), cfg=cfg, seed=seed,
                   impl=impl)
    _check_kernel_impl(dqn, impl, "dqn")
    want = {"head": impl, "combo_pick": "xla"}
    check(dqn.op_parts == want, f"dqn: head parts {dqn.op_parts}")
    if impl == "pallas":
        text = dqn._greedy.lower(dqn.params, dqn.counts,
                                 dqn.scen).as_text()
        check("tpu_custom_call" in text,
              "dqn: the fused head did not lower to a Mosaic kernel")
    log("dqn", f"impl={dqn._op_impl} parts={dqn.op_parts} cells={cells} "
        f"hidden={cfg.hidden} topk={cfg.topk} ({cfg.topk ** USERS} combos "
        f"per cell) QoS={cfg.accuracy_threshold}%")
    first, steady, ms = _timed_runs(dqn, steps)
    check(dqn.steps == steps and np.isfinite(ms).all(),
          "dqn: steps missing or non-finite responses")
    log("dqn", f"{dqn.steps} scan steps: first call {first:.2f} s, then "
        f"{steady * 1e3:.3f} ms/step (host clock, blocked); "
        f"{clock.since(mark)}; fleet-mean response {ms[0]:.1f} -> "
        f"{ms[-1]:.1f} ms; device {device_bytes()}")
    _check_dqn_head(clock, dqn, impl)
    return dqn


#: max |Q_kernel - Q_ref| over max |Q_ref| on the DQN head: the kernel's
#: and XLA's f32 matmuls accumulate in different orders (last bits)
DQN_RTOL = 1e-5


def _check_dqn_head(clock, dqn, impl: str) -> None:
    """The fused head against ``impl="ref"`` on the trained params and
    the fleet's current scenario, in two parts: the head values agree to
    ``DQN_RTOL``, and the ref's own pick (``lax.top_k`` + ``combo_pick``)
    on the kernel's head values gives the kernel's decisions exactly.
    Full-path decision equality is printed as well; where head values
    tie, last-bit differences may pick a different, equally valued
    action."""
    from repro.fleet import dynamics
    from repro.fleet.policy import fused_head_features
    from repro.kernels import ops, ref
    cfg, cells = dqn.cfg, dqn.scen.cells
    mark = clock.mark()
    feats = fused_head_features(dqn.counts, dqn.scen)
    acc = jnp.asarray(dynamics.accuracies(np.arange(10)), jnp.float32)
    kw = dict(threshold=float(cfg.accuracy_threshold), topk=cfg.topk)
    dec_k, q_k = ops.dqn_head(*feats, dqn.params, dqn.allowed, acc, **kw,
                              **dqn._op_kwargs)
    dec_r, q_r = ops.dqn_head(*feats, dqn.params, dqn.allowed, acc, **kw,
                              impl="ref")
    vals, idx = jax.lax.top_k(q_k, cfg.topk)
    dec_kr = ref.combo_pick(vals, idx, feats[1], ref.first_argmax_ref(q_k),
                            acc, threshold=kw["threshold"])
    q_k, q_r = np.asarray(q_k), np.asarray(q_r)
    scale = np.abs(q_r[q_r > -1e29]).max(initial=1.0)   # unmasked values
    rel = float(np.abs(q_k - q_r).max() / scale)
    same_pick = int((np.asarray(dec_kr) == np.asarray(dec_k)).all(-1).sum())
    full = int((np.asarray(dec_k) == np.asarray(dec_r)).all(-1).sum())
    log("dqn", f"head {impl} vs ref on the trained params: max |dQ| / "
        f"max |Q| = {rel:.3g} (bound {DQN_RTOL}); ref pick on the "
        f"kernel's values equals the kernel's in {same_pick}/{cells} "
        f"cells; full-path decisions equal in {full}/{cells} cells; "
        f"{clock.since(mark)}")
    check(rel <= DQN_RTOL, f"dqn: head values off the ref by {rel}")
    check(same_pick == cells, "dqn: the kernel's pick differs from the "
          "ref's on the same head values")


def phase_served(clock, dqn, cells=64, waves=3, max_batch=8, seed=0,
                 engines=None):
    from repro.fleet import FleetOrchestrator, SyntheticSource
    from repro.obs.timeline import exact_quantiles
    mark = clock.mark()
    if engines is None:
        from repro.configs import get_config
        from repro.launch.serve import build_engines
        engines = build_engines(get_config("edge-ladder"),
                                variants=("d0", "d4", "d7"))
    t0 = time.perf_counter()
    for vs in engines.values():        # every batch shape the bridge forms
        for eng in vs.values():
            for b in range(1, max_batch + 1):
                eng.generate(np.zeros((b, 32), np.int32), max_new_tokens=4)
    d0 = engines["S"]["d0"].model.cfg
    log("served", f"edge-ladder d_model={d0.d_model} layers={d0.n_layers} "
        f"vocab={d0.vocab_size}; engines "
        f"{sorted(f'{t}/{v}' for t, vs in engines.items() for v in vs)} "
        f"warmed at batch 1..{max_batch} in "
        f"{time.perf_counter() - t0:.2f} s; {clock.since(mark)}")
    source = SyntheticSource(dqn_config(cells))
    scen, state = source.reset(jax.random.PRNGKey(seed + 7))
    orch = FleetOrchestrator(dqn)
    e2e, pred, totals = [], [], dict(submitted=0, served=0, shed=0,
                                     errors=0, timeouts=0)
    mark, t0 = clock.mark(), time.perf_counter()
    for w in range(waves):
        res = orch.route(scen=scen, dispatch=engines, bridge=True,
                         batch_size=max_batch, max_new_tokens=4, seed=w)
        br = res.summary()["bridge"]
        for key, val in (("submitted", br["submitted"]),
                         ("served", br["served"]),
                         ("shed", br["shed"]["total"]),
                         ("errors", len(br["engine_errors"])),
                         ("timeouts", br["timeouts"])):
            totals[key] += val
        check(not br["engine_errors"],
              f"served: engine errors {br['engine_errors']}")
        e2e += [r.e2e_ms for r in res.served]
        pred += [r.predicted_ms for r in res.served]
        scen, state = source.step(jax.random.PRNGKey(seed + 100 + w), state)
    wall = time.perf_counter() - t0
    q = exact_quantiles(np.asarray(e2e))
    log("served", f"{waves} waves of {cells} held-out cells routed cold: "
        f"submitted={totals['submitted']} served={totals['served']} "
        f"shed={totals['shed']} engine_errors={totals['errors']} "
        f"timeouts={totals['timeouts']} in {wall:.2f} s; "
        f"{clock.since(mark)}")
    log("served", f"e2e P50 {q['p50']:.1f} ms, P99 {q['p99']:.1f} ms "
        f"measured (host clock) vs predicted mean {np.mean(pred):.1f} ms, "
        f"measured mean {np.mean(e2e):.1f} ms")
    check(totals["submitted"] > 0 and
          totals["served"] == totals["submitted"],
          f"served {totals['served']} of {totals['submitted']}")
    check(totals["shed"] == 0 and totals["errors"] == 0,
          f"sheds {totals['shed']}, engine errors {totals['errors']}")


def phase_sharded(clock, cells=65536, steps=100, n_devices=4, seed=0):
    from repro.fleet import (FleetConfig, FleetQLearning, SyntheticSource,
                             shard, topology)
    mesh = shard.fleet_mesh(n_devices)
    check(mesh.devices.size == n_devices,
          f"mesh has {mesh.devices.size} devices, wanted {n_devices}")
    cfg = FleetConfig(cells=cells, users=USERS, n_edges=cells // 16,
                      shard_local=True, n_shards=n_devices, p_r2w=0.05,
                      p_w2r=0.15)
    mark = clock.mark()
    meshed = FleetQLearning(SyntheticSource(cfg), seed=seed, mesh=mesh)
    single = FleetQLearning(SyntheticSource(cfg), seed=seed,
                            impl=meshed._op_impl)
    log("sharded", f"impl under the mesh resolves to {meshed._op_impl!r} "
        f"(update {meshed.update_path!r}); the one-device run uses "
        f"the same path; cells={cells} Q {meshed.q.shape} "
        f"{meshed.q.nbytes / 1e9:.2f} GB")
    for ag in (meshed, single):
        ag.run(steps)
    shards = meshed.q.addressable_shards
    check(meshed.q.sharding.spec[0] == shard.FLEET_AXIS
          and len({s.device for s in shards}) == n_devices
          and all(s.data.shape[0] == cells // n_devices for s in shards),
          f"Q-table not spread over {n_devices} devices: "
          f"{meshed.q.sharding}")
    q_eq = np.array_equal(np.asarray(meshed.q), np.asarray(single.q))
    c_eq = np.array_equal(np.asarray(meshed.counts),
                          np.asarray(single.counts))
    per_user = meshed.greedy_decisions()
    s = meshed.scen
    loc = shard.local_expected_response(per_user, s.end_b, s.edge_b, s.topo,
                                        mesh, active=s.active)
    glob = topology.topology_expected_response(
        per_user, s.end_b, s.edge_b, s.topo, active=s.active)
    agg_eq = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(loc, glob))
    log("sharded", f"{steps} steps on {n_devices} chips, Q shards "
        f"{[tuple(x.data.shape) for x in shards]}: Q equal to one device="
        f"{q_eq}, counts equal={c_eq}; shard-local contention equal to "
        f"the global segment-sum={agg_eq}; {clock.since(mark)}")
    check(q_eq and c_eq, "sharded training differs from one device")
    check(agg_eq, "shard.local_contention differs from the global path")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-fleet phase")
    args = ap.parse_args(argv)
    devs = require_tpu()
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    log("device", f"{len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}); jax {jax.__version__}; compile cache "
        f"{cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        check(len(devs) >= 4, f"--chips 4 needs 4 devices, found "
              f"{len(devs)}")
        phase_sharded(clock)
    else:
        phase_tabular(clock)
        dqn = phase_dqn(clock)
        phase_served(clock, dqn)
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"compile {clock.seconds:.2f} s in all, {clock.cache_hits} "
        f"persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
