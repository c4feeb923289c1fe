"""Compiled-cost profiling: what a jitted fleet program *costs* before
it runs, and where the RL hot path and the scaling cliff actually are.

The repo's wall-clock benchmarks say how fast things ARE; this seam
says what they SHOULD cost. Everything here is built on the ahead-of-
time pipeline ``jax.jit(fn).lower(*args).compile()`` →
``cost_analysis()`` / ``memory_analysis()``, the same machinery
``repro.launch.dryrun`` uses for the model stack — generalized so any
fleet program gets the treatment:

* :class:`CostProfile` / :func:`profile_fn` — flops, bytes accessed,
  temp/arg/output bytes, arithmetic intensity, and the roofline terms
  (``compute_s`` / ``memory_s`` / ``dominant``) against per-device-kind
  peak constants. No execution happens: the numbers come out of the
  compiled executable, so they are deterministic across runs and
  machines with the same compiler.
* :func:`stage_costs` — compile the fleet RL loop's stages SEPARATELY
  (encode/act, env step, replay push+sample, TD/DQN update) and report
  each stage's fraction of the loop's compiled cost next to measured
  wall time (recorded through ``obs.spans.SpanRecorder``). This is the
  map the ROADMAP's "Pallas-fused RL hot path" item needs: the stage
  with the dominant flop/wall fraction is the fusion to write.
* :func:`scaling_sweep` — compiled flops/device vs measured wall time
  across a cells grid (single-device or on a fleet mesh), classifying
  a per-device flatness cliff as *runtime* overhead (flops/cell flat,
  device-time/cell grows — dispatch/partitioning, fix the harness) vs
  *algorithmic* growth (flops/cell grows — superlinear work, fix the
  program), and naming the first offending fleet size.

Caveat inherited from ``launch.dryrun``: XLA counts a ``lax.scan``
body ONCE, not times the trip count — so cost profiles here are taken
on single-step programs and wall time on the scanned program, never
the other way around.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import SpanRecorder, span


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Peak rates of one device, which the roofline terms are computed
    against, and where they come from."""
    flops_per_s: float
    bytes_per_s: float
    source: str
    #: chip-to-chip interconnect, all links together (0: none)
    ici_bytes_per_s: float = 0.0


#: Peaks per ``jax.Device.device_kind``: the one table of the repo.
#: ``"cpu"`` is an order-of-magnitude reference for comparing programs
#: and stages on a CI host, not a device measurement.
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        197e12, 819e9, ici_bytes_per_s=200e9,
        source='Google Cloud documentation, "TPU v5e": per chip 197 '
               "TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI"),
    "cpu": DevicePeaks(1e11, 5e10,
                       source="rough reference for a CI-class host"),
}


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of ``kind`` (default: the first device's ``device_kind``).
    A kind that is not in ``PEAKS`` is an error, not a default."""
    kind = kind or jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {kind!r}; add it to "
                       f"repro.obs.prof.PEAKS with its source "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]


@dataclasses.dataclass
class CostProfile:
    """Compiled-cost profile of one jitted program.

    ``flops`` / ``bytes_accessed`` come from the compiler's
    ``cost_analysis`` of the optimized (post-SPMD) module — under a
    mesh they are PER-DEVICE numbers. ``temp/arg/out_bytes`` come from
    ``memory_analysis`` (per-device buffer sizes of the executable).
    """
    name: str
    flops: float
    bytes_accessed: float
    arg_bytes: int
    out_bytes: int
    temp_bytes: int
    backend: str
    peak_flops_per_s: float
    peak_bytes_per_s: float

    @property
    def arithmetic_intensity(self) -> float:
        """flops per byte accessed (0 when the compiler reports no
        traffic — e.g. a constant-folded program)."""
        return self.flops / self.bytes_accessed if self.bytes_accessed \
            else 0.0

    @property
    def ridge_intensity(self) -> float:
        """The roofline ridge point of this backend (flops/byte above
        which a program is compute-bound at peak)."""
        return self.peak_flops_per_s / self.peak_bytes_per_s

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops_per_s

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / self.peak_bytes_per_s

    @property
    def dominant(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"

    def as_dict(self) -> dict:
        """JSON-ready dict (fields + the derived roofline terms)."""
        d = dataclasses.asdict(self)
        d.update(arithmetic_intensity=self.arithmetic_intensity,
                 ridge_intensity=self.ridge_intensity,
                 compute_s=self.compute_s, memory_s=self.memory_s,
                 dominant=self.dominant)
        return d

    @classmethod
    def from_compiled(cls, compiled, name: str,
                      peaks: Optional[DevicePeaks] = None) -> "CostProfile":
        """Build from an already-compiled ``jax.stages.Compiled``."""
        peaks = peaks or device_peaks()
        ca = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        return cls(
            name=name,
            flops=float(ca.get("flops", 0.0)),
            bytes_accessed=float(ca.get("bytes accessed", 0.0)),
            arg_bytes=int(getattr(ma, "argument_size_in_bytes", 0) or 0),
            out_bytes=int(getattr(ma, "output_size_in_bytes", 0) or 0),
            temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0) or 0),
            backend=jax.default_backend(),
            peak_flops_per_s=peaks.flops_per_s,
            peak_bytes_per_s=peaks.bytes_per_s)


def profile_fn(fn: Callable, *args, name: Optional[str] = None,
               peaks: Optional[DevicePeaks] = None,
               static_argnums=(), **jit_kwargs) -> CostProfile:
    """Lower + compile ``fn(*args)`` and wrap its compiled cost and
    memory analyses into a :class:`CostProfile`. Nothing executes —
    donated buffers (``donate_argnums``) stay valid."""
    jfn = jax.jit(fn, static_argnums=static_argnums, **jit_kwargs)
    compiled = jfn.lower(*args).compile()
    return CostProfile.from_compiled(
        compiled, name or getattr(fn, "__name__", "fn"), peaks)


# ---------------------------------------------------------------------------
# Stage breakdown of the fleet RL loops
# ---------------------------------------------------------------------------


def _median_wall_ms(jfn, args, name: str, reps: int,
                    spans: Optional[SpanRecorder]) -> float:
    """Median host wall of ``reps`` blocked executions, recorded as
    ``prof.stage.{name}`` spans on ``spans`` (one per rep)."""
    rec = spans if spans is not None else SpanRecorder()
    tag = f"prof.stage.{name}"
    jax.block_until_ready(jfn(*args))                        # compile/warm
    for _ in range(reps):
        with span(rec, tag):
            jax.block_until_ready(jfn(*args))
    return float(np.median(rec.durations_ms(tag)[-reps:]))


def _dqn_stage_fns(agent):
    """(name -> (fn, args)) decomposition of ``FleetDQN._make_step``:
    the same closures the fused scan body is built from, compiled one
    stage at a time. Args are the agent's live carries, so shapes and
    shardings match the real loop."""
    from repro.fleet.api import make_env_step
    from repro.fleet.policy import encode_fleet_state
    from repro.fleet.replay import replay_push, replay_sample

    cfg = agent.cfg
    act = agent._make_act(agent._make_greedy())
    env_step = make_env_step(agent.source,
                             threshold=cfg.accuracy_threshold,
                             noise=cfg.noise)
    train_step = agent._make_train_step()
    key = jax.random.PRNGKey(0)
    scen, counts, buf = agent.scen, agent.counts, agent.buffer
    s = jax.block_until_ready(encode_fleet_state(counts, scen))
    a = jnp.zeros((scen.cells, scen.users), jnp.int32)
    r = jnp.zeros((scen.cells,), jnp.float32)
    bs = jnp.zeros((cfg.batch_size, agent.state_dim), jnp.float32)
    ba = jnp.zeros((cfg.batch_size, scen.users), jnp.int32)
    br = jnp.zeros((cfg.batch_size,), jnp.float32)

    def encode_act(params, counts, scen, eps, key):
        return act(params, counts, scen, eps, key)

    def replay(key, buf, s, a, r, s2):
        buf = replay_push(buf, s, a, r, s2)
        return buf, replay_sample(key, buf, cfg.batch_size)

    def update(params, opt, s, a, r, s2):
        return train_step(params, opt, s, a, r, s2)

    # the act closure above already routes through the agent's fused
    # head when one is active — only the reported stage name changes
    act_name = ("encode_act" if getattr(agent, "_op_impl", "xla") == "xla"
                else "fused_encode_act")
    return {
        act_name: (encode_act,
                   (agent.params, counts, scen, agent.eps, key)),
        "env_step": (lambda key, scen, a: env_step(key, scen, a),
                     (key, scen, a)),
        "replay": (replay, (key, buf, s, a, r, s)),
        "update": (update, (agent.params, agent.opt, bs, ba, br, bs)),
    }


def _tabular_stage_fns(agent):
    """(name -> (fn, args)) decomposition of ``FleetQLearning``'s step.

    Legacy (``impl='xla'``) stages: eps-greedy act (state index +
    gather + argmax), env step, TD scatter-update. Fused agents
    replace the last with ``fused_update_act`` — the single
    ``kernels.ops.fused_tabular_update`` call that covers the TD
    update AND the next step's act-side gather/argmax (the scan
    carries its ``greedy2``), so ``encode_act`` shrinks to the state
    index + exploration draw."""
    from repro.fleet.api import make_env_step

    cfg = agent.cfg
    env_step = make_env_step(agent.source,
                             threshold=cfg.accuracy_threshold,
                             noise=cfg.noise)
    pu, n_actions = agent.pu_table, agent.n_actions
    key = jax.random.PRNGKey(0)
    scen, counts = agent.scen, agent.counts
    a0 = jnp.zeros((scen.cells,), jnp.int32)
    r = jnp.zeros((scen.cells,), jnp.float32)

    if getattr(agent, "_op_impl", "xla") != "xla":
        from repro.kernels import ops
        s0 = jnp.zeros((scen.cells,), jnp.int32)
        g0 = jnp.zeros((scen.cells,), jnp.int32)

        def encode_act(counts, scen, greedy, eps, key):
            s = agent._state_index(counts, scen)
            a = agent._explore(greedy, eps, key)
            return s, a, pu[a]

        def fused_update_act(q, s, a, r, s2):
            return ops.fused_tabular_update(
                q, s, a, r, s2, alpha=cfg.alpha, gamma=cfg.gamma,
                **agent._op_kwargs)

        return {
            "encode_act": (encode_act,
                           (counts, scen, g0, agent.eps, key)),
            "env_step": (lambda key, scen, a: env_step(key, scen, a),
                         (key, scen, jnp.zeros((scen.cells, scen.users),
                                               jnp.int32))),
            "fused_update_act": (fused_update_act,
                                 (agent.q, s0, a0, r, s0)),
        }

    def encode_act(q, counts, scen, eps, key):
        cells = jnp.arange(q.shape[0])
        s = agent._state_index(counts, scen)
        u = jax.random.uniform(key, (q.shape[0],))
        rand = jnp.minimum((u / jnp.maximum(eps, 1e-9)
                            * n_actions).astype(jnp.int32), n_actions - 1)
        a = jnp.where(u < eps, rand, q[cells, s].argmax(-1))
        return a, pu[a]

    def td_update(q, counts, scen, a, r, counts2, scen2):
        cells = jnp.arange(q.shape[0])
        s = agent._state_index(counts, scen)
        s2 = agent._state_index(counts2, scen2)
        td = r + cfg.gamma * q[cells, s2].max(-1) - q[cells, s, a]
        return q.at[cells, s, a].add(cfg.alpha * td)

    return {
        "encode_act": (encode_act,
                       (agent.q, counts, scen, agent.eps, key)),
        "env_step": (lambda key, scen, a: env_step(key, scen, a),
                     (key, scen, jnp.zeros((scen.cells, scen.users),
                                           jnp.int32))),
        "update": (td_update, (agent.q, counts, scen, a0, r, counts,
                               scen)),
    }


def stage_costs(agent, reps: int = 5,
                spans: Optional[SpanRecorder] = None,
                peaks: Optional[DevicePeaks] = None) -> dict:
    """Fractional compiled-cost breakdown of a fleet agent's RL loop.

    Compiles each stage of the agent's per-step program separately
    (``FleetDQN``: encode/act, env step, replay push+sample, DQN
    update; ``FleetQLearning``: encode/act, env step, TD update),
    profiles the compiled cost of each, and measures ``reps`` blocked
    executions per stage through ``SpanRecorder`` spans
    (``prof.stage.{name}`` on ``spans`` when given).

    Returns ``{"kind", "cells", "users", "backend", "stages": {name:
    profile-dict + wall_ms}, "flop_fracs", "byte_fracs", "wall_fracs",
    "dominant_stage_flops", "dominant_stage_wall"}`` — the flop/wall
    fractions are the map of which fusion the Pallas item should write.

    Note the stages are compiled as standalone programs: their summed
    cost is an upper bound on the fused scan body (XLA fuses across
    stage boundaries), but the *fractions* are what localize the hot
    stage, and they are deterministic across recompiles.
    """
    stage_fns = (_dqn_stage_fns(agent) if hasattr(agent, "buffer")
                 else _tabular_stage_fns(agent))
    kind = "dqn" if hasattr(agent, "buffer") else "tabular"
    stages = {}
    for name, (fn, args) in stage_fns.items():
        jfn = jax.jit(fn)
        prof = CostProfile.from_compiled(jfn.lower(*args).compile(),
                                         name, peaks)
        wall = _median_wall_ms(jfn, args, name, reps, spans)
        stages[name] = {**prof.as_dict(), "wall_ms": wall}

    def fracs(key):
        tot = sum(s[key] for s in stages.values())
        return {n: s[key] / tot if tot else 0.0
                for n, s in stages.items()}

    flop_fracs = fracs("flops")
    wall_fracs = fracs("wall_ms")
    return {
        "kind": kind,
        "cells": int(agent.scen.cells),
        "users": int(agent.scen.users),
        "backend": jax.default_backend(),
        "stages": stages,
        "flop_fracs": flop_fracs,
        "byte_fracs": fracs("bytes_accessed"),
        "wall_fracs": wall_fracs,
        "dominant_stage_flops": max(flop_fracs, key=flop_fracs.get),
        "dominant_stage_wall": max(wall_fracs, key=wall_fracs.get),
    }


# ---------------------------------------------------------------------------
# Scaling sweep: localize and classify the per-device flatness cliff
# ---------------------------------------------------------------------------


def _make_run_chunk(env_step):
    def run_chunk(key, scen, actions):
        def body(carry, a):
            key, scen = carry
            key, k = jax.random.split(key)
            scen2, _, ms, _, _ = env_step(k, scen, a)
            return (key, scen2), ms.mean()
        (key, scen), ms = jax.lax.scan(body, (key, scen), actions)
        return key, scen, ms
    return run_chunk


def scaling_sweep(cells_grid: Sequence[int], users: int = 3, mesh=None,
                  steps: int = 200, chunk: int = 20,
                  cliff_tol: float = 0.5, flop_tol: float = 0.15,
                  config_kwargs: Optional[Dict[str, Any]] = None) -> dict:
    """Sweep the fleet env step over ``cells_grid`` and classify the
    per-device scaling cliff.

    For each fleet size the SINGLE-STEP env program is lowered and
    compiled for its per-device flops (scan bodies are counted once by
    ``cost_analysis``, so cost comes from the unscanned program), and
    the SCANNED program (``chunk`` steps per host call) is timed for
    measured wall — the cross-reference that separates the two cliff
    kinds:

    * ``flops/cell`` flat but device-time/cell grows by more than
      ``cliff_tol`` over the grid's best → **runtime** overhead
      (dispatch, partitioning, collective latency — the program's work
      is linear; fix the harness);
    * ``flops/cell`` grows by more than ``flop_tol`` → **algorithmic**
      growth (the compiled program itself does superlinear per-cell
      work; fix the program).

    ``cliff_cells`` names the first grid size whose device-time per
    cell-step exceeds ``(1 + cliff_tol) x`` the grid minimum (None when
    the sweep is flat). With ``mesh`` the scenario and action stream
    shard along the fleet axis and all numbers are per-device.
    """
    from repro.fleet import shard
    from repro.fleet.api import SyntheticSource, make_env_step
    from repro.fleet.scenarios import FleetConfig

    ndev = int(np.prod(list(mesh.shape.values()))) if mesh is not None \
        else 1
    cfg_kw = dict(arrival_rate=1.0, p_r2w=0.05, p_w2r=0.1)
    cfg_kw.update(config_kwargs or {})
    flops_per_cell: Dict[int, float] = {}
    us_dev_per_cell: Dict[int, float] = {}
    per_device_sps: Dict[int, float] = {}
    for cells in cells_grid:
        cfg = FleetConfig(cells=cells, users=users, **cfg_kw)
        source = SyntheticSource(cfg, mesh=mesh)
        env_step = make_env_step(source)
        scen, _ = source.reset(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        a1 = jnp.zeros((cells, users), jnp.int32)
        actions = jnp.zeros((chunk, cells, users), jnp.int32)
        if mesh is not None:
            a1 = shard.shard_array(a1, mesh)
            actions = shard.shard_array(actions, mesh, axis=1)
        # compiled cost of ONE step (per-device under a mesh)
        prof = profile_fn(lambda k, s, a: env_step(k, s, a), key, scen, a1,
                          name=f"env_step_{cells}")
        flops_per_cell[cells] = prof.flops / (cells / ndev)
        # measured wall of the scanned program
        run_chunk = jax.jit(_make_run_chunk(env_step))
        key, scen, ms = run_chunk(key, scen, actions)        # compile
        jax.block_until_ready(ms)
        n_chunks = max(1, steps // chunk)
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            key, scen, ms = run_chunk(key, scen, actions)
            jax.block_until_ready(ms)
        dt = time.perf_counter() - t0
        total = n_chunks * chunk * cells
        per_device_sps[cells] = total / dt / ndev
        us_dev_per_cell[cells] = dt * ndev / total * 1e6

    grid = list(cells_grid)
    best = min(us_dev_per_cell.values())
    best_cells = min(us_dev_per_cell, key=us_dev_per_cell.get)
    flop_floor = min(flops_per_cell.values())
    offending = [c for c in grid
                 if us_dev_per_cell[c] > (1.0 + cliff_tol) * best]
    cliff = offending[0] if offending else None
    if cliff is None:
        classification = "flat"
        summary = (f"flat: device-time per cell-step within "
                   f"{cliff_tol:.0%} of the best ({best:.2f}us at "
                   f"{best_cells} cells) across the grid")
    else:
        algorithmic = (flops_per_cell[cliff]
                       > (1.0 + flop_tol) * flop_floor)
        classification = "algorithmic" if algorithmic else "runtime"
        ratio = us_dev_per_cell[cliff] / best
        summary = (
            f"cliff at {cliff} cells: device-time per cell-step "
            f"{us_dev_per_cell[cliff]:.2f}us is {ratio:.1f}x the best "
            f"({best:.2f}us at {best_cells} cells) while compiled "
            f"flops/cell "
            + (f"grows {flops_per_cell[cliff] / flop_floor:.2f}x — "
               f"algorithmic growth (the program does superlinear "
               f"per-cell work)" if algorithmic else
               f"stays flat ({flops_per_cell[cliff]:.0f} vs "
               f"{flop_floor:.0f}) — runtime overhead (dispatch/"
               f"partitioning, not the program)"))
    top2 = [per_device_sps[c] for c in grid[-2:]]
    return {
        "grid": grid,
        "users": users,
        "devices": ndev,
        "sharded": mesh is not None,
        "backend": jax.default_backend(),
        "flops_per_cell": {str(c): flops_per_cell[c] for c in grid},
        "us_device_per_cell_step": {str(c): us_dev_per_cell[c]
                                    for c in grid},
        "per_device_cell_steps_per_s": {str(c): per_device_sps[c]
                                        for c in grid},
        "flatness": min(top2) / max(top2),
        "cliff_cells": cliff,
        "classification": classification,
        "summary": summary,
    }
