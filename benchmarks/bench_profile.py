"""Compiled-cost profile of the fleet hot paths (ISSUE-7 acceptance):
the RL loop's per-stage cost breakdown and the scaling-cliff diagnosis,
via ``repro.obs.prof``.

Measurements:

* ``profile_dqn_stage_*`` — each ``FleetDQN`` loop stage's compiled
  flops fraction and measured wall fraction (``obs.prof.stage_costs``:
  stages compiled separately, wall recorded through ``SpanRecorder``
  spans). The dominant stage is the fusion the ROADMAP's "Pallas-fused
  RL hot path" item should write. ``FleetQLearning``'s stages are the
  ``fleet.*`` device scopes of its scan, read from a profiler capture
  (docs/OBSERVABILITY.md), so it has no block here.
* ``profile_sweep_single`` / ``profile_sweep_sharded`` — the cells-grid
  scaling sweep (``obs.prof.scaling_sweep``): compiled flops/cell vs
  measured device-time/cell, single-device and on the forced
  multi-device ``('fleet',)`` mesh, naming the first fleet size whose
  device-time per cell-step leaves the flat regime and classifying the
  cliff as runtime overhead vs algorithmic growth — the diagnosis the
  ROADMAP's "Million-cell fleets" flatness item asks for.

Like ``bench_fleet_sharded``, it runs on the devices of its own process
and starts no child: the sharded sweep runs when the caller's
environment gives more than one device. ``--tiny`` is the CI smoke
mode.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from benchmarks.common import FAST, emit, save_json
from repro.fleet import FleetConfig, shard
from repro.fleet.api import SyntheticSource
from repro.fleet.policy import FleetDQN, FleetDQNConfig
from repro.obs import SpanRecorder
from repro.obs.prof import scaling_sweep, stage_costs

USERS = 3


def _emit_stages(tag: str, rep: dict) -> None:
    for name, st in rep["stages"].items():
        emit(f"profile_{tag}_stage_{name}", st["wall_ms"] * 1e3,
             f"flop_frac={rep['flop_fracs'][name]:.3f} "
             f"wall_frac={rep['wall_fracs'][name]:.3f} "
             f"intensity={st['arithmetic_intensity']:.2f} "
             f"dominant={st['dominant']}")
    emit(f"profile_{tag}_dominant", 0.0,
         f"flops={rep['dominant_stage_flops']} "
         f"wall={rep['dominant_stage_wall']} "
         f"(the fusion the Pallas item should write)")


def _run(tiny: bool) -> dict:
    ndev = jax.device_count()
    if tiny:
        cells, reps, base, steps, chunk = 32, 2, 16, 40, 10
    elif FAST:
        cells, reps, base, steps, chunk = 256, 5, 256, 400, 50
    else:
        cells, reps, base, steps, chunk = 1024, 9, 256, 2000, 50

    spans = SpanRecorder()
    dqn = FleetDQN(
        SyntheticSource(FleetConfig(cells=cells, users=USERS,
                                    arrival_rate=1.0)),
        cfg=FleetDQNConfig(replay_capacity=4096 if tiny else 65536))
    dqn_rep = stage_costs(dqn, reps=reps, spans=spans)
    _emit_stages("dqn", dqn_rep)

    # scaling sweeps: same grid shape as bench_fleet_sharded so the
    # cliff diagnosis localizes the same flatness number
    grid = [ndev * base, ndev * 4 * base, ndev * 16 * base]
    single = scaling_sweep(grid, users=USERS, mesh=None, steps=steps,
                           chunk=chunk)
    emit("profile_sweep_single", 0.0,
         f"cliff={single['cliff_cells']} class={single['classification']}")
    sharded = single
    if ndev > 1:
        sharded = scaling_sweep(grid, users=USERS,
                                mesh=shard.fleet_mesh(), steps=steps,
                                chunk=chunk)
        emit("profile_sweep_sharded", 0.0,
             f"cliff={sharded['cliff_cells']} "
             f"class={sharded['classification']}")
    print(f"# {sharded['summary']}", flush=True)

    metrics = {
        "cells": cells,
        "users": USERS,
        "devices": ndev,
        "rl_stage_fracs": dqn_rep["flop_fracs"],
        "rl_stage_wall_fracs": dqn_rep["wall_fracs"],
        "dominant_stage_flops": dqn_rep["dominant_stage_flops"],
        "dominant_stage_wall": dqn_rep["dominant_stage_wall"],
        "dqn_stages": dqn_rep,
        # per-cell compiled cost of one env step at the largest size
        "env_flops_per_cell": single["flops_per_cell"][str(grid[-1])],
        "sweep_single": single,
        "sweep_sharded": sharded,
        "cliff_cells": sharded["cliff_cells"],
        "cliff_classification": sharded["classification"],
        "cliff_summary": sharded["summary"],
    }
    save_json("bench_profile", metrics)
    return metrics


def main(tiny: bool = False) -> dict:
    return _run(tiny)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale budgets (CI smoke)")
    main(tiny=ap.parse_args().tiny)
