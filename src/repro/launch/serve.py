"""Serving launcher: bring up the three-tier engine set for one arch's
variant ladder and run the RL-orchestrated decode loop on synthetic
request traffic (the paper's Fig. 4 runtime, reduced scale on CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch edge-ladder --requests 8
"""
from __future__ import annotations

import argparse
import zlib

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.core import (EXPERIMENTS, EndEdgeCloudEnv, QLearningAgent,
                        IntelligentOrchestrator, train_agent)
from repro.models import build_model
from repro.models.variants import build_ladder
from repro.serving import Request, RequestBatcher, ServingEngine


def build_engines(cfg, variants=("d0", "d4", "d7"), max_len=64, hop_ms=None):
    """One engine per (tier, variant); tiers emulated by compute_scale.

    ``hop_ms`` (e.g. ``{"E": 25.0, "C": 50.0}``) adds a real per-batch
    network-hop sleep per tier — tier SEPARATION emulation on a single
    host (see ``ServingEngine``); default: no hops (local tiers).
    Weights are random, seeded by the variant id, so every process
    builds the same engines."""
    ladder = build_ladder(cfg)
    engines = {"S": {}, "E": {}, "C": {}}
    scales = {"S": 1.0, "E": 2.0, "C": 4.0}
    hops = dict(hop_ms or {})
    for vid in variants:
        vcfg = ladder[vid].cfg
        model = build_model(vcfg)
        params = model.init(jax.random.PRNGKey(zlib.crc32(vid.encode())))
        for tier, sc in scales.items():
            if tier != "S" and vid != "d0":
                continue  # paper: edge/cloud always run d0
            engines[tier][vid] = ServingEngine(model, params, max_len=max_len,
                                               compute_scale=sc,
                                               hop_ms=hops.get(tier, 0.0))
    return engines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="edge-ladder")
    ap.add_argument("--users", type=int, default=3)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--threshold", type=float, default=85.0)
    ap.add_argument("--train-steps", type=int, default=6000)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced(get_config(args.arch)) if args.arch != "edge-ladder" \
        else get_config(args.arch)
    env = EndEdgeCloudEnv(args.users, EXPERIMENTS["EXP-A"],
                          accuracy_threshold=args.threshold, seed=0)
    agent = QLearningAgent(env.spec, seed=0)
    print("training orchestration agent...")
    res = train_agent(agent, env, args.train_steps)
    print(f"  converged_at={res.converged_at} greedy={res.greedy_ms:.1f}ms "
          f"(optimal {res.best_ms:.1f}ms)")

    engines = build_engines(cfg)
    orch = IntelligentOrchestrator(agent, env, engines)
    state = env.reset()
    rng = np.random.default_rng(0)
    for wave in range(args.requests):
        per_user = orch.decide(state)
        prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
                   for _ in range(args.users)]
        results = orch.dispatch(per_user, prompts)
        joint = env.spec.encode_action(per_user)
        state, _, info = env.step(joint)
        print(f"wave {wave}: decision={per_user} "
              f"env_avg={info['avg_response_ms']:.1f}ms "
              f"measured={[f'{r[2]:.0f}ms' for r in results]}")


if __name__ == "__main__":
    main()
