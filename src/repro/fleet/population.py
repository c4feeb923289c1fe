"""Population-scale RL training: thousands of independent cells per step.

The paper trains one tabular agent against one cell (≤5 users) with a
Python-loop environment. This module scales that to fleets: a dense
per-cell Q-table of shape ``(cells, states, actions)`` updated for every
cell in a single ``jax.jit`` call per host step, over the shared
``fleet.dynamics`` kernel and a ``fleet.scenarios.FleetScenario``.

State space. The scalar env's observation is fully determined by the
previous step's (edge jobs, cloud jobs) counts plus the link states, so
the fleet agent indexes its Q-table by
``(n_edge, n_cloud[, packed link bits])`` — ``(N+1)^2`` states for
static-link fleets (the paper's setting), times ``2^(N+1)`` when
``track_links`` is on for Markov-modulated fleets. This is exactly the
set of states the scalar agent's lazy dict ever materializes.

Action space. A candidate set of joint actions (default: the full
``10^N`` space for ``N <= 3``, the SOTA-restricted ``3^N`` offloading
set above) shared by all cells; its decoded ``(K, N)`` table lives on
device so greedy routing for the whole fleet is one argmax + one gather.

``fleet_bruteforce`` evaluates every candidate action for every cell in
chunks (the vectorized analogue of ``core.bruteforce``), and
``FleetQLearning.train`` reports per-cell convergence against it, the
fleet analogue of ``core.orchestrator.train_agent``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spaces import SpaceSpec, restricted_actions
from repro.fleet import dynamics, topology
from repro.fleet.scenarios import FleetConfig, FleetScenario
from repro.kernels import ops
from repro.kernels.ref import first_argmax_ref
from repro.obs.metrics import MetricDef, MetricsAccumulator
from repro.obs.spans import span


def fleet_metrics(cells: int, kind: str = "tabular", n_windows: int = 0,
                  window_len: int = 1) -> MetricsAccumulator:
    """The standard in-scan telemetry pack of the fleet agents.

    Per-cell signals use ``lanes=cells`` so every accumulator update is
    elementwise along the fleet axis — the mechanism that keeps sharded
    training bit-identical to single-device (see ``repro.obs.metrics``).
    Histogram ranges come from the dynamics invariants: rewards live in
    ``[-MAX_RESPONSE_MS/1000, 0]`` and response times in
    ``[0, MAX_RESPONSE_MS]``; out-of-range values clip into edge bins
    without corrupting the exact moments (and bump the explicit
    underflow/overflow counters).

    ``n_windows > 0`` gives every stream a ``(n_windows, lanes)``
    per-window ring (``window_len`` steps per slot), so ``summary()``
    reports the learning curve — reward/td_abs/loss per window — not
    just whole-run aggregates. The ring update is the same elementwise
    op class, so the sharding bit-identity is unchanged.
    """
    r_floor = -dynamics.MAX_RESPONSE_MS / 1000.0
    w = dict(n_windows=n_windows, window_len=window_len)
    defs = {
        "reward": MetricDef(lo=r_floor, hi=0.0, lanes=cells, **w),
        "mean_ms": MetricDef(lo=0.0, hi=dynamics.MAX_RESPONSE_MS,
                             lanes=cells, **w),
        "epsilon": MetricDef(lo=0.0, hi=1.0, **w),
    }
    if kind == "tabular":
        defs["td_abs"] = MetricDef(lo=0.0, hi=-r_floor, lanes=cells, **w)
    elif kind == "dqn":
        defs["loss"] = MetricDef(lo=0.0, hi=25.0, **w)
        defs["replay_fill"] = MetricDef(lo=0.0, hi=1.0, **w)
    else:
        raise ValueError(f"unknown metrics kind {kind!r}")
    return MetricsAccumulator.create(defs)


def place_metrics(mets, mesh):
    """Shard an agent's accumulator like its other carries: per-cell
    lanes along the fleet axis (axis 1 of the windowed rings),
    histograms/counters/scalars replicated."""
    if mets is None or mesh is None:
        return mets
    from repro.fleet import shard
    return mets.place(lambda x, axis=0: shard.shard_array(x, mesh,
                                                          axis=axis),
                      lambda x: shard.replicate(x, mesh))


def check_pad_width(n_users: int, scen: FleetScenario, who: str) -> None:
    """THE pad-width guard of the FleetPolicy protocol, shared by every
    policy (both agents, the oracle, the static baselines): a scenario
    padded to a different user width than the policy was built for —
    e.g. one produced by a ``TraceSource`` recorded at another width —
    must raise the same clear error everywhere instead of silently
    misreading feature blocks or state indices."""
    if scen.users != n_users:
        raise ValueError(
            f"{who} routes fleets padded to {n_users} users; got a "
            f"{scen.users}-wide scenario — regenerate it with "
            f"users={n_users} (smaller cells are expressed via the "
            "membership mask, not a narrower pad)")


def resolve_source(scen, fleet_cfg, seed: int, reset_key=None):
    """Normalize an agent's scenario arguments onto the ScenarioSource
    seam: a source resets into its initial scenario; the legacy
    ``(FleetScenario, FleetConfig)`` pair wraps bit-exactly into a
    ``SyntheticSource`` pinned to that scenario. Returns
    ``(scen0, source)``."""
    from repro.fleet.api import SyntheticSource, is_source, \
        require_scenario_state
    if is_source(scen):
        source = scen
        require_scenario_state(source)
        key = reset_key if reset_key is not None else \
            jax.random.PRNGKey(seed)
        scen0, _ = source.reset(key)
        return scen0, source
    if fleet_cfg is None:
        raise TypeError(
            "pass a ScenarioSource (repro.fleet.api), or a FleetScenario "
            "together with its FleetConfig")
    return scen, SyntheticSource(fleet_cfg, scen=scen)


def adopt_mesh(mesh, source, scen):
    """THE mesh-adoption step of both agent constructors: resolve the
    fleet mesh (an explicit argument wins, else the source's own),
    attach it to the source so the jitted scenario stream keeps the
    layout, and place the initial scenario. Returns ``(mesh, scen)``
    (``(None, scen)`` when no mesh is in play)."""
    mesh = mesh if mesh is not None else getattr(source, "mesh", None)
    if mesh is None:
        return None, scen
    from repro.fleet import shard
    attach = getattr(source, "attach_mesh", None)
    if attach is not None:
        attach(mesh)
    return mesh, shard.shard_scenario(scen, mesh)


def simulate_responses(key, scen: FleetScenario, per_user, noise: float):
    """Noisy fleet-wide response simulation: (cells,) mean ms and mean
    accuracy over each cell's active users, plus next-step job counts.
    The jittable analogue of ``EndEdgeCloudEnv.response_times`` +
    ``accuracies`` for every cell at once.

    With an attached ``scen.topo`` the responses couple across cells
    (shared edges, cloud queueing) via ``topology_expected_response``;
    the returned ``counts`` stay per-cell own-job counts either way (the
    observation both agents index/encode — aggregation over the
    assignment happens inside the dynamics each step)."""
    if scen.topo is None:
        mean_ms, acc = dynamics.expected_response(
            per_user, scen.end_b, scen.edge_b, active=scen.active,
            calib=scen.calib, xp=jnp)
    else:
        mean_ms, acc = topology.topology_expected_response(
            per_user, scen.end_b, scen.edge_b, scen.topo,
            active=scen.active, calib=scen.calib, xp=jnp)
    n_act = jnp.maximum(scen.active.sum(-1), 1)
    if noise:
        # one per-cell draw on the mean instead of the scalar env's N
        # per-user draws (~5x less RNG); the 1/sqrt(n) scaling matches the
        # variance of averaging n independent multipliers when per-user
        # times are equal, and approximates it otherwise
        mult = jnp.clip(1.0 + (noise / jnp.sqrt(n_act))
                        * jax.random.normal(key, mean_ms.shape), 0.8, 1.2)
        mean_ms = mean_ms * mult
    counts = jnp.stack(
        [((per_user == dynamics.A_EDGE) & scen.active).sum(-1),
         ((per_user == dynamics.A_CLOUD) & scen.active).sum(-1)],
        axis=-1).astype(jnp.int32)
    return mean_ms, acc, counts


def nominal_expected_response(scen: FleetScenario, per_user):
    """Noise-free (cells,) mean ms / mean accuracy of ``per_user`` under
    nominal load (all member users requesting), shared- or
    isolated-contention depending on ``scen.topo`` — the ONE evaluation
    behind both agents' ``greedy_expected``, the oracles, and the
    benchmarks, so the two contention regimes can't drift apart."""
    if scen.topo is None:
        return dynamics.fleet_expected_response(
            per_user, scen.end_b, scen.edge_b, scen.member,
            calib=scen.calib)
    return topology.fleet_topology_expected_response(
        per_user, scen.end_b, scen.edge_b, scen.topo, scen.member,
        calib=scen.calib)


def make_fleet_env_step(source, threshold: float = 0.0,
                        noise: float = 0.02):
    """Pure per-step fleet environment transition — the fleet analogue of
    ``EndEdgeCloudEnv.step`` with the decision supplied externally.

    Takes any ``repro.fleet.api.ScenarioSource`` (``SyntheticSource``,
    ``TraceSource``, ...). Returns ``env_step(key, scen, per_user) ->
    (scen2, counts2, mean_ms, mean_acc, reward)``; wrap in ``jax.jit`` /
    ``lax.scan`` to step every cell of the fleet per call.

    The PR-4 ``make_fleet_env_step(FleetConfig)`` deprecation shim has
    been removed — wrap the config in a ``SyntheticSource`` (results
    are bit-identical; same generators, same key usage).
    """
    from repro.fleet.api import make_env_step
    if isinstance(source, FleetConfig):
        raise TypeError(
            "make_fleet_env_step(FleetConfig) was removed; wrap the "
            "config: make_fleet_env_step(repro.fleet.api."
            "SyntheticSource(cfg)) — bit-identical results")
    return make_env_step(source, threshold=threshold, noise=noise)


def default_actions(spec: SpaceSpec) -> np.ndarray:
    """Full joint space for small N, SOTA-restricted offloading set above
    (keeps the dense per-cell table ~tens of MB at N=5)."""
    if spec.n_users <= 3:
        return spec.all_actions()
    return restricted_actions(spec)


@dataclasses.dataclass
class FleetQConfig:
    alpha: float = 0.9               # paper Table 7
    gamma: float = 0.1
    eps_start: float = 1.0
    eps_decay: float = 1e-3          # multiplicative, per fleet step
    eps_min: float = 0.01
    noise: float = 0.02
    accuracy_threshold: float = 0.0
    track_links: bool = False        # index Q by link bits (Markov fleets)


class FleetQLearning:
    """Batched epsilon-greedy tabular Q-learning over a fleet of cells.

    One ``step()`` = one environment step for EVERY cell: eps-greedy
    action selection, noisy response simulation, exogenous scenario
    transition, and TD update, all inside a single jitted call.
    """

    def __init__(self, scen, fleet_cfg: Optional[FleetConfig] = None,
                 cfg: Optional[FleetQConfig] = None,
                 actions: Optional[np.ndarray] = None, seed: int = 0,
                 reset_key=None, mesh=None, metrics: bool = True,
                 n_windows: int = 0, window_len: int = 1,
                 impl: str = "pallas", spans=None):
        """``scen`` is a ``repro.fleet.api.ScenarioSource`` (reset with
        ``reset_key``, default ``PRNGKey(seed)``) — or, equivalently, a
        ``FleetScenario`` plus its ``FleetConfig`` (wrapped into a
        ``SyntheticSource`` pinned to that scenario).

        ``mesh`` (``repro.fleet.shard.fleet_mesh``; default: the
        source's own mesh, if any) shards the per-cell Q-table, job
        counts, and scenario along the fleet axis — the TD update is
        per-cell, so training never leaves the shard, bit-identical to
        the single-device path.

        ``metrics`` (default on) rides a ``repro.obs`` accumulator in
        the scan carry — per-step reward / response time / |TD| /
        epsilon with zero host syncs; read it via ``metrics_summary``.
        Recording consumes no RNG and never feeds back into training,
        so trajectories are bit-identical with it on or off —
        including with ``n_windows > 0``, which adds a per-window ring
        (``window_len`` steps per slot) to every stream so
        ``metrics_summary()`` carries the learning curve.

        ``impl`` selects where the fused act+update runs (one Q-row
        gather shared by the TD max and the next step's greedy):
        ``"pallas"`` (default) is the compiled kernel on TPU, under a
        mesh once per device on its block of cells, and ``"ref"`` (the
        fused-jnp formulation) elsewhere; ``"pallas_interpret"`` forces
        the kernel in interpret mode (parity tests). ``self.path``
        (``kernels.ops.TabularPath``) holds the choice.

        ``spans`` (a ``repro.obs.spans.SpanRecorder``, default none)
        records each ``run`` call as a ``fleet.run`` span (args
        ``steps``, ``cells`` and ``update``, the update path that runs:
        ``update_path``) with a ``fleet.run.fetch`` child around the
        blocking reads. Both are profiler annotations with or without a
        recorder, and the fused scan's ops sit under the device scopes
        ``fleet.prologue`` / ``act`` / ``respond`` / ``scenario`` /
        ``update`` / ``telemetry`` / ``epilogue``
        (docs/OBSERVABILITY.md)."""
        self.cfg = cfg or FleetQConfig()
        self.spans = spans
        scen, self.source = resolve_source(scen, fleet_cfg, seed, reset_key)
        self.fleet_cfg = getattr(self.source, "cfg", None)
        self.mesh, scen = adopt_mesh(mesh, self.source, scen)
        from repro.fleet import shard
        self.spec = SpaceSpec(scen.users)
        self.actions = np.asarray(actions if actions is not None
                                  else default_actions(self.spec))
        self.pu_table = jnp.asarray(
            self.spec.decode_actions_batch(self.actions))      # (K, N)
        self.n_actions = len(self.actions)
        users = scen.users
        self._count_states = (users + 1) ** 2
        self._link_states = 2 ** (users + 1) if self.cfg.track_links else 1
        self.n_states = self._count_states * self._link_states
        # the kernel runs under a mesh once per device, on its block of
        # cells: only a table that the mesh splits along its cells has one
        per_shard = (self.mesh is not None and
                     shard.fleet_spec(self.mesh, (scen.cells,))[0]
                     is not None)
        #: where the TD update runs and the table's layout around it
        self.path = ops.tabular_path(impl, self.n_states, self.n_actions,
                                     self.mesh, per_shard=per_shard)
        # under a mesh each device makes its own block: whole, the table
        # of a fleet sized for the mesh does not fit one device
        self.q = shard.zeros((scen.cells, self.n_states, self.n_actions),
                             jnp.float32, self.mesh)
        self.scen = scen
        self.counts = jnp.zeros((scen.cells, 2), jnp.int32)
        self.metrics = fleet_metrics(scen.cells, "tabular",
                                     n_windows=n_windows,
                                     window_len=window_len) if metrics \
            else None
        if self.mesh is not None:
            self.counts = shard.shard_array(self.counts, self.mesh)
            self.metrics = place_metrics(self.metrics, self.mesh)
        self.eps = self.cfg.eps_start
        self.key = jax.random.PRNGKey(seed)
        self.steps = 0
        # donate the Q-table (and the metrics accumulator riding with it):
        # the scatter-add then runs in place instead of copying the whole
        # (cells, S, K) buffer every step (~30 ms at 36 MB)
        don = (0,) if self.metrics is None else (0, 1)
        self._step = jax.jit(self._make_step(), donate_argnums=don)
        self._run = jax.jit(self._make_run(), static_argnums=(6,),
                            donate_argnums=don)
        self._greedy = jax.jit(self._make_greedy())

    @property
    def _op_impl(self) -> str:
        return self.path.impl

    @property
    def update_path(self) -> str:
        return self.path.name

    # ------------------------------------------------------------------
    def _state_index(self, counts, scen: FleetScenario):
        users = scen.users
        s = counts[:, 0] * (users + 1) + counts[:, 1]
        if self.cfg.track_links:
            weights = 2 ** jnp.arange(users)
            packed = (scen.end_b * weights[None, :]).sum(-1) * 2 + scen.edge_b
            s = s * self._link_states + packed
        return s

    def _make_fused_core(self):
        """env step + fused TD update from a precomputed ``(s, greedy)``
        pair — the body shared by the single step and the scan (which
        carries ``greedy2`` instead of re-gathering the ``s2`` Q-row
        next step). ``q`` is in the layout of ``self.path``
        (``path.enter``) and stays in it."""
        cfg, pu, path = self.cfg, self.pu_table, self.path
        advance, n_actions = self.source.step, self.n_actions

        def core(q, mets, counts, scen, eps, key, s, greedy):
            with jax.named_scope("fleet.act"):
                k_exp, k_noise, k_scen = jax.random.split(key, 3)
                # eps-greedy: one uniform drives both the explore decision
                # and, given u < eps, the (still uniform) action u/eps
                u = jax.random.uniform(k_exp, greedy.shape)
                rand = jnp.minimum((u / jnp.maximum(eps, 1e-9)
                                    * n_actions).astype(jnp.int32),
                                   n_actions - 1)
                a = jnp.where(u < eps, rand, greedy)           # (cells,)
                per_user = pu[a]                               # (cells, N)
            with jax.named_scope("fleet.respond"):
                mean_ms, acc, counts2 = simulate_responses(
                    k_noise, scen, per_user, cfg.noise)
                r = dynamics.reward(mean_ms, acc, cfg.accuracy_threshold,
                                    xp=jnp)
            with jax.named_scope("fleet.scenario"):
                scen2, _ = advance(k_scen, scen)
                s2 = self._state_index(counts2, scen2)
            with jax.named_scope("fleet.update"):
                q, greedy2, td = path.update(q, s, a, r, s2,
                                             alpha=cfg.alpha,
                                             gamma=cfg.gamma)
            if mets is not None:   # trace-time constant, no host sync
                with jax.named_scope("fleet.telemetry"):
                    mets = mets.update({"reward": r, "mean_ms": mean_ms,
                                        "td_abs": jnp.abs(td),
                                        "epsilon": eps})
            info = {"mean_ms": mean_ms, "mean_acc": acc, "reward": r}
            return q, mets, counts2, scen2, greedy2, info

        return core

    def _make_step(self):
        core, path = self._make_fused_core(), self.path

        def step(q, mets, counts, scen, eps, key):
            q = path.enter(q)
            s = self._state_index(counts, scen)
            greedy = first_argmax_ref(path.rows(q, s))
            q, mets, counts2, scen2, _, info = core(
                q, mets, counts, scen, eps, key, s, greedy)
            return path.leave(q), mets, counts2, scen2, info

        return step

    def _make_run(self):
        """n environment steps for the whole fleet in ONE jitted lax.scan
        call (amortizes dispatch; donation keeps the table in place).
        The scan carries each step's ``greedy2`` (the act-side row
        gather happens once, in the PREVIOUS step's update) and the
        table in the layout of ``self.path``, brought in before it
        (``fleet.prologue``) and taken out after it
        (``fleet.epilogue``)."""
        decay, eps_min = self.cfg.eps_decay, self.cfg.eps_min
        core, path = self._make_fused_core(), self.path

        def run(q, mets, counts, scen, eps, key, n):
            def body(carry, _):
                q, mets, counts, scen, greedy, eps, key = carry
                with jax.named_scope("fleet.act"):
                    key, k = jax.random.split(key)
                    s = self._state_index(counts, scen)
                q, mets, counts, scen, greedy, info = core(
                    q, mets, counts, scen, eps, k, s, greedy)
                with jax.named_scope("fleet.act"):
                    eps = jnp.maximum(eps_min, eps * (1.0 - decay))
                with jax.named_scope("fleet.telemetry"):
                    trace = (info["mean_ms"].mean(),
                             info["mean_acc"].mean())
                return (q, mets, counts, scen, greedy, eps, key), trace
            with jax.named_scope("fleet.prologue"):
                s0 = self._state_index(counts, scen)
                q = path.enter(q)
                greedy0 = first_argmax_ref(path.rows(q, s0))
            carry, (ms, acc) = jax.lax.scan(
                body, (q, mets, counts, scen, greedy0, eps, key),
                None, length=n)
            q, mets, counts, scen, _, eps, key = carry
            with jax.named_scope("fleet.epilogue"):
                q = path.leave(q)
            return (q, mets, counts, scen, eps, key), ms, acc

        return run

    def step(self):
        """Advance every cell by one environment step (one jitted call)."""
        self.key, k = jax.random.split(self.key)
        self.q, self.metrics, self.counts, self.scen, info = self._step(
            self.q, self.metrics, self.counts, self.scen, self.eps, k)
        self.eps = max(self.cfg.eps_min,
                       self.eps * (1.0 - self.cfg.eps_decay))
        self.steps += 1
        return info

    def run(self, n: int):
        """Advance every cell by ``n`` steps inside one jitted scan.
        Returns per-step fleet-mean (ms, accuracy) traces of shape (n,)."""
        with span(self.spans, "fleet.run", steps=n,
                  cells=int(self.q.shape[0]), update=self.update_path):
            self.key, k = jax.random.split(self.key)
            (self.q, self.metrics, self.counts, self.scen, eps, _), ms, \
                acc = self._run(self.q, self.metrics, self.counts,
                                self.scen, self.eps, k, n)
            with span(self.spans, "fleet.run.fetch"):
                self.eps = float(eps)
                ms, acc = np.asarray(ms), np.asarray(acc)
        self.steps += n
        return ms, acc

    def metrics_summary(self):
        """Host-side summary of the in-scan telemetry (``None`` when the
        agent was built with ``metrics=False``)."""
        return None if self.metrics is None else self.metrics.summary()

    # ------------------------------------------------------------------
    def _make_greedy(self):
        """One vectorized greedy pass: (cells, N) decisions + (cells,)
        action ids — shared by training checks and FleetOrchestrator."""
        pu = self.pu_table

        def greedy(q, counts, scen):
            s = self._state_index(counts, scen)
            # first_argmax_ref == jnp.argmax (first-index tie-break),
            # ~2x faster on CPU XLA; shared with the fused hot path
            a = first_argmax_ref(q[jnp.arange(q.shape[0]), s])
            return pu[a], a

        return greedy

    def greedy_decisions(self) -> jnp.ndarray:
        """(cells, N) per-user decisions from one vectorized greedy pass
        at each cell's current state."""
        return self._greedy(self.q, self.counts, self.scen)[0]

    @property
    def accuracy_threshold(self) -> float:
        return self.cfg.accuracy_threshold

    def policy_decisions(self, counts, scen):
        """(cells, N) per-user decisions + (cells,) action ids from one
        vectorized greedy pass over the batched Q-table (the
        FleetOrchestrator entry point, shared with ``FleetDQN``).

        Each cell's table is tied to the fleet it trained on, so unlike
        the shared-policy DQN this agent cannot serve a held-out fleet —
        ``scen`` may vary link/membership state but must have this
        agent's cells."""
        check_pad_width(self.spec.n_users, scen, "FleetQLearning")
        if scen.cells != self.q.shape[0]:
            raise ValueError(
                f"FleetQLearning holds one Q-table per trained cell "
                f"({self.q.shape[0]}); it cannot route a {scen.cells}-cell "
                "scenario — use the shared-policy fleet.policy.FleetDQN "
                "for held-out fleets")
        return self._greedy(self.q, counts, scen)

    def train(self, max_steps: int, check_every: int = 200,
              tol: float = 0.01, patience: int = 3) -> "FleetTrainResult":
        """Train all cells; per-cell convergence = greedy expected response
        within ``tol`` of that cell's brute-force optimum for ``patience``
        consecutive checks (fleet analogue of ``train_agent``)."""
        return train_against_oracle(self, max_steps, check_every=check_every,
                                    tol=tol, patience=patience)

    def greedy_expected(self, scen: Optional[FleetScenario] = None,
                        counts=None):
        """Noise-free (mean ms, mean acc) of each cell's greedy decision.
        Accepts ``scen``/``counts`` for API parity with ``FleetDQN`` (so
        ``holdout_reward_ratio`` takes either agent), but the per-cell
        tables only serve this agent's own fleet — a genuinely held-out
        scenario raises via ``policy_decisions``."""
        eval_scen = scen if scen is not None else self.scen
        if counts is None:
            counts = (self.counts if scen is None else
                      jnp.zeros((eval_scen.cells, 2), jnp.int32))
        per_user = self.policy_decisions(counts, eval_scen)[0]
        ms, acc = nominal_expected_response(eval_scen, per_user)
        return np.asarray(ms), np.asarray(acc)

    # ------------------------------------------------ FleetPolicy protocol
    def decisions(self, counts, scen: FleetScenario):
        """``api.FleetPolicy`` surface (alias of ``policy_decisions``)."""
        return self.policy_decisions(counts, scen)

    def expected(self, scen: Optional[FleetScenario] = None, counts=None):
        """``api.FleetPolicy`` surface (alias of ``greedy_expected``)."""
        return self.greedy_expected(scen=scen, counts=counts)


def train_against_oracle(agent, max_steps: int, check_every: int = 200,
                         tol: float = 0.01,
                         patience: int = 3) -> "FleetTrainResult":
    """THE fleet training loop, shared by ``FleetQLearning`` and
    ``fleet.policy.FleetDQN`` (anything with ``run`` /
    ``greedy_expected`` / ``scen`` / ``pu_table`` / ``fleet_cfg`` /
    ``accuracy_threshold``): per-cell convergence = greedy expected
    response within ``tol`` of that cell's brute-force optimum for
    ``patience`` consecutive checks (fleet analogue of ``train_agent``).

    For dynamic fleets (Markov links / churn / trace replay) the
    scenario — and so the optimum — moves between checks; the oracle is
    then recomputed per check, and "converged" means tracking the
    current optimum. Whether the fleet is dynamic comes from the
    agent's ``ScenarioSource`` (``source.dynamic``); agents built
    outside the source seam fall back to their ``fleet_cfg``."""
    threshold = agent.accuracy_threshold
    source = getattr(agent, "source", None)
    if source is not None:
        dynamic = bool(source.dynamic)
    else:
        fc = agent.fleet_cfg
        dynamic = bool(fc.p_r2w or fc.p_w2r or fc.p_join or fc.p_leave
                       or fc.p_edge_fail)
    opt_ms = None                        # dynamic: computed per check instead
    if not dynamic:
        opt_ms = np.asarray(fleet_bruteforce(
            agent.scen, agent.pu_table, threshold)[0])
    cells = agent.scen.cells
    converged_at = np.full(cells, -1, np.int64)
    streak = np.zeros(cells, np.int64)
    t0 = time.perf_counter()
    history = []
    for step in range(check_every, max_steps + 1, check_every):
        agent.run(check_every)
        if dynamic:
            opt_ms = np.asarray(fleet_bruteforce(
                agent.scen, agent.pu_table, threshold)[0])
        g_ms, g_acc = agent.greedy_expected()
        ok = np.asarray(dynamics.feasible(g_acc, threshold)
                        & (g_ms <= opt_ms * (1 + tol)))
        streak = np.where(ok, streak + 1, 0)
        newly = (streak >= patience) & (converged_at < 0)
        converged_at[newly] = step - (patience - 1) * check_every
        frac = float((converged_at >= 0).mean())
        history.append({"step": step, "frac_converged": frac,
                        "median_greedy_ms": float(np.median(g_ms))})
        if frac >= 1.0:
            break
    else:
        if max_steps < check_every:          # loop never ran
            g_ms, g_acc = agent.greedy_expected()
    if opt_ms is None:                       # dynamic fleet, loop never ran
        opt_ms = np.asarray(fleet_bruteforce(
            agent.scen, agent.pu_table, threshold)[0])
    from repro.obs.report import run_manifest
    wall = time.perf_counter() - t0
    return FleetTrainResult(
        converged_at=converged_at, steps=agent.steps,
        frac_converged=float((converged_at >= 0).mean()),
        optimal_ms=np.asarray(opt_ms), greedy_ms=np.asarray(g_ms),
        greedy_acc=np.asarray(g_acc), history=history,
        wall_seconds=wall,
        manifest=run_manifest(config=agent.cfg,
                              mesh=getattr(agent, "mesh", None),
                              wall_seconds=wall, steps=agent.steps))


@dataclasses.dataclass
class FleetTrainResult:
    converged_at: np.ndarray         # (cells,) step index, -1 = not yet
    steps: int
    frac_converged: float
    optimal_ms: np.ndarray           # (cells,)
    greedy_ms: np.ndarray            # (cells,)
    greedy_acc: np.ndarray           # (cells,)
    history: list
    wall_seconds: float
    #: provenance stamp (repro.obs.report.run_manifest) for this run
    manifest: Optional[dict] = None

    @property
    def cells_per_second(self) -> float:
        """Converged cells per wall-clock second of training."""
        n = int((self.converged_at >= 0).sum())
        return n / max(self.wall_seconds, 1e-9)


# ---------------------------------------------------------------------------
def fleet_bruteforce(scen: FleetScenario, pu_table: jnp.ndarray,
                     threshold: float = 0.0, chunk: int = 4096):
    """Per-cell optimum over the candidate action table under nominal
    load (all member users requesting). Returns ((cells,) best ms,
    (cells,) best index).

    Isolated fleets get the exact chunked brute force; with an attached
    ``scen.topo`` the per-cell argmax is no longer exact (cells couple
    through shared edges and the cloud queue), so this dispatches to the
    coordinate-descent ``topology_bruteforce`` — same return contract,
    so ``train_against_oracle`` / ``holdout_reward_ratio`` work
    unchanged on either fleet kind.
    """
    if scen.topo is not None:
        ms, idx, _, _ = topology_bruteforce(scen, pu_table, threshold,
                                            chunk=chunk)
        return ms, idx
    return _isolated_bruteforce(scen, pu_table, threshold, chunk)


def _isolated_bruteforce(scen: FleetScenario, pu_table: jnp.ndarray,
                         threshold: float = 0.0, chunk: int = 4096):
    """The exact per-cell brute force for uncoupled cells: evaluates all
    K candidates for all cells, chunked over K to bound the
    ``cells x chunk x N`` intermediate."""
    member = scen.member
    best_ms = jnp.full((scen.cells,), jnp.inf)
    best_idx = jnp.zeros((scen.cells,), jnp.int32)
    for lo in range(0, pu_table.shape[0], chunk):
        pu = pu_table[lo:lo + chunk]                           # (k, N)
        ms, acc = dynamics.fleet_actions_expected_response(
            pu, scen.end_b, scen.edge_b, member,
            calib=scen.calib)                                  # (cells, k)
        ms = jnp.where(dynamics.feasible(acc, threshold, xp=jnp), ms,
                       jnp.inf)
        i = ms.argmin(-1)
        m = jnp.take_along_axis(ms, i[:, None], -1)[:, 0]
        better = m < best_ms
        best_idx = jnp.where(better, i + lo, best_idx).astype(jnp.int32)
        best_ms = jnp.where(better, m, best_ms)
    if bool(jnp.isinf(best_ms).any()):
        raise ValueError("no feasible action for threshold %.2f in %d cells"
                         % (threshold, int(jnp.isinf(best_ms).sum())))
    return best_ms, best_idx


#: minimum per-cell improvement (ms) for a best-response switch — a
#: strict-improvement margin so equal-cost candidates can't cycle
BEST_RESPONSE_TOL = 1e-6


@jax.jit
def _best_response_round(idx, pu_table, end_b, edge_b, member, feas,
                         cand_e, cand_c, cell_edge, edge_capacity,
                         cloud_servers, calib=None):
    """One Gauss-Seidel sweep: each cell in turn picks its best feasible
    candidate given every OTHER cell's current decision, with running
    per-edge / cloud totals updated in place (O(1) per cell instead of a
    fleet-wide re-aggregation). ``feas`` / ``cand_e`` / ``cand_c`` are
    the (cells, K) round-invariant tables precomputed by
    ``topology_bruteforce`` — recomputing them here would redo a
    cells x K x N reduce on every sweep."""
    n_edges = edge_capacity.shape[0]
    cells = idx.shape[0]
    rows = jnp.arange(cells)
    e_cnt = cand_e[rows, idx]
    c_cnt = cand_c[rows, idx]
    edge_tot = jax.ops.segment_sum(e_cnt, cell_edge, num_segments=n_edges)
    cloud_tot = c_cnt.sum()

    def body(i, carry):
        idx, e_cnt, c_cnt, edge_tot, cloud_tot = carry
        e_i = cell_edge[i]
        n_e_k = (edge_tot[e_i] - e_cnt[i] + cand_e[i]) / edge_capacity[e_i]
        tot_c_k = cloud_tot - c_cnt[i] + cand_c[i]
        mult_k = topology.cloud_load_multiplier(tot_c_k, cloud_servers,
                                                xp=jnp)
        ms_k, _ = dynamics.expected_response(
            pu_table, end_b[i][None, :], edge_b[i],
            active=member[i][None, :], counts=(n_e_k, cand_c[i]),
            cloud_mult=mult_k[:, None], calib=calib, xp=jnp)  # (K,)
        score = jnp.where(feas[i], ms_k, jnp.inf)
        j = score.argmin()
        cur = idx[i]
        new = jnp.where(score[j] < score[cur] - BEST_RESPONSE_TOL, j,
                        cur).astype(idx.dtype)
        edge_tot = edge_tot.at[e_i].add(cand_e[i, new] - e_cnt[i])
        cloud_tot = cloud_tot + cand_c[i, new] - c_cnt[i]
        return (idx.at[i].set(new), e_cnt.at[i].set(cand_e[i, new]),
                c_cnt.at[i].set(cand_c[i, new]), edge_tot, cloud_tot)

    idx, _, _, _, _ = jax.lax.fori_loop(
        0, cells, body, (idx, e_cnt, c_cnt, edge_tot, cloud_tot))
    return idx


def topology_bruteforce(scen: FleetScenario, pu_table: jnp.ndarray,
                        threshold: float = 0.0, max_rounds: int = 50,
                        chunk: int = 4096):
    """Coupled-fleet oracle: coordinate descent by best response.

    Once cells share an edge or queue at the cloud, the per-cell argmax
    of ``_isolated_bruteforce`` is no longer exact — one cell's best
    decision depends on its neighbors'. Starting from the isolated
    optimum, this sweeps the fleet in Gauss-Seidel rounds (each cell
    best-responds to every other cell's current decision; feasibility
    depends only on a cell's own action, so the filter is exact) until a
    full round changes nothing — a pure equilibrium of the resulting
    congestion game, the standard orchestration target for this
    coupling — or ``max_rounds`` sweeps.

    Returns ``((cells,) ms, (cells,) index, converged, rounds)`` where
    ``ms`` is each cell's nominal-load expected response under shared
    contention and ``converged`` reports the fixed-point check (False
    means a best-response cycle was cut off at ``max_rounds`` and the
    result is the last sweep, still feasible but possibly unstable).
    Without an attached topology this is exactly the isolated oracle
    (converged in 0 rounds).
    """
    if scen.topo is None:
        ms, idx = _isolated_bruteforce(scen, pu_table, threshold, chunk)
        return ms, idx, True, 0
    # isolated optimum as the starting point (also raises on an
    # infeasible threshold — feasibility is contention-independent)
    _, idx = _isolated_bruteforce(scen, pu_table, threshold, chunk)
    # round-invariant (cells, K) tables, built chunked over K so the
    # cells x chunk x N intermediate stays as bounded as the isolated
    # oracle's: the feasibility filter and the per-candidate edge/cloud
    # offload counts under nominal (member) load
    member = np.asarray(scen.member)
    cells_n, K = member.shape[0], pu_table.shape[0]
    nm = np.maximum(member.sum(-1), 1)[:, None]
    any_m = member.any(-1)[:, None]
    pu_np = np.asarray(pu_table)
    feas_np = np.empty((cells_n, K), bool)
    cand_e_np = np.empty((cells_n, K), np.int32)
    cand_c_np = np.empty((cells_n, K), np.int32)
    for lo in range(0, K, chunk):
        pu = pu_np[lo:lo + chunk]                            # (k, N)
        acc = dynamics.accuracies(pu)
        macc = np.where(any_m,
                        (acc[None] * member[:, None, :]).sum(-1) / nm,
                        100.0)
        feas_np[:, lo:lo + chunk] = dynamics.feasible(macc, threshold)
        cand_e_np[:, lo:lo + chunk] = ((pu[None] == dynamics.A_EDGE)
                                       & member[:, None, :]).sum(-1)
        cand_c_np[:, lo:lo + chunk] = ((pu[None] == dynamics.A_CLOUD)
                                       & member[:, None, :]).sum(-1)
    feas = jnp.asarray(feas_np)
    cand_e, cand_c = jnp.asarray(cand_e_np), jnp.asarray(cand_c_np)
    topo = scen.topo
    converged, rounds = False, 0
    for rounds in range(1, max_rounds + 1):
        new_idx = _best_response_round(
            idx, pu_table, scen.end_b, scen.edge_b, scen.member, feas,
            cand_e, cand_c, topo.cell_edge, topo.edge_capacity,
            topo.cloud_servers, calib=scen.calib)
        if bool((new_idx == idx).all()):
            converged = True
            break
        idx = new_idx
    ms, _ = topology.fleet_topology_expected_response(
        pu_table[idx], scen.end_b, scen.edge_b, topo, scen.member,
        calib=scen.calib)
    return ms, idx, converged, rounds


