"""Fleet-scale shared-policy DQN: one network, pooled experience.

``population.FleetQLearning`` gives every cell its own dense Q-table —
nothing is shared, the table caps out around 10^3 states x actions, and
a cell can only ever learn from its own history. This module is the
other end of the design space (ROADMAP "Fleet-scale DQN"): ONE factored
Q-network (``core.networks.make_factored_q``, the VDN-style per-user
decomposition of ``core.dqn``'s ``form='factored'``) trained on the
pooled experience of the whole fleet.

Three pieces make it fleet-shaped:

* **Featurized state** (``encode_fleet_state``): instead of dense table
  indices, each cell is a vector of per-user request bits, per-user
  membership (the cell-size mask), per-user end-link quality, the edge
  backhaul state, and the previous step's normalized job counts. One
  network therefore serves heterogeneous cell sizes and link patterns it
  never trained on — generalization the per-cell table cannot do.
* **On-device replay** (``fleet.replay.FleetReplay``): every fleet step
  pushes ``cells`` transitions and samples one mini-batch without
  leaving the device, so act + env + TD-update + replay stay inside a
  single ``lax.scan`` with zero host sync (buffers donated like the
  fleet Q-table).
* **Constraint-aware greedy head**: the sum decomposition cannot
  represent the QoS constraint (paper Eq. 4) — a mean-accuracy cliff
  shared across users — so, exactly like ``core.dqn``'s constraint
  greedy, the head enumerates per-user top-k combinations and filters
  them by the *known* Table-4 accuracy ladder, vectorized over the whole
  fleet: ``(cells, topk^N)`` candidates in one jitted pass.

Two design choices measurably unlock cross-size generalization (each
was worth ~15-75% held-out regret in ablation; ``net='cell'`` keeps the
monolithic baseline for comparison):

* **Sum-scaled regression target.** The env reward is the *mean*
  response over active users (paper Eq. 4), so fitting it directly
  forces per-user values onto a 1/n scale that varies with cell size —
  a size-2 cell's values don't transfer to a size-1 cell. The factored
  sum instead regresses on ``n_active * reward`` (the summed response):
  per-user values become size-invariant estimates of each user's own
  -ms contribution, and the per-cell argmax/ranking is unchanged
  (positive per-state scaling). When a QoS goal is set, the regression
  target stays the un-floored delay term: the constraint cliff is not
  representable by a sum of per-user values (it would just corrupt the
  ranking — observed as a ~20% held-out regret plateau), and
  feasibility is enforced exactly by the greedy head instead, which is
  precisely how ``core.dqn``'s constraint-greedy divides the labor. The
  reported ``info["reward"]`` remains the paper's floored reward.
* **Weight-shared per-user encoder** (``net='shared'``, default): one
  MLP maps each user's local view (own request bit, membership, link
  state, plus cell aggregates: edge link, active fraction, job counts,
  weak-link fraction) to that user's action values, vmapped over the
  user axis. The head is permutation-equivariant and size-invariant by
  construction — a fleet trained on 2-3-user cells routes 1-user cells
  it never saw at the brute-force optimum, where the monolithic
  ``net='cell'`` trunk (``core.networks.make_factored_q`` over the flat
  state) overfits the member-pattern bits it trained on.

``FleetDQN`` mirrors ``FleetQLearning``'s API (``step`` / ``run`` /
``train`` / ``greedy_decisions`` / ``policy_decisions``) so
``FleetOrchestrator`` and ``train_against_oracle`` accept either agent.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.networks import make_factored_q, mlp_apply, mlp_init
from repro.core.spaces import (N_PER_USER_ACTIONS, SpaceSpec,
                               allowed_per_user)
from repro.fleet import dynamics
from repro.fleet.population import (FleetTrainResult, adopt_mesh,
                                    check_pad_width, default_actions,
                                    fleet_bruteforce, fleet_metrics,
                                    nominal_expected_response,
                                    place_metrics, resolve_source,
                                    simulate_responses,
                                    train_against_oracle)
from repro.fleet.replay import (replay_init, replay_push, replay_sample,
                                replay_size)
from repro.kernels import ops
from repro.fleet.scenarios import FleetConfig, FleetScenario
from repro.training.optimizer import (apply_updates, constant_lr_adamw,
                                      init_opt_state)


def state_dim(users: int) -> int:
    """Feature width of ``encode_fleet_state``: 3 per-user blocks
    (active, member, end link) + edge link + 2 counts + cell size + 3
    topology features (own-edge shared load, own-edge capacity, fleet
    cloud utilization)."""
    return 3 * users + 7


def _topo_features(counts, scen: FleetScenario):
    """The three (cells, 1) topology features — own-edge shared load,
    own-edge capacity tier, fleet cloud utilization — shared by
    ``encode_fleet_state`` (flat layout) and ``fused_head_features``
    (direct per-user blocks) so the two paths cannot drift."""
    inv = 1.0 / scen.users
    counts_f = counts.astype(jnp.float32)
    if scen.topo is None:
        edge_load = counts_f[:, :1] * inv          # own jobs == shared jobs
        cap = jnp.ones((scen.cells, 1), jnp.float32)
        util = jnp.zeros((scen.cells, 1), jnp.float32)
    else:
        topo = scen.topo
        tot = jax.ops.segment_sum(counts[:, 0], topo.cell_edge,
                                  num_segments=topo.n_edges)
        cap_cell = topo.edge_capacity[topo.cell_edge]
        edge_load = (tot[topo.cell_edge] / cap_cell)[:, None] * inv
        cap = cap_cell[:, None]
        util = jnp.broadcast_to(counts_f[:, 1].sum() / topo.cloud_servers,
                                (scen.cells, 1))
    return edge_load.astype(jnp.float32), cap, util


def encode_fleet_state(counts, scen: FleetScenario) -> jnp.ndarray:
    """(cells, state_dim) feature encoding of the fleet state.

    Layout (N = scen.users):
      [0:N)    per-user request bits (active this step)
      [N:2N)   per-user membership bits (the cell-size mask)
      [2N:3N)  per-user end-link state (0 Regular, 1 Weak)
      [3N]     edge backhaul link state
      [3N+1,2] previous step's (edge, cloud) job counts / N
      [3N+3]   cell size / N
      [3N+4]   own edge's SHARED load: last-step edge jobs summed over
               every cell on this cell's edge, / (N * capacity) — the
               neighbor-pressure signal (== [3N+1] for isolated fleets)
      [3N+5]   own edge's capacity tier (1.0 for isolated fleets)
      [3N+6]   fleet-wide cloud utilization, last-step cloud jobs /
               cloud_servers (0.0 for isolated / unbounded clouds)

    The loss slices the request bits back out of stored states to mask
    per-user terms, so the layout above is load-bearing — keep the
    active block first.
    """
    users = scen.users
    inv = 1.0 / users
    counts_f = counts.astype(jnp.float32)
    edge_load, cap, util = _topo_features(counts, scen)
    return jnp.concatenate([
        scen.active.astype(jnp.float32),
        scen.member.astype(jnp.float32),
        scen.end_b.astype(jnp.float32),
        scen.edge_b[:, None].astype(jnp.float32),
        counts_f * inv,
        scen.member.sum(-1, keepdims=True).astype(jnp.float32) * inv,
        edge_load.astype(jnp.float32),
        cap,
        util,
    ], axis=-1)


#: per-user input width of the shared encoder: [own request bit, own
#: membership, own end-link, edge link, active fraction, edge jobs /N,
#: cloud jobs /N, weak-link fraction among active users, own-edge shared
#: load, own-edge capacity, fleet cloud utilization]
N_USER_FEATURES = 11


def make_shared_per_user_q(users: int, allowed):
    """Weight-shared per-user Q head (``net='shared'``).

    Rebuilds each user's local feature row from the flat
    ``encode_fleet_state`` vector and applies ONE shared MLP
    (``N_USER_FEATURES -> ... -> N_PER_USER_ACTIONS``) to every user —
    permutation-equivariant, so per-user values transfer across cell
    sizes and user orderings the fleet never trained on."""
    allowed = jnp.asarray(allowed)

    def per_user_q(params, s):
        n = users
        act, mem, end = s[:, :n], s[:, n:2 * n], s[:, 2 * n:3 * n]
        cell = s[:, 3 * n:3 * n + 3]               # edge_b, n_e/N, n_c/N
        topo_f = s[:, 3 * n + 4:3 * n + 7]         # shared load, cap, util
        n_act = act.sum(-1, keepdims=True)
        weak = (end * act).sum(-1, keepdims=True) / jnp.maximum(n_act, 1.0)
        agg = jnp.concatenate([cell[:, :1], n_act / n, cell[:, 1:], weak,
                               topo_f], -1)        # (B, 8)
        f = jnp.concatenate(
            [act[..., None], mem[..., None], end[..., None],
             jnp.broadcast_to(agg[:, None, :], (s.shape[0], n, 8))], -1)
        q = mlp_apply(params, f.reshape(-1, N_USER_FEATURES))
        return jnp.where(allowed[None], q.reshape(s.shape[0], n, -1), -1e30)

    return per_user_q


def fused_head_features(counts, scen: FleetScenario):
    """The fused head's inputs — per-user ``(active, member, end_b)``
    blocks plus the (cells, 8) cell-aggregate rows — assembled directly
    from the scenario, skipping the flat ``encode_fleet_state`` vector
    that ``make_shared_per_user_q`` would only re-slice apart. The
    arithmetic is the same op sequence, so the resulting feature rows
    (and the head's Q values) are bit-identical to the legacy path."""
    act = scen.active.astype(jnp.float32)
    end = scen.end_b.astype(jnp.float32)
    inv = 1.0 / scen.users
    counts_f = counts.astype(jnp.float32)
    edge_load, cap, util = _topo_features(counts, scen)
    n_act = act.sum(-1, keepdims=True)
    weak = (end * act).sum(-1, keepdims=True) / jnp.maximum(n_act, 1.0)
    # n_act / users (not * inv): the exact float op the legacy head
    # applies, so the rows stay bit-identical
    agg = jnp.concatenate(
        [scen.edge_b[:, None].astype(jnp.float32), n_act / scen.users,
         counts_f * inv, weak, edge_load, cap, util], -1)  # (cells, 8)
    return act, scen.member.astype(jnp.float32), end, agg


class HoldoutEval(NamedTuple):
    """Result of ``holdout_reward_ratio``: all rewards are negative
    (-expected ms; QoS-infeasible cells floored at -MAX_RESPONSE_MS), so
    ``ratio`` = optimal/achieved reward is 1.0 at the per-cell
    brute-force optimum and < 1 under regret (an untrained policy scores
    ~0.35)."""
    ratio: float              # fraction-of-optimal expected reward
    achieved: np.ndarray      # (cells,) the policy's expected rewards
    optimal: np.ndarray       # (cells,) brute-force expected rewards
    feasible: np.ndarray      # (cells,) bool, greedy meets the QoS goal


def holdout_reward_ratio(agent, scen: FleetScenario,
                         threshold: Optional[float] = None) -> HoldoutEval:
    """Score ``agent``'s cold-start greedy decisions on a (held-out)
    ``scen`` against the per-cell brute-force oracle over the agent's
    candidate set — THE cross-cell generalization metric, shared by the
    acceptance test, ``benchmarks/bench_fleet_dqn.py``, and the
    quickstart example so the floor/feasibility convention can't drift."""
    th = agent.accuracy_threshold if threshold is None else threshold
    expected = getattr(agent, "expected", None)       # FleetPolicy protocol
    g_ms, g_acc = (expected(scen) if expected is not None
                   else agent.greedy_expected(scen=scen))
    feas = np.asarray(dynamics.feasible(g_acc, th))
    opt_ms = np.asarray(fleet_bruteforce(scen, agent.pu_table, th)[0])
    achieved = np.where(feas, -g_ms, -dynamics.MAX_RESPONSE_MS)
    return HoldoutEval(float((-opt_ms).mean() / achieved.mean()),
                       achieved, -opt_ms, feas)


@dataclasses.dataclass
class FleetDQNConfig:
    lr: float = 1e-3                  # paper Table 7
    gamma: float = 0.1
    eps_start: float = 1.0
    eps_decay: float = 2e-3           # multiplicative, per fleet step
    eps_min: float = 0.02
    replay_capacity: int = 65536      # pooled transitions (rows)
    batch_size: int = 256
    hidden: int = 128                 # paper §5.4's widest rung
    noise: float = 0.02
    accuracy_threshold: float = 0.0   # QoS goal (paper Eq. 4)
    topk: int = 5                     # constraint head's per-user top-k
    net: str = "shared"               # 'shared' | 'cell' (see module doc)


class FleetDQN:
    """Shared-policy factored DQN over a fleet of cells.

    One ``step()`` = one environment step for EVERY cell plus one
    mini-batch update from the pooled replay, all inside a single jitted
    call; ``run(n)`` amortizes n of those into one ``lax.scan``.

    ``actions``: optional joint candidate set. Unlike the tabular agent
    the factored head never enumerates joint actions, so by default the
    policy spans the full 10^N space (per-user mask all-allowed) while
    the *oracle* used by ``train()`` still scores against
    ``default_actions`` (full space for N<=3, the SOTA-restricted set
    above — a lower bound on the true optimum there). Passing ``actions``
    restricts both to that candidate set.
    """

    def __init__(self, scen, fleet_cfg: Optional[FleetConfig] = None,
                 cfg: Optional[FleetDQNConfig] = None,
                 actions: Optional[np.ndarray] = None, seed: int = 0,
                 reset_key=None, mesh=None, metrics: bool = True,
                 n_windows: int = 0, window_len: int = 1,
                 impl: str = "pallas"):
        """``scen`` is a ``repro.fleet.api.ScenarioSource`` (reset with
        ``reset_key``, default ``PRNGKey(seed)``) — or, equivalently, a
        ``FleetScenario`` plus its ``FleetConfig`` (wrapped into a
        ``SyntheticSource`` pinned to that scenario).

        ``mesh`` (``repro.fleet.shard.fleet_mesh``; default: the
        source's own mesh, if any) is data-parallel training: params
        and optimizer state REPLICATE across devices, the scenario
        stream shards along the fleet axis, the replay ring splits its
        slot blocks across devices (see ``shard.shard_replay`` — push/
        sample reshard inside the scan), and the mini-batch loss mean
        becomes the partitioner's cross-device gradient reduction.

        ``metrics`` (default on) rides a ``repro.obs`` accumulator in
        the scan carry — per-step reward / response time / loss /
        replay occupancy / epsilon with zero host syncs; read it via
        ``metrics_summary``. Recording consumes no RNG and never feeds
        back into training, so trajectories are bit-identical with it
        on or off — including with ``n_windows > 0``, which adds a
        per-window ring (``window_len`` steps per slot) to every
        stream so ``metrics_summary()`` carries the learning curve.

        ``impl`` selects the encode/act head implementation:
        ``"pallas"`` (default) is the fused featurize + constraint-aware
        greedy head (``kernels.dqn_head``) — per-user feature rows
        assembled directly from the scenario, the shared MLP, the
        allowed-action mask, and the top-k accuracy-ladder filter in one
        fused pass (the compiled Pallas kernel on TPU, the
        bit-equivalent fused-jnp formulation elsewhere; see
        ``kernels.ops.resolve_rl_impl``). ``"xla"`` keeps the legacy
        head; ``"pallas_interpret"`` forces the real kernel in
        interpret mode (parity tests). The fused head exists only for
        the weight-shared ``net='shared'`` encoder — ``net='cell'``
        agents fall back to the legacy head regardless of ``impl``."""
        self.cfg = cfg or FleetDQNConfig()
        scen, self.source = resolve_source(scen, fleet_cfg, seed, reset_key)
        self.fleet_cfg = getattr(self.source, "cfg", None)
        self.mesh, scen = adopt_mesh(mesh, self.source, scen)
        self.spec = SpaceSpec(scen.users)
        users = scen.users
        if actions is None:
            self.allowed = np.ones((users, N_PER_USER_ACTIONS), bool)
            oracle = default_actions(self.spec)
        else:
            oracle = np.asarray(actions)
            self.allowed = allowed_per_user(self.spec, oracle)
        self.pu_table = jnp.asarray(self.spec.decode_actions_batch(oracle))
        self.state_dim = state_dim(users)
        key = jax.random.PRNGKey(seed)
        k_net, self.key = jax.random.split(key)
        h = self.cfg.hidden
        if self.cfg.net == "shared":
            self.params = mlp_init(
                k_net, [N_USER_FEATURES, h, h, N_PER_USER_ACTIONS])
            self._per_user_q = make_shared_per_user_q(users, self.allowed)
        elif self.cfg.net == "cell":
            self.params = mlp_init(
                k_net, [self.state_dim, h, h, users * N_PER_USER_ACTIONS])
            self._per_user_q = make_factored_q(users, self.allowed)
        else:
            raise ValueError(f"unknown net form {self.cfg.net!r} "
                             "(expected 'shared' or 'cell')")
        self.impl = impl
        resolved = ops.resolve_rl_impl(impl, self.mesh)
        if self.cfg.net != "shared":
            resolved = "xla"        # fused head is shared-encoder only
        self._op_impl = resolved
        self._op_kwargs = (None if resolved == "xla"
                           else ops.rl_op_kwargs(resolved))
        #: where each part of the act head runs: the fused head splits
        #: kernel and XLA work (``ops.dqn_head_parts``)
        self.op_parts = ({"head": "xla"} if resolved == "xla" else
                         ops.dqn_head_parts(resolved,
                                            self.cfg.accuracy_threshold))
        self.opt = init_opt_state(self.params)
        self.buffer = replay_init(self.cfg.replay_capacity, self.state_dim,
                                  action_shape=(users,))
        self.scen = scen
        self.counts = jnp.zeros((scen.cells, 2), jnp.int32)
        self.metrics = fleet_metrics(scen.cells, "dqn",
                                     n_windows=n_windows,
                                     window_len=window_len) if metrics \
            else None
        if self.mesh is not None:
            from repro.fleet import shard
            self.params = shard.replicate(self.params, self.mesh)
            self.opt = shard.replicate(self.opt, self.mesh)
            self.buffer = shard.shard_replay(self.buffer, self.mesh)
            self.counts = shard.shard_array(self.counts, self.mesh)
            self.metrics = place_metrics(self.metrics, self.mesh)
        self.eps = self.cfg.eps_start
        self.steps = 0
        # one greedy/act/step closure each, threaded through the jitted
        # entry points so step() and run()'s scan body cannot diverge;
        # donate params/opt/replay (and the metrics accumulator riding
        # with them) so the scan updates them in place
        greedy = self._make_greedy()
        step = self._make_step(self._make_act(greedy))
        don = (0, 1, 2) if self.metrics is None else (0, 1, 2, 3)
        self._step = jax.jit(step, donate_argnums=don)
        self._run = jax.jit(self._make_run(step), static_argnums=(8,),
                            donate_argnums=don)
        self._greedy = jax.jit(greedy)

    @property
    def accuracy_threshold(self) -> float:
        return self.cfg.accuracy_threshold

    # ---------------------------------------------------------- policy ----
    def _make_greedy(self):
        """Vectorized greedy head: (params, counts, scen) -> ((cells, N)
        per-user decisions, (cells,) joint action ids). With a QoS goal
        set, enumerates per-user top-k combos and filters by the known
        accuracy table (constraint-aware, like ``core.dqn``)."""
        if self._op_impl != "xla":
            return self._make_fused_greedy()
        users = self.spec.n_users
        per_user_q = self._per_user_q
        threshold = self.cfg.accuracy_threshold
        k = min(self.cfg.topk, N_PER_USER_ACTIONS)
        powers = jnp.asarray(
            [N_PER_USER_ACTIONS ** (users - 1 - u) for u in range(users)],
            jnp.int32)
        # static (k^N, N) table of per-user top-k index combinations
        combos = jnp.asarray(
            list(itertools.product(range(k), repeat=users)), jnp.int32)
        uidx = jnp.broadcast_to(jnp.arange(users), combos.shape)

        def constrained(q, member):
            vals, idx = jax.lax.top_k(q, k)                # (cells, N, k)
            cand = idx[:, uidx, combos]                    # (cells, K, N)
            cvals = vals[:, uidx, combos]
            acc = dynamics.accuracies(cand, xp=jnp)
            m = member[:, None, :]
            nm = jnp.maximum(member.sum(-1), 1)[:, None]
            macc = jnp.where(member.any(-1)[:, None],
                             (acc * m).sum(-1) / nm, 100.0)
            score = (cvals * m).sum(-1)                    # (cells, K)
            # a user with fewer than k allowed actions gets top-k rows
            # padded with -1e30-masked DISALLOWED ids — their scores are
            # finite, so they must be culled here or the feasibility
            # filter can prefer an action outside the candidate set
            invalid = ((cvals < -1e29) & m).any(-1)
            score = jnp.where(dynamics.feasible(macc, threshold, xp=jnp)
                              & ~invalid, score, -jnp.inf)
            j = score.argmax(-1)
            best = jnp.take_along_axis(cand, j[:, None, None], 1)[:, 0]
            # no feasible combo in the top-k set: plain factored argmax
            return jnp.where(jnp.isfinite(
                jnp.take_along_axis(score, j[:, None], 1))[:, 0][:, None],
                best, q.argmax(-1))

        def greedy(params, counts, scen):
            q = per_user_q(params, encode_fleet_state(counts, scen))
            dec = (constrained(q, scen.member) if threshold
                   else q.argmax(-1)).astype(jnp.int32)
            return dec, (dec * powers[None, :]).sum(-1)

        return greedy

    def _make_fused_greedy(self):
        """The fused encode/act head: one ``kernels.ops.dqn_head`` call
        replaces encode_fleet_state -> per_user_q -> top-k constraint
        filter. Same (dec, joint id) contract as the legacy greedy."""
        users = self.spec.n_users
        threshold = float(self.cfg.accuracy_threshold)
        k = min(self.cfg.topk, N_PER_USER_ACTIONS)
        powers = jnp.asarray(
            [N_PER_USER_ACTIONS ** (users - 1 - u) for u in range(users)],
            jnp.int32)
        allowed = jnp.asarray(self.allowed)
        acc_table = jnp.asarray(
            dynamics.accuracies(np.arange(N_PER_USER_ACTIONS)),
            jnp.float32)
        op_kwargs = self._op_kwargs

        def greedy(params, counts, scen):
            act, mem, end, agg = fused_head_features(counts, scen)
            dec, _ = ops.dqn_head(act, mem, end, agg, params, allowed,
                                  acc_table, threshold=threshold, topk=k,
                                  **op_kwargs)
            return dec, (dec * powers[None, :]).sum(-1)

        return greedy

    def _make_act(self, greedy):
        """eps-greedy over the factored head: per-user exploration draws
        a uniform allowed action, greedy uses the (constraint-aware)
        head."""
        users = self.spec.n_users
        # padded per-user allowed-id table for uniform exploration draws
        n_allowed = self.allowed.sum(-1)
        ids = np.zeros((users, n_allowed.max()), np.int32)
        for u in range(users):
            ids[u, :n_allowed[u]] = np.flatnonzero(self.allowed[u])
        ids, n_allowed = jnp.asarray(ids), jnp.asarray(n_allowed)

        def act(params, counts, scen, eps, key):
            k_exp, k_rand = jax.random.split(key)
            dec, _ = greedy(params, counts, scen)
            shape = (scen.cells, users)
            j = (jax.random.uniform(k_rand, shape)
                 * n_allowed[None, :]).astype(jnp.int32)
            rand = ids[jnp.arange(users)[None, :], j]
            explore = jax.random.uniform(k_exp, shape) < eps
            return jnp.where(explore, rand, dec)

        return act

    # ------------------------------------------------------------ train ---
    def _make_train_step(self):
        cfg = self.cfg
        users = self.spec.n_users
        per_user_q = self._per_user_q
        opt_cfg = constant_lr_adamw(cfg.lr)

        def loss_fn(params, s, a, r, s2):
            # per-user terms masked by the request bits stored in the
            # state (inactive users' actions had no effect)
            act_m, act2_m = s[:, :users], s2[:, :users]
            q = per_user_q(params, s)                      # (B, N, NA)
            qa = (jnp.take_along_axis(q, a[..., None], 2)[..., 0]
                  * act_m).sum(-1)
            q2 = (per_user_q(params, s2).max(-1) * act2_m).sum(-1)
            target = r + cfg.gamma * jax.lax.stop_gradient(q2)
            return jnp.mean((qa - target) ** 2)

        def train_step(params, opt, s, a, r, s2):
            loss, grads = jax.value_and_grad(loss_fn)(params, s, a, r, s2)
            params, opt, _ = apply_updates(params, grads, opt, opt_cfg)
            return params, opt, loss

        return train_step

    def _make_step(self, act):
        cfg = self.cfg
        advance = self.source.step          # jit-pure ScenarioSource step
        train_step = self._make_train_step()

        def step(params, opt, buf, mets, counts, scen, eps, key):
            k_act, k_noise, k_scen, k_samp = jax.random.split(key, 4)
            s = encode_fleet_state(counts, scen)
            a = act(params, counts, scen, eps, k_act)       # (cells, N)
            mean_ms, acc, counts2 = simulate_responses(k_noise, scen, a,
                                                       cfg.noise)
            # regression target: summed (not mean) response, no floor —
            # size-invariant per-user values; see module docstring
            r_train = -(mean_ms * scen.active.sum(-1)) / 1000.0
            scen2, _ = advance(k_scen, scen)
            s2 = encode_fleet_state(counts2, scen2)
            buf = replay_push(buf, s, a, r_train, s2)
            bs, ba, br, bs2 = replay_sample(k_samp, buf, cfg.batch_size)
            params, opt, loss = train_step(params, opt, bs, ba, br, bs2)
            # reported reward stays the env's floored Eq.-4 reward
            r = dynamics.reward(mean_ms, acc, cfg.accuracy_threshold,
                                xp=jnp)
            if mets is not None:       # trace-time constant, no host sync
                fill = (replay_size(buf).astype(jnp.float32)
                        / buf.capacity)
                mets = mets.update({"reward": r, "mean_ms": mean_ms,
                                    "loss": loss, "replay_fill": fill,
                                    "epsilon": eps})
            info = {"mean_ms": mean_ms, "mean_acc": acc, "reward": r,
                    "loss": loss}
            return params, opt, buf, mets, counts2, scen2, info

        return step

    def _make_run(self, step):
        """n fleet steps (act + env + replay push + mini-batch update) in
        ONE jitted lax.scan call — no host sync inside the scan."""
        decay, eps_min = self.cfg.eps_decay, self.cfg.eps_min

        def run(params, opt, buf, mets, counts, scen, eps, key, n):
            def body(carry, _):
                params, opt, buf, mets, counts, scen, eps, key = carry
                key, k = jax.random.split(key)
                params, opt, buf, mets, counts, scen, info = step(
                    params, opt, buf, mets, counts, scen, eps, k)
                eps = jnp.maximum(eps_min, eps * (1.0 - decay))
                return (params, opt, buf, mets, counts, scen, eps, key), (
                    info["mean_ms"].mean(), info["mean_acc"].mean(),
                    info["loss"])
            carry, traces = jax.lax.scan(
                body, (params, opt, buf, mets, counts, scen, eps, key),
                None, length=n)
            return carry, traces

        return run

    # -------------------------------------------------------- public API --
    def step(self):
        """Advance every cell by one step + one pooled-replay update."""
        self.key, k = jax.random.split(self.key)
        (self.params, self.opt, self.buffer, self.metrics, self.counts,
         self.scen, info) = self._step(self.params, self.opt, self.buffer,
                                       self.metrics, self.counts,
                                       self.scen, self.eps, k)
        self.eps = max(self.cfg.eps_min,
                       self.eps * (1.0 - self.cfg.eps_decay))
        self.steps += 1
        return info

    def run(self, n: int):
        """Advance every cell by ``n`` steps inside one jitted scan.
        Returns per-step fleet-mean (ms, accuracy) traces of shape (n,)."""
        self.key, k = jax.random.split(self.key)
        carry, (ms, acc, _loss) = self._run(
            self.params, self.opt, self.buffer, self.metrics, self.counts,
            self.scen, self.eps, k, n)
        (self.params, self.opt, self.buffer, self.metrics, self.counts,
         self.scen, eps, _) = carry
        self.eps = float(eps)
        self.steps += n
        return np.asarray(ms), np.asarray(acc)

    def metrics_summary(self):
        """Host-side summary of the in-scan telemetry (``None`` when the
        agent was built with ``metrics=False``)."""
        return None if self.metrics is None else self.metrics.summary()

    def _check_width(self, scen: FleetScenario) -> None:
        """The feature layout (and the 'cell' net's input width) is tied
        to the trained padded width: a wider scen would silently misread
        every feature block, a narrower one crashes cryptically — catch
        both up front through the protocol-shared guard. Smaller CELLS
        are fine (the membership mask); only the padding width is
        pinned."""
        check_pad_width(self.spec.n_users, scen, "FleetDQN")

    def policy_decisions(self, counts, scen):
        """(cells, N) per-user decisions + (cells,) joint action ids from
        one vectorized greedy pass (the FleetOrchestrator entry point)."""
        self._check_width(scen)
        return self._greedy(self.params, counts, scen)

    def greedy_decisions(self, scen: Optional[FleetScenario] = None,
                         counts=None) -> jnp.ndarray:
        """(cells, N) per-user decisions at each cell's current state —
        or, given a (possibly held-out) ``scen``, cold-start decisions
        for cells the policy has never trained on."""
        if scen is None:
            scen = self.scen
            if counts is None:
                counts = self.counts
        self._check_width(scen)
        if counts is None:
            counts = jnp.zeros((scen.cells, 2), jnp.int32)
        return self._greedy(self.params, counts, scen)[0]

    def greedy_expected(self, scen: Optional[FleetScenario] = None,
                        counts=None):
        """Noise-free (mean ms, mean acc) of each cell's greedy decision;
        pass a held-out ``scen`` to score cross-cell generalization."""
        eval_scen = scen if scen is not None else self.scen
        per_user = self.greedy_decisions(scen=scen, counts=counts)
        ms, acc = nominal_expected_response(eval_scen, per_user)
        return np.asarray(ms), np.asarray(acc)

    # ------------------------------------------------ FleetPolicy protocol
    def decisions(self, counts, scen: FleetScenario):
        """``api.FleetPolicy`` surface (alias of ``policy_decisions``)."""
        return self.policy_decisions(counts, scen)

    def expected(self, scen: Optional[FleetScenario] = None, counts=None):
        """``api.FleetPolicy`` surface (alias of ``greedy_expected``)."""
        return self.greedy_expected(scen=scen, counts=counts)

    def train(self, max_steps: int, check_every: int = 200,
              tol: float = 0.01, patience: int = 3) -> FleetTrainResult:
        """Train the shared policy; per-cell convergence is scored
        against ``fleet_bruteforce`` over this agent's candidate set
        (see ``population.train_against_oracle``)."""
        return train_against_oracle(self, max_steps, check_every=check_every,
                                    tol=tol, patience=patience)
