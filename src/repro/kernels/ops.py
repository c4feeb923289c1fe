"""Jit'd public wrappers around the Pallas kernels.

Each op pads/flattens to the kernel's layout, runs the kernel (interpret
mode on CPU — the TPU target compiles the same pallas_call), and undoes
the layout. ``impl='ref'`` routes to the pure-jnp oracle instead, which
is also the path the SPMD dry-run lowers (see DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref, tabular_rl
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.dqn_head import block_cells, dqn_head_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.int8_matmul import int8_matmul_kernel
from repro.kernels.selective_scan import selective_scan_kernel

NEG_INF = -1e30


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "pallas", bq: int = 128, bk: int = 128,
                    interpret: bool = True):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) -> (B,Sq,H,hd)."""
    if impl == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    b, sq, h, hd = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    g = h // n_kv
    # layout: (B*H, S, hd); pad sq/skv to block multiples, hd to 128
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * n_kv, skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * n_kv, skv, hd)
    qf, _ = _pad_to(qf, 1, bq)
    kf, _ = _pad_to(kf, 1, bk)
    vf, _ = _pad_to(vf, 1, bk)
    qf, hd_pad = _pad_to(qf, 2, 128)
    kf, _ = _pad_to(kf, 2, 128)
    vf, _ = _pad_to(vf, 2, 128)
    import math
    o = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                               bq=bq, bk=bk, scale=1.0 / math.sqrt(hd),
                               seq_kv=skv, q_offset=skv - sq,
                               interpret=interpret)
    o = o[:, :sq, :hd].reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    return o


@functools.partial(jax.jit, static_argnames=("window", "impl", "bk",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, kv_pos, cur_pos, *, window: int = 0,
                     impl: str = "pallas", bk: int = 512,
                     interpret: bool = True):
    """q: (B,H,hd); caches: (B,S,KV,hd); kv_pos: (B,S) absolute slot
    positions (-1 empty); cur_pos: (B,)."""
    valid = (kv_pos >= 0) & (kv_pos <= cur_pos[:, None])
    if window:
        valid &= kv_pos > cur_pos[:, None] - window
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    if impl == "ref":
        return ref.decode_attention_ref(q, k_cache, v_cache, bias)
    b, h, hd = q.shape
    s = k_cache.shape[1]
    q_, hd_pad = _pad_to(q, 2, 128)
    k_, _ = _pad_to(k_cache, 3, 128)
    v_, _ = _pad_to(v_cache, 3, 128)
    k_, spad = _pad_to(k_, 1, bk)
    v_, _ = _pad_to(v_, 1, bk)
    bias_, _ = _pad_to(bias, 1, bk)
    if spad:
        bias_ = bias_.at[:, s:].set(NEG_INF)
    import math
    o = decode_attention_kernel(q_, k_, v_, bias_, bk=bk,
                                scale=1.0 / math.sqrt(hd), interpret=interpret)
    return o[:, :, :hd]


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int8_matmul(x_q, sx, w_q, sw, *, impl: str = "pallas", bm: int = 256,
                bn: int = 256, bk: int = 256, out_dtype=jnp.float32,
                interpret: bool = True):
    if impl == "ref":
        return ref.int8_matmul_ref(x_q, sx, w_q, sw).astype(out_dtype)
    m, k = x_q.shape
    n = w_q.shape[1]
    x_, _ = _pad_to(x_q, 0, bm)
    x_, _ = _pad_to(x_, 1, bk)
    w_, _ = _pad_to(w_q, 0, bk)
    w_, _ = _pad_to(w_, 1, bn)
    sx_, _ = _pad_to(sx, 0, bm)
    sw_, _ = _pad_to(sw, 1, bn)
    o = int8_matmul_kernel(x_, sx_, w_, sw_, bm=bm, bn=bn, bk=bk,
                           out_dtype=out_dtype, interpret=interpret)
    return o[:m, :n]


def quantize(x, axis=-1):
    return ref.quantize_ref(x, axis)


@functools.partial(jax.jit, static_argnames=("impl", "bd", "interpret"))
def selective_scan(u, dt, A, B, C, D, *, impl: str = "pallas", bd: int = 256,
                   interpret: bool = True):
    """See kernels/selective_scan.py; returns (y, h_last)."""
    if impl == "ref":
        return ref.selective_scan_ref(u, dt, A, B, C, D)
    di = u.shape[2]
    bd = min(bd, di)
    pad = (-di) % bd
    u_, _ = _pad_to(u, 2, bd)
    dt_, _ = _pad_to(dt, 2, bd)
    A_ = jnp.pad(A, ((0, pad), (0, 0)))
    D_ = jnp.pad(D, (0, pad))
    y, h = selective_scan_kernel(u_, dt_, A_, B, C, D_, bd=bd,
                                 interpret=interpret)
    return y[:, :, :di], h[:, :di]


def resolve_rl_impl(impl: str, mesh=None, per_shard: bool = False) -> str:
    """Resolve a fleet agent's ``impl`` request to an executable path.

    ``"pallas"`` resolves by capability: the compiled kernel needs a TPU
    backend and, since GSPMD cannot partition a ``pallas_call``, under a
    device mesh a caller that runs it once per device on its block of
    cells (``per_shard``: ``FleetQLearning``, whose Q-table is split
    along its cells). Elsewhere the fused-jnp reference formulation
    runs (``"ref"``): per-cell elementwise + batched gather/scatter +
    reduces along the unsharded action axis, bit-identical sharded and
    single-device (``tests/test_fleet_shard.py``), and off a TPU the
    fused win itself. ``"pallas_interpret"`` forces the real kernel in
    interpret mode (CPU parity runs; far too slow for training)."""
    if impl in ("ref", "pallas_interpret"):
        return impl
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas', "
                         "'ref', or 'pallas_interpret'")
    if mesh is not None and not per_shard:
        return "ref"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "ref"


def per_block(fn, mesh: Optional[Mesh]):
    """``fn`` run by each device on its own block of cells, every
    argument and result split along its first axis (``shard_map``,
    unchecked: ``fn`` may call a ``pallas_call``); ``fn`` itself when
    ``mesh`` is None."""
    if mesh is None:
        return fn
    cells = P(mesh.axis_names)
    return jax.shard_map(fn, mesh=mesh, in_specs=cells, out_specs=cells,
                         check_vma=False)


@dataclasses.dataclass(frozen=True)
class TabularPath:
    """Where a fleet's ``(cells, n_states, n_actions)`` Q-table is
    updated, and its layout meanwhile. ``impl``: ``"ref"`` (the fused-jnp
    formulation on the logical table), or the ``tabular_rl`` kernel,
    compiled (``"pallas"``) or not (``"pallas_interpret"``), on the
    table in its own layout; ``mesh``: the kernel and the layout changes
    run once per device, on its block of cells. A caller brings the
    table in once (``enter``), reads rows (``rows``) and updates it
    (``update``) as often as it likes, and takes it out (``leave``)."""
    impl: str
    n_states: int
    n_actions: int
    mesh: Optional[Mesh] = None

    @property
    def kernel(self) -> bool:
        return self.impl != "ref"

    @property
    def interpret(self) -> bool:
        return self.impl == "pallas_interpret"

    @property
    def name(self) -> str:
        return self.impl + ("_per_shard" if self.mesh is not None else "")

    def enter(self, q):
        """The logical table in the layout ``update`` takes."""
        if not self.kernel:
            return q
        return per_block(tabular_rl.align_table, self.mesh)(q)

    def rows(self, q, s):
        """``(cells, n_actions)``: row ``s[c]`` of each cell's table."""
        if not self.kernel:
            return q[jnp.arange(q.shape[0]), s]
        return per_block(functools.partial(
            tabular_rl.gather_rows, n_actions=self.n_actions),
            self.mesh)(q, s)

    def leave(self, q):
        """The logical table (``enter``'s inverse)."""
        if not self.kernel:
            return q
        return per_block(functools.partial(
            tabular_rl.unalign_table, n_states=self.n_states,
            n_actions=self.n_actions), self.mesh)(q)

    def update(self, q, s, a, r, s2, *, alpha: float, gamma: float):
        """``fused_tabular_update`` in this layout, looked up when
        traced: a replacement of ``ops.fused_tabular_update`` counts."""
        kw = (dict(impl="pallas", interpret=self.interpret,
                   n_actions=self.n_actions, mesh=self.mesh)
              if self.kernel else dict(impl="ref"))
        return fused_tabular_update(q, s, a, r, s2, alpha=alpha,
                                    gamma=gamma, **kw)


def tabular_path(impl: str, n_states: int, n_actions: int, mesh=None,
                 per_shard: bool = False) -> TabularPath:
    """The ``TabularPath`` ``impl`` resolves to (``resolve_rl_impl``);
    ``per_shard``: ``mesh`` splits the table along its cells."""
    resolved = resolve_rl_impl(impl, mesh, per_shard=per_shard)
    block_mesh = mesh if per_shard and resolved != "ref" else None
    return TabularPath(resolved, n_states, n_actions, block_mesh)


def dqn_head_parts(resolved: str, threshold: float) -> dict:
    """Where each part of the fused DQN head runs for a
    ``resolve_rl_impl`` result: on the kernel path the featurize + MLP +
    mask + top-k part is the Pallas kernel and, with a QoS goal, the
    ``topk^N`` combination scoring (``ref.combo_pick``) is XLA."""
    kernel = resolved in ("pallas", "pallas_interpret")
    parts = {"head": resolved}
    if threshold:
        parts["combo_pick"] = "xla" if kernel else resolved
    return parts


@functools.partial(jax.jit, static_argnames=("alpha", "gamma", "impl",
                                             "n_actions", "bc", "interpret",
                                             "mesh"))
def fused_tabular_update(q, s, a, r, s2, *, alpha: float, gamma: float,
                         impl: str = "ref", n_actions: Optional[int] = None,
                         bc: Optional[int] = None, interpret: bool = True,
                         mesh=None):
    """Fused tabular act+update: q (cells,S,K) f32, s/a/s2 (cells,)
    int32, r (cells,) f32 -> (q_new, greedy2, td); see
    ``ref.fused_tabular_ref``.

    The kernel path takes the table in its own layout
    (``tabular_rl.align_table``). A caller that keeps it so across many
    steps, as the fleet scan does, passes that table with
    ``n_actions``, its logical width, and gets ``q_new`` back in that
    layout; a logical table (``n_actions`` None) is aligned for the call
    and given back logical. ``bc`` defaults to the largest block that
    fits the kernel's VMEM budget (``tabular_rl.block_cells``).

    ``mesh``: every argument and result is split along its cells over
    the mesh, and each device runs the update on its own block of cells
    (``shard_map``), since GSPMD cannot partition a ``pallas_call``.
    Each block is padded to ``bc`` on its own."""
    if mesh is not None:
        return per_block(functools.partial(
            fused_tabular_update, alpha=alpha, gamma=gamma, impl=impl,
            n_actions=n_actions, bc=bc, interpret=interpret), mesh)(
                q, s, a, r, s2)
    if impl == "ref":
        if n_actions is not None:
            raise ValueError("the ref path takes a logical table")
        return ref.fused_tabular_ref(q, s, a, r, s2, alpha=alpha,
                                     gamma=gamma)
    cells, n_states, width = q.shape
    logical = n_actions is None
    n_actions = width if logical else n_actions
    if bc is None:
        bc = tabular_rl.block_cells(n_actions)
    cols = [_pad_to(x, 0, bc)[0].reshape(-1, 1, bc) for x in (s, a, r, s2)]
    q_new, greedy2, td = tabular_rl.tabular_rl_kernel(
        tabular_rl.align_table(q) if logical else q, *cols, alpha=alpha,
        gamma=gamma, n_actions=n_actions, bc=bc, interpret=interpret)
    if logical:
        q_new = tabular_rl.unalign_table(q_new, n_states, width)
    return q_new, greedy2.reshape(-1)[:cells], td.reshape(-1)[:cells]


@functools.partial(jax.jit, static_argnames=("threshold", "topk", "impl",
                                             "bc", "interpret"))
def dqn_head(active, member, end_b, agg, params, allowed, acc_table, *,
             threshold: float, topk: int, impl: str = "ref",
             bc: Optional[int] = None, interpret: bool = True):
    """Fused featurize + constraint-aware greedy head.

    active/member/end_b: (cells, N) f32; agg: (cells, 8) f32 cell
    aggregates; params: the 3-layer shared-net param list
    (``[{"w", "b"}] * 3``); allowed: (N, A) bool allowed-action mask;
    acc_table: (A,) f32 accuracy ladder. Returns ``(dec, q)``; see
    ``ref.dqn_head_ref``. On the kernel path the head and top-k run in
    the Pallas kernel and, with a QoS ``threshold``, the combination
    scoring in XLA (``dqn_head_parts``); ``bc`` defaults to the largest
    block that fits the kernel's VMEM budget (``block_cells``).
    """
    (w1, b1), (w2, b2), (w3, b3) = [(p["w"], p["b"].reshape(1, -1))
                                    for p in params]
    allowed_f = jnp.asarray(allowed).astype(jnp.float32)
    if impl == "ref":
        return ref.dqn_head_ref(active, member, end_b, agg, w1, b1, w2,
                                b2, w3, b3, allowed_f, acc_table,
                                threshold=threshold, topk=topk)
    cells, users = active.shape
    k = topk if threshold else 0          # top-k planes only for the goal
    if bc is None:
        bc = block_cells(users, w1.shape[1], w3.shape[1], k)
    padded = [_pad_to(x, 0, bc)[0] for x in (active, member, end_b, agg)]
    outs = dqn_head_kernel(*padded, w1, b1, w2, b2, w3, b3, allowed_f,
                           topk=k, bc=bc, interpret=interpret)
    plain, q = outs[0][:cells], outs[1][:cells]
    if not threshold:
        return plain, q
    vals, idx = outs[2][:cells], outs[3][:cells]
    return ref.combo_pick(vals, idx, member, plain, acc_table,
                          threshold=threshold), q
