"""Jit'd public wrappers around the Pallas kernels.

Each op pads/flattens to the kernel's layout, runs the kernel (interpret
mode on CPU — the TPU target compiles the same pallas_call), and undoes
the layout. ``impl='ref'`` routes to the pure-jnp oracle instead, which
is also the path the SPMD dry-run lowers (see DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.dqn_head import block_cells, dqn_head_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.int8_matmul import int8_matmul_kernel
from repro.kernels.selective_scan import selective_scan_kernel
from repro.kernels.tabular_rl import tabular_rl_kernel

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


def resolve_rl_impl(impl: str, mesh=None) -> str:
    """Resolve a fleet agent's ``impl`` request to an executable path.

    ``"xla"`` is the legacy unfused step, untouched. ``"pallas"`` is
    the fused hot path and resolves by capability: the compiled kernel
    needs a TPU backend, and ``pallas_call`` cannot be partitioned by
    GSPMD, so under a device mesh (``fleet.shard``) the fused-jnp
    reference formulation runs instead — it is per-cell elementwise +
    batched gather/scatter + reduces along the unsharded action axis,
    so it stays bit-identical sharded-vs-single-device (the discipline
    ``tests/test_fleet_shard.py`` pins). On non-TPU hosts the same
    reference formulation IS the fused win: one row-gather shared by
    act and update, and the two-reduce ``first_argmax_ref``.
    ``"pallas_interpret"`` forces the real kernel in interpret mode
    (CPU CI parity runs; far too slow for production loops).
    """
    if impl in ("xla", "ref", "pallas_interpret"):
        return impl
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas', "
                         "'xla', 'ref', or 'pallas_interpret'")
    if mesh is not None:
        return "ref"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "ref"


def rl_op_kwargs(resolved: str) -> dict:
    """kwargs for the fused ops matching a ``resolve_rl_impl`` result."""
    if resolved == "ref":
        return {"impl": "ref"}
    if resolved == "pallas":
        return {"impl": "pallas", "interpret": False}
    if resolved == "pallas_interpret":
        return {"impl": "pallas", "interpret": True}
    raise ValueError(f"no fused op path for resolved impl {resolved!r}")


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
                                             "bc", "interpret"))
def fused_tabular_update(q, s, a, r, s2, *, alpha: float, gamma: float,
                         impl: str = "ref", bc: int = 8,
                         interpret: bool = True):
    """Fused tabular act+update: q (cells,S,K) f32, s/a/s2 (cells,)
    int32, r (cells,) f32 -> (q_new, greedy2, td); see
    ``ref.fused_tabular_ref``."""
    if impl == "ref":
        return ref.fused_tabular_ref(q, s, a, r, s2, alpha=alpha,
                                     gamma=gamma)
    cells = q.shape[0]
    q_, _ = _pad_to(q, 0, bc)
    cols = [_pad_to(x, 0, bc)[0] for x in (s, a, r, s2)]
    q_new, greedy2, td = tabular_rl_kernel(
        q_, *cols, alpha=alpha, gamma=gamma, bc=bc, interpret=interpret)
    return q_new[:cells], greedy2[:cells, 0], td[:cells, 0]


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
