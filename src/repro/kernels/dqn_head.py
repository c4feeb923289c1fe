"""Fused featurize + masked Q head + per-user top-k Pallas TPU kernel.

``FleetDQN``'s act path materializes the flat ``encode_fleet_state``
vector, re-slices it back into per-user rows, runs the shared MLP, and
— with a QoS goal — takes each user's top-k actions before scoring
the ``topk^N`` combinations against the accuracy ladder. This kernel
fuses the per-user part per fleet block: each grid program assembles
the ``(BC * N, 11)`` per-user feature matrix directly from the
``active``/``member``/``end_b`` blocks plus the 8-wide cell aggregates,
keeps the three MLP weight matrices resident in VMEM across the block,
masks with the allowed-action table, and emits the plain per-user
greedy decisions, the masked head values and — with a QoS goal — the
top-k values and ids as ``k`` (max, first-argmax, mask) reduce pairs.

The combination scoring (``ref.combo_pick``: ``(cells, topk^N)``
candidates, 3,125 at 5 users and ``topk=5``) runs outside the kernel
in XLA. Unrolled in the kernel it needs more scoped VMEM than a v5e
core has at a useful block size and does not compile in bounded time.
``kernels.ops.dqn_head_parts`` names the split, and the agents report
it next to the resolved impl.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
#: the head's matmuls run at full f32 precision on both the kernel and
#: the oracle (XLA's TPU default for f32 is one bf16 pass, Mosaic's is
#: not), so decisions agree across the two paths on the chip
HIGHEST = jax.lax.Precision.HIGHEST

#: scoped-VMEM budget the block size is chosen against: v5e's 16 MiB
#: default scoped limit, less headroom for Mosaic's own scratch
VMEM_BUDGET = 12 * 2 ** 20


def _first_argmax(x, iota):
    """First-index argmax over the last axis (jnp.argmax tie-break)."""
    m = jnp.max(x, axis=-1, keepdims=True)
    k = x.shape[-1]
    return jnp.min(jnp.where(x == m, iota, k), axis=-1).astype(jnp.int32)


def block_cells(users: int, hidden: int, n_act: int, topk: int,
                budget: int = VMEM_BUDGET) -> int:
    """Largest fleet block (a multiple of 8, at most 512 cells) whose
    kernel working set fits ``budget`` bytes of scoped VMEM.

    A conservative count per cell, in f32 (8, 128)-tiled layout: the
    ``(N, 11)`` feature rows and two ``(N, hidden)`` activations as
    ``N``-row slabs; the ``(N, A)`` head values, the top-k working copy
    and ``2 * topk`` top-k planes as ``(N -> 8, A -> 128)`` tiles; and
    every in/out block as such tiles, double-buffered. The resident MLP
    weights come off the budget first."""
    lanes = lambda n: -(-n // 128) * 128
    rows = -(-users // 8) * 8
    f32 = 4
    per_cell = f32 * (users * (lanes(11) + 2 * lanes(hidden))
                      + rows * lanes(n_act) * (4 + 2 * topk)
                      + 2 * 2 * (4 * rows * lanes(1)
                                 + rows * lanes(n_act)
                                 + 2 * rows * lanes(topk)))
    weights = 2 * f32 * (lanes(hidden) * (16 + hidden) + lanes(n_act)
                         * (hidden + 8) + 2 * 8 * lanes(hidden))
    bc = (budget - weights) // per_cell
    return int(max(8, min(512, bc // 8 * 8)))


def _kernel(act_ref, mem_ref, end_ref, agg_ref, w1_ref, b1_ref, w2_ref,
            b2_ref, w3_ref, b3_ref, mask_ref, dec_ref, q_ref, *topk_refs,
            bc: int, users: int, topk: int):
    n = users
    act = act_ref[...]                                    # (BC, N)
    agg = agg_ref[...]                                    # (BC, 8)
    feats = jnp.concatenate(
        [act[..., None], mem_ref[...][..., None], end_ref[...][..., None],
         jnp.broadcast_to(agg[:, None, :], (bc, n, agg.shape[-1]))], -1)
    x = feats.reshape(bc * n, feats.shape[-1])
    dot = functools.partial(jnp.dot, precision=HIGHEST)
    h = jnp.maximum(dot(x, w1_ref[...]) + b1_ref[...], 0.0)
    h = jnp.maximum(dot(h, w2_ref[...]) + b2_ref[...], 0.0)
    q = dot(h, w3_ref[...]) + b3_ref[...]
    n_act = q.shape[-1]
    q = jnp.where(mask_ref[...][None] > 0.5, q.reshape(bc, n, n_act),
                  NEG_INF)                                # (BC, N, A)
    q_ref[...] = q
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_act), 2)
    dec_ref[...] = _first_argmax(q, iota_a)               # (BC, N)
    if not topk_refs:
        return
    # stable top-k: k rounds of (max, first-argmax, mask-out); an
    # exhausted row re-yields NEG_INF, culled by ref.combo_pick
    vals_ref, idx_ref = topk_refs
    vals, idx, cur = [], [], q
    for _ in range(topk):
        i = _first_argmax(cur, iota_a)
        hit = iota_a == i[..., None]
        vals.append(jnp.sum(jnp.where(hit, cur, 0.0), -1)
                    + jnp.where(jnp.all(~hit, -1), NEG_INF, 0.0))
        idx.append(i)
        cur = jnp.where(hit, NEG_INF, cur)
    vals_ref[...] = jnp.stack(vals, -1)                   # (BC, N, k)
    idx_ref[...] = jnp.stack(idx, -1)


def dqn_head_kernel(active, member, end_b, agg, w1, b1, w2, b2, w3, b3,
                    allowed, *, topk: int, bc: int, interpret: bool = True):
    """active/member/end_b: (cells, N) f32, cells a multiple of ``bc``;
    agg: (cells, 8) f32; w*/b*: the 3-layer shared MLP (biases shaped
    (1, width)); allowed: (N, A) f32 0/1 mask. ``topk=0`` skips the
    top-k planes. Returns ``(plain, q)`` — (cells, N) int32 per-user
    argmax and (cells, N, A) f32 masked values — plus, for ``topk > 0``,
    ``(vals, idx)``: (cells, N, topk) f32 / int32, descending values
    with ties by ascending id (the ``jax.lax.top_k`` order)."""
    cells, users = active.shape
    n_act = w3.shape[1]
    kernel = functools.partial(_kernel, bc=bc, users=users, topk=topk)
    user_spec = pl.BlockSpec((bc, users), lambda i: (i, 0))
    full = [pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)
            for arr in (w1, b1, w2, b2, w3, b3, allowed)]
    per_user = lambda w: pl.BlockSpec((bc, users, w), lambda i: (i, 0, 0))
    out_specs = [user_spec, per_user(n_act)]
    out_shape = [jax.ShapeDtypeStruct((cells, users), jnp.int32),
                 jax.ShapeDtypeStruct((cells, users, n_act), jnp.float32)]
    if topk:
        out_specs += [per_user(topk), per_user(topk)]
        out_shape += [jax.ShapeDtypeStruct((cells, users, topk), jnp.float32),
                      jax.ShapeDtypeStruct((cells, users, topk), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid=(cells // bc,),
        in_specs=[user_spec, user_spec, user_spec,
                  pl.BlockSpec((bc, agg.shape[1]), lambda i: (i, 0)),
                  *full],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(active, member, end_b, agg, w1, b1, w2, b2, w3, b3, allowed)
