"""Fused tabular-RL act+update Pallas TPU kernel.

``FleetQLearning``'s per-cell hot path is three HLOs round-tripping the
same two Q-table rows through HBM: gather ``q[c, s2]`` for the TD max,
gather/scatter ``q[c, s, a]`` for the update, then — on the NEXT step —
gather ``q[c, s2]`` again for the greedy argmax (``s2`` is exactly the
next step's state index). This kernel fuses the act+update pair:
blocking over the fleet axis, each grid program stages a ``(BC, S, K)``
slab of the Q-table into VMEM, and for every cell in the block reads
row ``s`` and row ``s2`` ONCE, computes the TD error, writes the
updated ``(s, a)`` entry in place (``input_output_aliases`` keeps the
table buffer donated), and emits the next step's greedy action from
the post-update ``s2`` row — so the scan carries ``greedy`` instead of
re-gathering the row, and Q-table rows never leave VMEM between the
act and the update that consumed them.

Layout for Mosaic: the per-cell indices and rewards (``s/a/r/s2``) are
SMEM blocks, read as scalars to address the rows; ``greedy2``/``td``
are ``(BC, 1)`` VMEM vectors carried through the cell loop and stored
once per block (Mosaic refuses scalar stores to VMEM).

Argmax is the first-index tie-break of ``jnp.argmax``, computed as a
(max, masked index-min) reduce pair — the same trick
``ref.first_argmax_ref`` uses, vectorized on the VPU lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, a_ref, r_ref, s2_ref, q_ref, q_out_ref, g_ref, td_ref,
            *, bc: int, alpha: float, gamma: float, n_actions: int):
    # s/a/r/s2: (1, BC) SMEM; q: (BC, S, K) VMEM; g/td: (BC, 1) VMEM
    q_out_ref[...] = q_ref[...]    # the out block is its own VMEM buffer
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_actions), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bc, 1), 0)

    def cell(c, carry):
        g_vec, td_vec = carry
        s_c, a_c = s_ref[0, c], a_ref[0, c]
        s2_c, r_c = s2_ref[0, c], r_ref[0, c]
        row_s = q_ref[c, pl.ds(s_c, 1), :]                     # (1, K)
        row_2 = q_ref[c, pl.ds(s2_c, 1), :]                    # (1, K)
        onehot = iota == a_c
        q_sa = jnp.sum(jnp.where(onehot, row_s, 0.0), -1, keepdims=True)
        td = r_c + gamma * jnp.max(row_2, -1, keepdims=True) - q_sa
        row_s_new = row_s + jnp.where(onehot, alpha * td, 0.0)
        q_out_ref[c, pl.ds(s_c, 1), :] = row_s_new
        # next step's greedy on the POST-update s2 row (when s2 == s the
        # freshly written entry participates)
        row_2_new = jnp.where(s2_c == s_c, row_s_new, row_2)
        m2 = jnp.max(row_2_new, -1, keepdims=True)
        g = jnp.min(jnp.where(row_2_new == m2, iota, n_actions), -1,
                    keepdims=True)                             # (1, 1)
        hit = rows == c
        return jnp.where(hit, g, g_vec), jnp.where(hit, td, td_vec)

    g_vec, td_vec = jax.lax.fori_loop(
        0, bc, cell, (jnp.zeros((bc, 1), jnp.int32),
                      jnp.zeros((bc, 1), jnp.float32)))
    g_ref[...] = g_vec
    td_ref[...] = td_vec


def tabular_rl_kernel(q, s, a, r, s2, *, alpha: float, gamma: float,
                      bc: int = 8, interpret: bool = True):
    """q: (cells, S, K) f32; s/a/s2: (cells,) int32, r: (cells,) f32,
    cells a multiple of ``bc``. Returns ``(q_new, greedy2, td)`` with
    greedy2/td shaped (cells, 1); semantics of ``ref.fused_tabular_ref``."""
    cells, n_states, n_actions = q.shape
    kernel = functools.partial(_kernel, bc=bc, alpha=alpha, gamma=gamma,
                               n_actions=n_actions)
    smem = pl.BlockSpec((None, 1, bc), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    vec = pl.BlockSpec((bc, 1), lambda i: (i, 0))
    q_spec = pl.BlockSpec((bc, n_states, n_actions), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(cells // bc,),
        in_specs=[smem, smem, smem, smem, q_spec],
        out_specs=[q_spec, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((cells, 1), jnp.int32),
            jax.ShapeDtypeStruct((cells, 1), jnp.float32),
        ],
        input_output_aliases={4: 0},     # update the Q slab in place
        interpret=interpret,
    )(*(x.reshape(cells // bc, 1, bc) for x in (s, a, r, s2)), q)
