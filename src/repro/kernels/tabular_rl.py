"""Fused tabular-RL act+update Pallas TPU kernel: a tile-row gather.

A fleet step of ``FleetQLearning`` needs two rows of each cell's
Q-table: row ``s2`` for the TD max and for the next step's greedy
action, and entry ``(s, a)`` for the update. The table stays in HBM (a
``memory_space=pl.ANY`` operand, aliased input -> output, so it is
updated in place), and the kernel moves only the tiles those rows live
in.

**Alignment contract.** On the chip an f32 array is tiled ``(8, 128)``
over its two minor dims, and a DMA moves whole tiles: neither one row
of a cell's table nor an 8-row slice of a 243-wide row can be copied.
So the kernel takes the table in its own layout (``align_table``):
rows padded to a multiple of 8 and actions to ``T`` lane tiles of 128,
and each aligned 8-row *group* of a cell's table stored as one
contiguous ``(8 T, 128)`` run of whole tiles, lane tile ``t`` of row ``8
g + j`` at row ``8 T g + 8 t + j``. That is the byte order of a ``(S8,
128 T)`` table tiled ``(8, 128)``, with a minor dim of 128 so that the
kernel's strided loads can address it. ``n_actions`` is the logical
``K``: the padded lanes never enter a max or an argmax, and the padded
rows and lanes are written back as they were read. A caller that keeps
the table aligned across many steps (the fleet scan) pays for the
layout change once, ``align_table`` before and ``unalign_table`` after,
a piece of cells at a time so that the change holds no second table.

**Per block of ``bc`` cells**, the group holding row ``s`` and the group
holding row ``s2`` of each cell are DMA'd into two ``(bc * 8 T, 128)``
VMEM buffers, 8 KiB a group at ``K = 243``. The math runs over chunks
of ``tc`` cells, cells on sublanes and actions on lanes: a strided load
takes lane tile ``t`` of row ``j`` of every group in the chunk, and a
select on ``s % 8`` (``s2 % 8``) keeps each cell's row. The TD error,
the new entry and the first-index argmax of the post-update ``s2`` row
(when ``s2 == s`` the fresh entry takes part) are then a few lane
reductions over the chunk at once, the semantics of
``ref.fused_tabular_ref``. The new entry goes into the ``s`` buffer by
masked strided stores, and each cell's ``s`` group goes back with one
DMA: 3 x 8 KiB a cell, where a stream of whole tables moves 2 x 40 KiB.

**Double buffering, copies started in straight-line code.** Both
buffers have two slots. Grid step ``i`` waits for the write-backs of
block ``i - 1`` (their slot is the one block ``i + 1`` reads into) and
for its own reads, each with
one wait the size of the whole slot. Then, chunk by chunk, it starts the
next block's reads of the chunk's cells, does the chunk's math and
starts its write-backs. That code is unrolled: on a v5e the kernel is
bound by the scalar work of starting some 400,000 copies a step, and
only in one basic block does that work share its bundles with the
vector math (a loop over the same code ran 40% slower). The last step
re-reads its own block into the idle slot instead of branching, and
waits for everything it started. The grid axis runs in order
(``arbitrary``). Only the steps that touch a part-empty last block
(``cells`` not a multiple of ``bc``) run a guarded loop instead, which
starts and waits for the copies of real cells only; the index vectors
are padded to whole blocks, the table is not.

**Indices.** DMA addresses need ``s`` and ``s2`` as scalars, but
prefetching the whole ``(cells,)`` vectors as scalars overflows SMEM at
a fleet's size (1 MiB at 131,072 cells). So they come in per-block
``(1, bc)`` SMEM blocks, twice: this block's, and the next block's,
whose reads this step starts. The vector math takes ``s``, ``a``,
``r`` and ``s2`` as lane-dense ``(1, bc)`` VMEM blocks, turned into
``(tc, 1)`` columns by a small transpose; the greedy action and the TD
error leave the same way, so no ``(cells, 1)`` array, 512 B a cell once
tiled, is written to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a group: the sublane tile of an f32 table
GROUP = 8
#: lanes of the f32 tile the action axis is padded to
LANES = 128
#: bytes of table a piece of cells when a whole table changes layout
PIECE_BYTES = 64 * 2 ** 20
#: scoped-VMEM budget the block size is chosen against: v5e's 16 MiB
#: default scoped limit, less headroom for Mosaic's own scratch
VMEM_BUDGET = 12 * 2 ** 20


def lane_tiles(n_actions: int) -> int:
    """128-lane tiles of one padded row."""
    return -(-n_actions // LANES)


def _align(q):
    cells, n_states, n_actions = q.shape
    groups, tiles = -(-n_states // GROUP), lane_tiles(n_actions)
    q = jnp.pad(q, ((0, 0), (0, groups * GROUP - n_states),
                    (0, tiles * LANES - n_actions)))
    q = q.reshape(cells, groups, GROUP, tiles, LANES).transpose(0, 1, 3, 2, 4)
    return q.reshape(cells, groups * tiles * GROUP, LANES)


def _unalign(q, n_states: int, n_actions: int):
    cells, tiles = q.shape[0], lane_tiles(n_actions)
    groups = q.shape[1] // (tiles * GROUP)
    q = q.reshape(cells, groups, tiles, GROUP, LANES).transpose(0, 1, 3, 2, 4)
    q = q.reshape(cells, groups * GROUP, tiles * LANES)
    return q[:, :n_states, :n_actions]


def _by_pieces(fn, q, out):
    """``out`` with ``fn`` of ``q`` written into it, a piece of cells at a
    time: XLA pads and changes layout in separate passes, so one pass
    over a whole fleet's table would hold two more tables at once."""
    cells = q.shape[0]
    piece = max(1, min(cells, PIECE_BYTES // (q[0].size * q.dtype.itemsize)))
    if piece == cells:
        return fn(q)

    def body(k, out):
        # the last piece is clamped to end at the last cell: it rewrites
        # some cells of the one before with the same values
        start = jnp.minimum(k * piece, cells - piece)
        block = jax.lax.dynamic_slice_in_dim(q, start, piece, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, fn(block), start, 0)

    return jax.lax.fori_loop(0, -(-cells // piece), body, out)


def align_table(q):
    """The kernel's layout of a logical ``(cells, S, K)`` table:
    ``(cells, ceil8(S) * T, 128)`` with ``T = lane_tiles(K)``, zero
    padded. Rows ``8 g .. 8 g + 7`` of the logical table (group ``g``)
    take rows ``8 T g .. 8 T (g + 1) - 1``, lane tile ``t`` of row ``8 g
    + j`` at ``8 T g + 8 t + j``: one group is one contiguous run of
    whole tiles, the layout of ``(cells, ceil8(S), 128 T)`` tiled
    ``(8, 128)`` with the tiles made rows."""
    cells, n_states, n_actions = q.shape
    rows = -(-n_states // GROUP) * GROUP * lane_tiles(n_actions)
    return _by_pieces(_align, q, jnp.zeros((cells, rows, LANES), q.dtype))


def unalign_table(q, n_states: int, n_actions: int):
    """The logical ``(cells, n_states, n_actions)`` table of one in the
    kernel's layout (``align_table``'s inverse)."""
    return _by_pieces(functools.partial(_unalign, n_states=n_states,
                                        n_actions=n_actions), q,
                      jnp.zeros((q.shape[0], n_states, n_actions), q.dtype))


def gather_rows(q, s, n_actions: int):
    """``(cells, n_actions)``: row ``s[c]`` of each cell's table, read
    from the kernel's layout (``align_table``)."""
    cells = jnp.arange(q.shape[0])
    first = s // GROUP * GROUP * lane_tiles(n_actions) + s % GROUP
    return jnp.concatenate([q[cells, first + GROUP * u]
                            for u in range(lane_tiles(n_actions))],
                           1)[:, :n_actions]


def block_cells(n_actions: int, budget: int = VMEM_BUDGET) -> int:
    """Largest fleet block whose VMEM fits ``budget`` bytes of scoped
    VMEM: two groups (rows ``s`` and ``s2``) x two slots x ``bc`` cells
    x one ``(8, 128 T)`` f32 group, and six ``(bc, 1)`` index and output
    columns (128 lanes each once tiled). A multiple of 128 cells, the
    kernel's column transposes, when one fits, else of 8; at most 512.
    Nothing here grows with the number of states."""
    per_cell = 4 * (2 * 2 * GROUP * lane_tiles(n_actions) * LANES
                    + 6 * LANES)
    fit = min(512, budget // per_cell)
    return int(fit // LANES * LANES if fit >= LANES else max(GROUP,
                                                              fit // 8 * 8))


def _column(row, tc: int):
    """(1, tc) lane vector -> (tc, 1) sublane column (exact: a transpose)."""
    return jnp.transpose(jnp.broadcast_to(row, (GROUP, tc)))[:, :1]


def _row(col, tc: int):
    """(tc, 1) sublane column -> (1, tc) lane vector."""
    return jnp.transpose(jnp.broadcast_to(col, (tc, GROUP)))[:1, :]


def _kernel(s_sm, s2_sm, sn_sm, s2n_sm, s_v, a_v, r_v, s2_v, q_hbm, q_out,
            g_ref, td_ref, s_buf0, s_buf1, s2_buf0, s2_buf1, idx, rew, g_col,
            td_col, sem, *, bc: int, tc: int, cells: int, blocks: int,
            n_actions: int, alpha: float, gamma: float):
    # s/s2 (this block), sn/s2n (the next): (1, bc) SMEM; s/a/r/s2_v:
    # (1, bc) VMEM; q_hbm is q_out (aliased, HBM); g/td: (1, bc) VMEM;
    # s_buf0/1, s2_buf0/1: (bc * 8 T, 128) VMEM, slots 0 and 1 of the
    # groups holding rows s and s2; idx (3, bc, 1) (s, a, s2), rew,
    # g_col, td_col (bc, 1): columns; sem: DMA (3 kinds: s reads, s2
    # reads, writes; 2 slots)
    i, n = pl.program_id(0), pl.num_programs(0)
    s_bufs, s2_bufs = (s_buf0, s_buf1), (s2_buf0, s2_buf1)
    tiles = lane_tiles(n_actions)
    span = GROUP * tiles           # rows of one group in the kernel layout
    ragged = cells % bc != 0       # then the last block is part empty
    # the block whose reads this step starts; the last step re-reads its
    # own block into the idle slot, so that no branch sits among the
    # copies of the straight-line code
    nxt = jnp.minimum(i + 1, n - 1)
    first, first_nxt = i * bc, nxt * bc      # their first cells

    def group(c):                  # VMEM rows of cell c's group
        start = c * span
        return pl.ds(start if isinstance(c, int) else
                     pl.multiple_of(start, GROUP), span)

    def row0(idx_ref, c):          # table rows of the group holding a row
        # lax, not jnp: the copies are unrolled by the hundred, and each
        # jnp operator costs a trace of its own when the kernel is built
        start = jax.lax.mul(jax.lax.div(idx_ref[0, c], GROUP), span)
        return pl.ds(pl.multiple_of(start, GROUP), span)

    def copy(cell0, c, fn, checked):
        """``fn(cell)`` for cell ``c`` of the block from cell ``cell0``,
        only if it is a real cell when ``checked``."""
        cell = jax.lax.add(cell0, c)
        if checked:
            pl.when(cell < cells)(lambda: fn(cell))
        else:
            fn(cell)

    def read(cell0, c, s_ref, s2_ref, to, checked):
        def start(cell):
            pltpu.make_async_copy(q_hbm.at[cell, row0(s_ref, c)],
                                  s_bufs[to].at[group(c)],
                                  sem.at[0, to]).start()
            pltpu.make_async_copy(q_hbm.at[cell, row0(s2_ref, c)],
                                  s2_bufs[to].at[group(c)],
                                  sem.at[1, to]).start()
        copy(cell0, c, start, checked)

    def wait(bufs, kind, at, blk):
        """The copies of kind ``kind`` into or out of slot ``at`` of
        ``bufs`` for block ``blk``: one wait the size of all ``bc``
        copies, or one a real cell in a part-empty block."""
        buf = bufs[at]

        def whole():
            pltpu.make_async_copy(buf, buf, sem.at[kind, at]).wait()

        def each():
            def one(c, carry):
                pltpu.make_async_copy(buf.at[group(0)], buf.at[group(0)],
                                      sem.at[kind, at]).wait()
                return carry
            jax.lax.fori_loop(0, cells - blk * bc, one, 0)

        if not ragged:
            whole()
            return
        pl.when(blk + 1 < n)(whole)
        pl.when(blk + 1 == n)(each)

    def update(s_buf, s2_buf, base, size):
        """The math of cells ``base .. base + size - 1`` of this block,
        cells on sublanes and actions on lanes, ``size`` a multiple of 8:
        pick each cell's rows ``s`` and ``s2`` out of their groups, then
        the TD error, the new entry, written into the ``s`` group, and
        the greedy action of the post-update ``s2`` row."""
        s, a, s2 = (idx[j, pl.ds(base, size), :] for j in range(3))
        r = rew[pl.ds(base, size), :]
        sub_s, sub_2 = s % GROUP, s2 % GROUP
        lane = [jax.lax.broadcasted_iota(jnp.int32, (size, LANES), 1)
                + LANES * u for u in range(tiles)]
        hit = [x == a for x in lane]

        def rows(u, j):            # lane tile u of row j of the groups
            return pl.ds(base * span + u * GROUP + j, size, stride=span)

        def pick(buf, sub, u):     # lane tile u of each cell's row
            row = buf[rows(u, 0), :]
            for j in range(1, GROUP):
                row = jnp.where(sub == j, buf[rows(u, j), :], row)
            return row

        def lane_max(xs):
            return functools.reduce(jnp.maximum, [
                jnp.max(x, 1, keepdims=True) for x in xs])

        # one entry each: a max over -inf picks it exactly (sign of 0 too)
        q_sa = lane_max([jnp.where(h, pick(s_buf, sub_s, u), -jnp.inf)
                         for u, h in enumerate(hit)])
        row_2 = [jnp.where(x < n_actions, pick(s2_buf, sub_2, u), -jnp.inf)
                 for u, x in enumerate(lane)]
        td = r + gamma * lane_max(row_2) - q_sa
        v_new = q_sa + alpha * td
        # next step's greedy on the POST-update s2 row
        row_2 = [jnp.where((s2 == s) & h, v_new, x)
                 for h, x in zip(hit, row_2)]
        m2 = lane_max(row_2)
        g_col[pl.ds(base, size), :] = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(x == m2, i_, n_actions), 1, keepdims=True)
            for x, i_ in zip(row_2, lane)])
        td_col[pl.ds(base, size), :] = td
        for u, h in enumerate(hit):    # the new entry into the s groups
            for j in range(GROUP):
                s_buf[rows(u, j), :] = jnp.where(
                    (sub_s == j) & h, v_new, s_buf[rows(u, j), :])

    def step(slot, checked):
        """Grid step ``i``, its block's groups in slot ``slot``, the next
        block's reads going to the other. ``checked``: some cell it
        touches may be past ``cells``, so every copy is guarded, in a
        loop of 8 cells."""
        other = 1 - slot
        s_buf, s2_buf = s_bufs[slot], s2_bufs[slot]

        def write(c, checked):
            copy(first, c, lambda cell: pltpu.make_async_copy(
                s_buf.at[group(c)], q_out.at[cell, row0(s_sm, c)],
                sem.at[2, slot]).start(), checked)

        @pl.when(i == 0)
        def _():                   # block 0's reads: no step before it
            def one(c, carry):
                read(0, c, s_sm, s2_sm, slot, False)
                return carry
            jax.lax.fori_loop(0, min(bc, cells), one, 0)

        @pl.when(i > 0)
        def _():                   # block i - 1's write-backs
            wait(s_bufs, 2, other, i - 1)

        for base in range(0, bc, tc):
            for k, v in enumerate((s_v, a_v, s2_v)):
                idx[k, pl.ds(base, tc), :] = _column(v[:, pl.ds(base, tc)],
                                                     tc)
            rew[pl.ds(base, tc), :] = _column(r_v[:, pl.ds(base, tc)], tc)
        wait(s_bufs, 0, slot, i)
        wait(s2_bufs, 1, slot, i)
        if checked:
            def eight(k, carry):
                base = pl.multiple_of(k * GROUP, GROUP)
                for u in range(GROUP):
                    read(first_nxt, base + u, sn_sm, s2n_sm, other, True)
                update(s_buf, s2_buf, base, GROUP)
                for u in range(GROUP):
                    write(base + u, True)
                return carry
            real = jnp.minimum(bc, cells - first)   # this block's cells
            jax.lax.fori_loop(0, (real + GROUP - 1) // GROUP, eight, 0)
        else:
            # straight-line code: the scalar work of starting a chunk's copies
            # shares the bundles of one basic block with the vector math
            # (in a loop, dynamic offsets, it ran 40% slower on a v5e)
            for base in range(0, bc, tc):
                for c in range(base, base + tc):
                    read(first_nxt, c, sn_sm, s2n_sm, other, False)
                update(s_buf, s2_buf, base, tc)
                for c in range(base, base + tc):
                    write(c, False)
        for base in range(0, bc, tc):
            g_ref[:, pl.ds(base, tc)] = _row(g_col[pl.ds(base, tc), :], tc)
            td_ref[:, pl.ds(base, tc)] = _row(td_col[pl.ds(base, tc), :], tc)

        @pl.when(i + 1 == n)
        def _():                   # the idle re-reads, the write-backs
            wait(s_bufs, 0, other, nxt)
            wait(s2_bufs, 1, other, nxt)
            wait(s_bufs, 2, slot, i)

    # a static slot each: the copies into one slot's buffers and the
    # math on the other's are then seen not to overlap, and may share
    # bundles (17% of the kernel's time on a v5e)
    for slot in (0, 1):
        on_slot = i % 2 == slot
        if not ragged:
            pl.when(on_slot)(functools.partial(step, slot, False))
            continue
        # only steps n - 2 and n - 1 touch the part-empty last block
        if blocks > 2:
            pl.when(on_slot & (i + 2 < n))(
                functools.partial(step, slot, False))
        pl.when(on_slot & (i + 2 >= n))(functools.partial(step, slot, True))


def tabular_rl_kernel(q, s, a, r, s2, *, alpha: float, gamma: float,
                      n_actions: int, bc: int, interpret: bool = True):
    """q: f32 in the kernel's layout (``align_table``) of a table with
    ``n_actions`` actions; s/a/s2: int32 and r: f32, each ``(blocks, 1,
    bc)`` with ``blocks * bc >= cells`` (lanes past ``cells`` are
    ignored). Returns ``(q_new, greedy2, td)`` with q_new in the same
    layout and greedy2/td shaped like ``s``; the semantics of
    ``ref.fused_tabular_ref``."""
    cells, rows, lanes = q.shape
    assert lanes == LANES and rows % (GROUP * lane_tiles(n_actions)) == 0, (
        q.shape, n_actions)
    blocks, rows_per_group = s.shape[0], GROUP * lane_tiles(n_actions)
    tc = min(bc, LANES)
    assert bc % GROUP == 0 and bc % tc == 0 and blocks * bc >= cells, (
        bc, blocks, cells)
    kernel = functools.partial(_kernel, bc=bc, tc=tc, cells=cells,
                               blocks=blocks, n_actions=n_actions,
                               alpha=alpha, gamma=gamma)
    smem = pl.BlockSpec((None, 1, bc), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    smem_next = pl.BlockSpec(
        (None, 1, bc), lambda i: (jnp.minimum(i + 1, blocks - 1), 0, 0),
        memory_space=pltpu.SMEM)
    vec = pl.BlockSpec((None, 1, bc), lambda i: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[smem, smem, smem_next, smem_next, vec, vec, vec, vec, hbm],
        out_specs=[hbm, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(s.shape, jnp.int32),
            jax.ShapeDtypeStruct(s.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bc * rows_per_group, LANES),
                                   jnp.float32)] * 4 + [
            pltpu.VMEM((3, bc, 1), jnp.int32),
            pltpu.VMEM((bc, 1), jnp.float32),
            pltpu.VMEM((bc, 1), jnp.int32),
            pltpu.VMEM((bc, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((3, 2))],
        input_output_aliases={8: 0},     # update the table in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s, s2, s, s2, s, a, r, s2, q)
