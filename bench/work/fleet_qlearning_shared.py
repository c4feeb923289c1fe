"""Work that fleet tabular Q-learning over shared edges and Markov links
requires, counted from shapes, the same for every implementation.

It is the isolated fleet's step (``bench/work/fleet_qlearning.py``: the
TD update, the cell's links and job counts, the response model) and what
the shared edges and the links' chains add to it:

* the links' step: every end and edge link (``N + 1`` a cell) is written
  once, after a compare of its draw and a select (2 operations);
* the edge sums: each cell's edge id and edge job count are read (4
  bytes each) and added into its edge's total (1 operation); the totals
  (``n_edges``) are written once;
* the totals read back: each cell reads its edge's total and capacity
  (4 bytes each) and divides (1 operation); the capacities are read once.

Random draws are not counted, as in the isolated count.
"""
from __future__ import annotations

import spec

F32 = 4
isolated = spec.load_module("work", "fleet_qlearning")


def step(cells: int, users: int, actions: int, n_edges: int) -> dict:
    """One whole training step of every cell."""
    base = isolated.step(cells, users, actions)
    links = cells * (users + 1)
    return {"flops": base["flops"] + 2 * links + cells * (1 + 1),
            "bytes": base["bytes"] + F32 * (links + cells * (2 + 2)
                                            + 2 * n_edges)}


def work(cells: int, users: int, actions: int, n_edges: int,
         chips: int = 1) -> dict:
    """Required work of the ops a traced run can time: the whole step.
    The mesh runs no kernel, so no kernel's count is given."""
    return {"chips": chips, "step": step(cells, users, actions, n_edges)}
