"""Work that fleet tabular Q-learning requires, counted from shapes.

These counts are what the algorithm needs, whatever implements it, so a
roofline share built on them cannot pass 100% for any implementation:

* The TD update of a cell reads row ``s2`` of its Q-table (``K`` values)
  for the target's max, and reads and writes the one entry ``(s, a)``.
  The next step's greedy action is the argmax of that same row after the
  update, so acting needs no further row. An implementation that streams
  the whole table, or reads row ``s`` again, does more than this.
* Per cell it also reads the indices ``s``, ``a``, ``s2`` and the reward,
  and writes the next greedy action and the TD error (4 bytes each).
* Operations: the row's max and argmax (``2 K``), the TD error and the
  update (4).

The whole step adds the cell's state read and written once (the link
states of ``N`` users and the edge, the two job counts) and the response
model: per user about 24 operations (the local, edge and cloud terms and
their contention factors), the noise and the reward. Random draws are
not counted: they are integer work, and leaving them out only keeps the
count a lower bound.
"""
from __future__ import annotations

F32 = 4


def tabular_update(cells: int, actions: int) -> dict:
    """One fused act+update over every cell."""
    return {"flops": cells * (2 * actions + 4),
            "bytes": cells * F32 * (actions + 2 + 4 + 2)}


def step(cells: int, users: int, actions: int) -> dict:
    """One whole training step of every cell."""
    upd = tabular_update(cells, actions)
    state = F32 * (users + 1 + 2 * 2)           # links, counts in and out
    return {"flops": upd["flops"] + cells * (24 * users + 8),
            "bytes": upd["bytes"] + cells * state}


def work(cells: int, users: int, actions: int, impl: str,
         chips: int = 1) -> dict:
    """Required work of the ops a traced run can time: the whole step,
    and the ``tabular_rl`` kernel where the compiled kernel runs. The
    Q-table's number of states does not enter: streaming the table is
    not required work."""
    out = {"chips": chips, "step": step(cells, users, actions)}
    if impl == "pallas":
        out["tabular_rl"] = tabular_update(cells, actions)
    return out
