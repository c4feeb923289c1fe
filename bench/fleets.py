"""The benchmark's own fleet generator: a configuration's fleet from the
seed, on the host with NumPy, handed to the system under test and to the
reference alike.

``fleet`` in a configuration file picks one of:

* ``table5_mix`` — every cell is one of the paper's Table-5 link
  patterns (``patterns``: name -> "RWRWR|W", end links | edge link),
  drawn uniformly; links static.
"""
from __future__ import annotations

import numpy as np


def _pattern(p: str):
    ends, edge = p.split("|")
    return [int(c == "W") for c in ends], int(edge == "W")


def make(config: dict, seed: int) -> dict:
    cells, users = int(config["cells"]), int(config["users"])
    rng = np.random.default_rng(seed)
    kind = config["fleet"]
    if kind == "table5_mix":
        pats = [_pattern(p) for p in config["patterns"].values()]
        pick = rng.integers(0, len(pats), cells)
        ends = np.array([p[0][:users] for p in pats], np.int32)
        edges = np.array([p[1] for p in pats], np.int32)
        return {"end_b": ends[pick], "edge_b": edges[pick]}
    raise ValueError(f"unknown fleet {kind!r}")


def sample_cells(config: dict, seed: int) -> np.ndarray:
    """The cells whose whole Q-tables the check compares, drawn from the
    seed apart from the fleet's own draws."""
    rng = np.random.default_rng([seed, 1])
    cells = int(config["cells"])
    n = min(int(config["check_cells"]), cells)
    return np.sort(rng.choice(cells, n, replace=False)).astype(np.int32)
