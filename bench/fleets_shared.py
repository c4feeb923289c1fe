"""The fleet generator of the shared-edge, Markov-link configurations: a
configuration's fleet from the seed, on the host with NumPy, handed to
the system under test and to the reference alike.

``fleet`` in a configuration file picks one of:

* ``table5_markov`` — every cell starts in one of the paper's Table-5
  link patterns, drawn uniformly (``fleets.make``'s ``table5_mix``);
  from there each link is a two-state Regular/Weak chain that switches
  with probability ``p_switch`` a step, stepped by the program and the
  reference alike from the run's keys. Cells share ``n_edges`` edges of
  capacity ``edge_capacity``: the cells and the edges are cut into
  ``chips`` contiguous blocks, the blocks a fleet mesh places on each
  chip, and each cell draws its edge uniformly within its own block, so
  no edge serves cells of two chips.
"""
from __future__ import annotations

import numpy as np

import fleets


def blocks(config: dict):
    """(cells, edges) of one chip's block; both counts have to divide."""
    cells, edges = int(config["cells"]), int(config["n_edges"])
    chips = int(config["chips"])
    if cells % chips or edges % chips:
        raise ValueError(f"{cells} cells and {edges} edges do not split "
                         f"into {chips} equal blocks")
    return cells // chips, edges // chips


def make(config: dict, seed: int) -> dict:
    """``end_b`` (cells, N) and ``edge_b`` (cells,) initial link states,
    ``cell_edge`` (cells,) the edge serving each cell and
    ``edge_capacity`` (n_edges,)."""
    kind = config["fleet"]
    if kind != "table5_markov" or not config["shard_local"]:
        raise ValueError(f"unknown fleet {kind!r} (shard_local "
                         f"{config['shard_local']!r}): this generator "
                         f"draws shard-local edges only")
    links = fleets.make(dict(config, fleet="table5_mix"), seed)
    cells = int(config["cells"])
    cpb, epb = blocks(config)
    rng = np.random.default_rng([seed, 2])
    cell_edge = (np.arange(cells) // cpb) * epb + rng.integers(0, epb, cells)
    capacity = np.full(int(config["n_edges"]), config["edge_capacity"],
                       np.float32)
    return dict(links, cell_edge=cell_edge.astype(np.int32),
                edge_capacity=capacity)
