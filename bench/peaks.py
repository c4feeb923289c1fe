"""Peak rates per ``device_kind`` from ``bench/peaks.json``, with their
source. A kind that is not in the table is an error, not a default."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peak rates for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def least_seconds(work: dict, kind: str, chips: int = 1) -> float:
    """The least time ``chips`` chips could take for ``work`` (required
    FLOPs and bytes): the larger of the compute and the memory bound."""
    p = peaks(kind)
    return max(work["flops"] / (chips * p["flops_per_s"]),
               work["bytes"] / (chips * p["bytes_per_s"]))
