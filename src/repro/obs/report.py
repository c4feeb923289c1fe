"""Run manifests: provenance stamped onto bench JSONs and train results.

A manifest answers "what produced this number?" — git SHA (+dirty
flag), jax/jaxlib versions, backend and device count, mesh shape,
a stable hash of the config, and wall-clock context. It is attached to
every ``benchmarks/run.py --json`` payload (via ``benchmarks.common.
save_json``) and to ``FleetTrainResult``; ``tools/obsview.py`` reads it
back to pretty-print or diff runs, and ``tools/benchgate.py`` diffs a
fresh run against the tracked baseline through the shared
:func:`flatten` / :func:`rel_diff` helpers below.

Everything here is fault-tolerant: a missing git binary or a non-repo
checkout yields ``None`` fields, never an exception — provenance must
not take down a benchmark. ``jax`` is imported lazily (only
``run_manifest`` needs it) so the stdlib-level helpers stay cheap to
import from the ``tools/`` scripts.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import numbers
import os
import platform
import subprocess
import sys
from datetime import datetime, timezone
from typing import Any, Optional

MANIFEST_SCHEMA = "repro.obs/manifest-v1"


def flatten(obj: Any, prefix: str = "") -> dict:
    """Flat dict of dotted-path -> scalar, skipping the manifest.

    THE shared flattening of nested run JSONs — ``tools/obsview.py``
    (show/diff/history) and ``tools/benchgate.py`` both read metrics
    through it, so a key renders and gates under the same dotted path.
    """
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            if k == "manifest":
                continue
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}{i}."))
    else:
        out[prefix[:-1]] = obj
    return out


def is_number(v: Any) -> bool:
    """True for real numerics that compare as metrics (bools excluded —
    a flipped flag is a structural change, not a relative move)."""
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


def rel_diff(a: float, b: float) -> float:
    """Signed relative move from ``a`` to ``b``; a zero base falls back
    to an absolute difference (base 1.0) so dividing never explodes."""
    base = abs(a) if a else 1.0
    return (b - a) / base

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def config_hash(config: Any) -> str:
    """Stable short hash of a config (dataclass, dict, or anything with
    a deterministic repr via ``default=str``)."""
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        config = dataclasses.asdict(config)
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _git(*args: str) -> Optional[str]:
    try:
        out = subprocess.run(
            ("git", "-C", _REPO_ROOT) + args,
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def git_info() -> dict:
    sha = _git("rev-parse", "HEAD")
    status = _git("status", "--porcelain")
    return {
        "sha": sha,
        "branch": _git("rev-parse", "--abbrev-ref", "HEAD"),
        "dirty": bool(status) if status is not None else None,
    }


def run_manifest(config: Any = None, mesh=None, **extra) -> dict:
    """The provenance stamp. ``mesh`` is a ``jax.sharding.Mesh`` (or
    None); ``extra`` keys (e.g. ``wall_seconds=...``) merge in last."""
    import jax
    import jaxlib
    m = {
        "schema": MANIFEST_SCHEMA,
        "created_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "git": git_info(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kinds": sorted({d.device_kind for d in jax.devices()}),
        "mesh_shape": ({str(k): int(v) for k, v in dict(mesh.shape).items()}
                       if mesh is not None else None),
        "config_hash": config_hash(config) if config is not None else None,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "argv": list(sys.argv),
    }
    m.update(extra)
    return m


def attach_manifest(payload: dict, config: Any = None, mesh=None,
                    **extra) -> dict:
    """Return a copy of ``payload`` with a ``manifest`` key added; the
    input dict is not mutated."""
    out = dict(payload)
    out["manifest"] = run_manifest(config=config, mesh=mesh, **extra)
    return out
