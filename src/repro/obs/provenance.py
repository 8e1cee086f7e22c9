"""Provenance stamping for benchmark artifacts.

Every ``BENCH_*.json`` the repo emits embeds :func:`provenance_block` so a
number can always be traced back to the code and machine that produced it
— git SHA (+dirty flag), jax/jaxlib versions, the active JAX backend,
platform string, CPU count, UTC timestamp and the CLI args the run was
invoked with. Bench trajectories across PRs and machines are only
comparable when this block says they are.

Git facts degrade to ``None`` rather than raising (git absent, or running
from an sdist without a work tree). JAX facts do not: a JAX that cannot
start is not a backend to stamp, so its error propagates and the bench
fails instead of recording ``backend: None`` and running on.

The block also carries a **config digest** (:func:`config_digest` — a
sha256 over the run's canonicalized configuration: bench args + solver
config) and the **seed list** the run consumed. ``tools/bench_compare.py``
refuses to compare two BENCH files whose digests differ — a tolerance
policy is meaningless across different workloads, and "the numbers moved"
must never be confused with "the experiment changed".
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import subprocess
import sys
from datetime import datetime, timezone
from typing import Any, Dict, List, Optional, Sequence

__all__ = ["git_sha", "config_digest", "provenance_block"]


def git_sha(repo_dir: Optional[str] = None) -> Optional[str]:
    """Current commit SHA, suffixed ``+dirty`` when the tree has
    uncommitted changes; None when git/worktree is unavailable."""
    try:
        kw: Dict[str, Any] = {"stderr": subprocess.DEVNULL, "text": True}
        if repo_dir is not None:
            kw["cwd"] = repo_dir
        sha = subprocess.check_output(
            ["git", "rev-parse", "HEAD"], **kw).strip()
        dirty = subprocess.check_output(
            ["git", "status", "--porcelain"], **kw).strip()
        return sha + ("+dirty" if dirty else "")
    except (OSError, subprocess.CalledProcessError):
        return None


def _canonical(obj: Any) -> Any:
    """Coerce a config value into a JSON-stable form: numpy scalars/arrays
    to Python numbers/lists, tuples to lists, anything exotic to its repr —
    so the digest depends on VALUES, not container or dtype identity."""
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items(),
                                                         key=lambda kv:
                                                         str(kv[0]))}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (str, bool)) or obj is None:
        return obj
    if isinstance(obj, (int, float)):
        return obj
    if hasattr(obj, "item") and getattr(obj, "shape", None) == ():
        return obj.item()                     # numpy/jax scalar
    if hasattr(obj, "tolist"):
        return obj.tolist()                   # numpy/jax array
    return repr(obj)


def config_digest(config: Any) -> str:
    """A short sha256 hex digest of the run's canonicalized configuration
    (bench args + solver config). Two BENCH files are comparable only when
    their digests match — ``bench_compare`` refuses otherwise. Dict key
    order, tuple-vs-list and numpy-vs-Python scalar types do not affect
    the digest; values do."""
    blob = json.dumps(_canonical(config), sort_keys=True,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def provenance_block(argv: Optional[List[str]] = None,
                     config: Any = None,
                     seeds: Optional[Sequence[int]] = None
                     ) -> Dict[str, Any]:
    """The provenance dict embedded in every emitted BENCH JSON.

    ``argv`` should be the CLI args the bench was invoked with (defaults
    to ``sys.argv[1:]``). ``config`` is the run's full configuration (bench
    parameters + solver config), digested via :func:`config_digest` so
    ``bench_compare`` can refuse cross-config comparisons; ``seeds`` the
    RNG seeds the run consumed. Both stamp ``None`` when omitted (older
    BENCH files simply lack the keys)."""
    import jax
    import jaxlib
    return {
        "git_sha": git_sha(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count(),
        "timestamp_utc": datetime.now(timezone.utc).isoformat(),
        "argv": list(sys.argv[1:] if argv is None else argv),
        "config_digest": None if config is None else config_digest(config),
        "seeds": None if seeds is None else [int(s) for s in seeds],
    }
