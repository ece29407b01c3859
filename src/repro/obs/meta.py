"""Run provenance: one dict stamped into every exported trace.

A number without its machine is unauditable -- a 1.07x device win on a
1-core CPU runner and the same ratio on a TPU runner are different
facts.  :func:`bench_meta` captures the invariants that make a
measurement comparable: jax/jaxlib versions, backend + device
kind/count, host platform, an ISO-8601 UTC timestamp, and the git
revision.
"""

from __future__ import annotations

import platform
import subprocess
import sys
from datetime import datetime, timezone
from typing import Any, Dict

__all__ = ["bench_meta", "git_rev"]


def git_rev() -> str:
    """Short git revision of the working tree ("unknown" outside a
    checkout), with a ``-dirty`` suffix for uncommitted changes."""
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            check=True).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=5,
            check=True).stdout.strip()
        return rev + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def bench_meta() -> Dict[str, Any]:
    """Provenance block for exported traces (JSON-able)."""
    meta: Dict[str, Any] = {
        "timestamp": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "git_rev": git_rev(),
    }
    try:
        import jax
        import jaxlib
        devs = jax.devices()
        meta.update({
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind if devs else "none",
            "device_count": jax.device_count(),
        })
    except Exception as e:          # benches may pre-configure XLA flags
        meta["jax"] = f"unavailable ({type(e).__name__})"
    return meta
