"""Persistent XLA compile cache for the program's entry points.

A cold fit of the device pipeline compiles one large XLA program (minutes
at paper-scale caps), and every adaptive-cap retry compiles another.
JAX's persistent compilation cache keeps those executables on disk, so a
later process with the same program and caps loads them instead.

:func:`enable` is called once by each entry point (``chip_smoke.py``,
``bench/main.py``, ``python -m repro.serve.driver``) -- never at
import time and never by the tests:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here overrides it;
* otherwise the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (gitignored).  The directory is part of what a
  cache entry is found by, so it never depends on a temporary name, a
  process id or the clock.

Either way source paths in the compiled programs are made relative to
the checkout, so a second checkout of the same code finds the entries
the first one wrote.
"""

from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> Optional[str]:
    """Turn the persistent compile cache on; returns its directory.

    Returns None (cache left off) when the package is not running from
    a checkout, i.e. there is no ``src/repro`` under the would-be root
    to keep the cache next to.
    """
    import jax

    # a Pallas kernel carries its Mosaic module into the program with
    # source locations, and the cache key hashes it: without this the
    # same program compiled from another checkout path misses the cache
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    env_dir = os.environ.get(ENV, "").strip()
    if env_dir:
        return env_dir
    if not (REPO_ROOT / "src" / "repro").is_dir():
        return None
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
