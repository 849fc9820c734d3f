"""Where JAX keeps its persistent compilation cache.

Entry points (the sweep and calibrate CLIs, ``launch/train.py`` and
``chip_smoke.py``) call :func:`configure_compilation_cache` once at start.
Library code never does, so tests and embedding programs write nothing
into the checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing is set here. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` — fixed, so that a later run of the same
checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/runtime/…``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compilation_cache() -> Path:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return Path(env)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return CHECKOUT_CACHE_DIR
