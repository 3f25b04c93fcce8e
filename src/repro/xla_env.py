"""Pre-jax-import XLA environment setup.

jax locks the device count at first initialization, so anything that wants
forced host devices (the dry-run's 512 placeholder chips, ``train.py``'s
``--debug-mesh``) must append to ``XLA_FLAGS`` *before* the first
``import jax`` anywhere in the process.  This module therefore imports
nothing but the stdlib at import time — safe to import at the very top of
an entrypoint.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset:
# a fixed directory of the checkout, so that one run's programs are found by
# the next (a directory that moves between runs never hits).
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_host_devices(n: int) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={n}"
                               ).strip()


def debug_mesh_devices(argv: list[str] | None = None) -> None:
    """Force one host device per chip of a ``--debug-mesh AxB`` spec.

    Handles both argparse spellings (``--debug-mesh 4x2`` and
    ``--debug-mesh=4x2``); a missing value is left for argparse to
    reject with a proper usage error after imports.
    """
    argv = sys.argv if argv is None else argv
    spec = None
    for i, arg in enumerate(argv):
        if arg == "--debug-mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif arg.startswith("--debug-mesh="):
            spec = arg.split("=", 1)[1]
    if not spec:
        return
    n = 1
    for part in spec.split("x"):
        n *= int(part)
    force_host_devices(n)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX honours it by itself
    and nothing is set here.  Otherwise the cache is :data:`CACHE_DIR`.
    Imports jax, so call it after any ``force_host_devices``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
