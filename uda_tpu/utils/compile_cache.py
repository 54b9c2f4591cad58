"""Persistent XLA compilation cache placement.

The reference pays its startup costs once per daemon (RDMA device
discovery + ~1 GB memory registration at MOFSupplier start, reference
src/DataNet/RDMAComm.cc:314-370): every later request reuses the warm
state. The TPU analogue of that warm state is the compiled XLA
executable — the served path compiles one Mosaic merge program per
run-capacity class (ops.merge.next_run_capacity: O(log n) shapes per
job) — so uda_tpu persists executables to an on-disk cache shared by
every process: only the first process ever pays for a given program.

Where the cache lives is decided HERE and nowhere else:

- ``JAX_COMPILATION_CACHE_DIR`` set: the directory is JAX's to read
  from the environment; this module sets none in code.
- unset: ``<checkout>/.jax_cache`` (git-ignored). Always the same
  path: a directory named after a pid, a time or a tempdir is never
  found again by the next process.

``enable()`` is idempotent, cheap, and never initializes a backend;
every uda_tpu entry point calls it (``UdaBridge.start``,
``MergeManager.__init__``, ``__graft_entry__``, ``chip_smoke.py``'s
children, ``tests/conftest.py``).
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")

_enabled = False


def cache_dir() -> str:
    """The directory the persistent cache uses when enabled."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_DIR


def enable() -> bool:
    """Turn on the persistent compilation cache for this process.
    Returns True when this module armed it.

    CPU-only configurations are excluded: CPU compiles are fast, and
    XLA:CPU AOT cache entries pin the compile machine's feature set —
    reloading them on a host with a different detected feature set
    risks SIGILL. The cache's purpose is the accelerator."""
    global _enabled
    if _enabled:
        return True
    import jax

    # Detect a CPU-only configuration WITHOUT instantiating a backend:
    # jax.default_backend() here would lock in platform selection and
    # break callers (dryrun_multichip) that force CPU after enable().
    # An unset value (auto-detect) enables the cache — the accelerator
    # case is the one that matters.
    platforms = (jax.config.jax_platforms
                 or os.environ.get("JAX_PLATFORMS", ""))
    if platforms.strip().lower() == "cpu":
        return False
    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # cache every program: the forest's small merge shapes compile in
    # well under JAX's default 1 s floor, and a warm process should
    # recompile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    return True
