"""Small runtime/compat helpers shared across the engines."""

from __future__ import annotations

import contextlib
import os
import warnings
from pathlib import Path

# the checkout's own compile cache: a fixed path, since the directory is
# part of every cache key (a path that moves never hits); gitignored
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to
    ``<repo>/.jax_cache``.  Called by the ``main`` of each example, of
    ``launch/serve_fl.py``, of ``benchmarks/run.py`` and by
    ``chip_smoke.py`` before their first compile — never by library code
    or tests, which keep JAX's defaults.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))


@contextlib.contextmanager
def suppress_unusable_donation_warnings():
    """Silence XLA's "Some donated buffers were not usable" warning.

    Both sweep engines donate their grid arrays so the multi-device path
    can reuse the buffers; CPU backends cannot honor the donation and warn
    once per compile.  That warning is expected and not actionable, so the
    engines wrap their jit entry calls in this context manager (defined
    once here instead of copy-pasting the filter).
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield
