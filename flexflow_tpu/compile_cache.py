"""The one rule for where the persistent XLA compile cache lives.

If ``JAX_COMPILATION_CACHE_DIR`` is in the environment, jax has already
read it and this program sets no cache directory in code, anywhere.  If
it is not, the cache is ``<checkout>/.jax_cache_chip`` — a fixed path,
because the path is part of the cache key and a directory that moves
never hits.

Called from the normal entries (``cli.main``, the serving engines,
bench.py, chip_smoke.py) rather than on library import, so embedding
applications keep control of their own jax.config: a directory some
harness configured before us (tests/conftest.py's session-scoped
``.jax_cache``) is kept.

Multi-model processes (a serving fleet — serving/fleet): XLA keys
entries on the lowered HLO + backend/topology, so two tenants with
IDENTICAL graphs/shapes share one on-disk entry — which is correct
and desirable (the executable is parameter-free; params are call
arguments).  Per-model separation of the IN-PROCESS bucket
executables is the job of ``FFModel.forward_compiled``'s
``(bucket, exec_digest)`` key, not this cache: model B can never be
handed an executable lowered for model A's graph/strategies/mesh
even when both warmed the same persistent cache
(tests/test_fleet.py pins the collision case).
"""

from __future__ import annotations

import os


def default_dir() -> str:
    """``<checkout>/.jax_cache_chip`` — used only when nothing outside
    the program placed the cache."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache_chip")


def enable() -> str:
    """Apply the rule above; returns the directory in use.  Idempotent.
    jax's own defaults decide what is worth an entry (compiles of a
    second or more), so nothing else is configured here."""
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current  # the environment (or the embedding harness) chose
    jax.config.update("jax_compilation_cache_dir", default_dir())
    return default_dir()
