"""Persistent XLA compilation cache.

The test suite, the benchmarks and every chip run are dominated by XLA
compiles (the reference copes with CI wall-clock via suite sharding,
SURVEY.md §4; here the analog is caching compiled executables across
processes). Enable early — before the first ``jit`` call — so every
compilation above the time threshold is persisted and reloaded.

Where the cache lives is decided outside the program when it can be:
``JAX_COMPILATION_CACHE_DIR`` is JAX's own variable, JAX reads it
itself, and this module then sets no directory at all — a second knob
beside JAX's would be a precedence bug waiting. Unset, the cache goes
to ONE fixed directory inside the checkout (gitignored). The path is
part of what makes an entry findable again, so it is never under
``~``, never a temporary name, and never a function of the pid, the
time or the working directory. JAX's cache key already covers the
machine: it hashes the backend's topology, which for XLA:CPU lists the
host's ISA features, so an executable compiled for another CPU is a
miss, not a SIGILL.
"""

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return the
    directory it uses: ``JAX_COMPILATION_CACHE_DIR`` where that is set
    (left entirely to JAX), else :data:`DEFAULT_DIR` (created if
    missing). Safe to call more than once."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
