"""Where compiled programs are kept: one rule for every process that jits.

A restarted training worker, a respawned serving worker and a bench child
are all new processes; what turns their recompile into a load from disk is
JAX's persistent compilation cache, and the directory is part of the
cache key's locality — a directory that moves never hits.  So:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code, and no other directory is ever used;
- unset: one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored) — never a temporary name, a pid or a timestamp.

Call :func:`ensure_compile_cache` first thing in a process that will jit.
:func:`cache_counts` then says how many compiles this process loaded from
the cache and how many it had to make (JAX's own monitoring events) —
how a restarted worker shows that its restart was a cache hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <repo>/.jax_cache: this file lives at <repo>/dlrover_tpu/utils/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _count(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key:
        _counts[key] += 1


def ensure_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache lands in."""
    global _listening
    import jax

    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_count)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_counts() -> dict:
    """Persistent-cache hits and misses of this process so far."""
    return dict(_counts)
