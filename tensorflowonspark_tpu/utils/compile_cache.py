"""Where JAX's persistent compilation cache lives, and what it did.

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` is used untouched when it is set,
and otherwise the entry points that start chip-owning processes
(``chip_smoke.py``, ``bench.py``, the sweep/profile scripts) export ONE
fixed path inside the checkout before they spawn anything, so executors,
replicas and phases inherit it through the environment.  JAX reads the
variable itself at import; nothing here touches ``jax.config``, and the
module imports without jax (a driver that must stay off the chip can
call :func:`export_env`).
"""

from __future__ import annotations

import os

DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — git-ignored; fixed, never derived from a pid, a
# time or a temporary name
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


def export_env(environ=None):
    """Make sure ``JAX_COMPILATION_CACHE_DIR`` is set for this process and
    every process it starts; returns the directory in force.  A value
    that is already set is left exactly as given."""
    environ = os.environ if environ is None else environ
    return environ.setdefault(DIR_ENV, DEFAULT_DIR)


class CacheCounter:
    """Counts this process's compile requests that consulted the
    persistent cache and how many of them it answered.  Programs below
    JAX's caching threshold (under a second to compile) are still
    requests, so ``compiled`` includes them."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def _on_event(self, event, **_kw):
        if event == _REQUESTS:
            self.requests += 1
        elif event == _HITS:
            self.hits += 1

    def install(self):
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def report(self):
        return {"dir": os.environ.get(DIR_ENV),
                "requests": self.requests, "hits": self.hits,
                "compiled": self.requests - self.hits}
