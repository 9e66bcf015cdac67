"""Where JAX keeps its persistent compilation cache, and whether it hit.

Entry-point scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/``)
call :func:`place` first thing; importing ``repro`` never touches the
cache setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
  else is set here.
* Unset: the cache goes to ``<repo root>/.jax_cache``.  The path is
  fixed (no temp name, pid or time) because it is part of what a later
  run in the same checkout must find again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


def place(repo_root) -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = str(Path(repo_root).resolve() / DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Counts persistent-cache hits and misses from JAX's monitoring
    events while registered."""

    def __init__(self):
        self.counts: Dict[str, int] = {v: 0 for v in _EVENTS.values()}
        self._registered = False

    def _on_event(self, event: str, **_kwargs) -> None:
        name: Optional[str] = _EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def __enter__(self) -> "CacheCounter":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        self._registered = True
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        if self._registered:
            jax.monitoring.unregister_event_listener(self._on_event)
            self._registered = False
