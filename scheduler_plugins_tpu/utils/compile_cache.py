"""Where the persistent XLA compile cache lives.

A cold compile of the cluster-size programs takes tens of seconds on a
TPU, and a machine that runs one command and is thrown away pays it on
every call unless compiled programs outlive the process. The cache
directory is part of the cache key, so it has to be the same path every
time:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself and this
  module sets no directory at all — whoever placed the cache owns it;
- unset: `<checkout>/.jax_cache` (git-ignored), the one fixed path every
  entry point shares. Never a temp name, a pid or a time.

`configure()` is called by the entry points (`python -m
scheduler_plugins_tpu`, `chip_smoke.py`, `benchmark/run.py` through the
daemon it starts) before their first compile. Tests call nothing and get no persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

from scheduler_plugins_tpu.utils import observability as obs

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        obs.COMPILE_CACHE_REQUESTS,
    "/jax/compilation_cache/cache_hits": obs.COMPILE_CACHE_HITS,
}

_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        obs.metrics.inc(name)


def configure() -> str:
    """Place the persistent compile cache and start counting its hits;
    returns the directory in use. Programs are cached whatever they cost
    to compile: a daemon tick dispatches dozens of sub-second programs,
    and JAX's default 1 s floor would recompile every one of them in each
    new process."""
    global _listening
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _listening:
        _listening = True
        monitoring.register_event_listener(_on_event)
    return directory
