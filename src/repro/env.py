"""Environment flags shared by benchmarks, examples, and tests.

Three variables are recognized:

  REPRO_SMOKE     truthy -> tiny-grid / few-step CI smoke runs.
  REPRO_PREFETCH  integer >= 0 -> streaming-pipeline prefetch depth for the
                  chunked sweep/search engine (how many chunks may be in
                  flight on the device ahead of the reducer fold).  0 means
                  fully serial (enqueue, block, fold); the default of 2 keeps
                  one chunk computing while the previous one folds —
                  double-buffering.  Any depth produces bit-identical reducer
                  states; the knob only trades memory for overlap.
  JAX_COMPILATION_CACHE_DIR  where `use_compile_cache` keeps JAX's
                  persistent compilation cache (read by JAX itself); unset
                  -> `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import os
from pathlib import Path

_TRUTHY = ("1", "true", "yes", "on")

DEFAULT_PREFETCH = 2


def smoke_mode(default: bool = False) -> bool:
    """True when REPRO_SMOKE requests tiny-grid / few-step CI smoke runs.

    The single source of truth for the flag's accepted values — benchmarks
    and examples must not re-parse the variable themselves, so the contract
    cannot silently diverge between entry points.
    """
    raw = os.environ.get("REPRO_SMOKE")
    if raw is None:
        return default
    return raw.strip().lower() in _TRUTHY


def prefetch_depth(default: int = DEFAULT_PREFETCH) -> int:
    """Streaming-pipeline prefetch depth from REPRO_PREFETCH (clamped >= 0).

    Single source of truth for the flag, mirroring `smoke_mode`: the engine
    (`core.sweep.sweep_chunked` and everything layered on it) consults this
    when no explicit ``prefetch=`` argument is given.  Unparseable values
    fall back to the default rather than erroring — a misconfigured shell
    must not change results, only scheduling.
    """
    raw = os.environ.get("REPRO_PREFETCH")
    if raw is None:
        return default
    try:
        return max(0, int(raw.strip()))
    except ValueError:
        return default


# fixed, so that one checkout's runs find each other's compiled programs
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile.  Where
    JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing else
    is set here; otherwise the cache lives at the checkout's fixed
    `.jax_cache` (never a temporary, per-process or timed path: the path is
    part of what makes a later run hit)."""
    raw = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if raw:
        return raw
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
