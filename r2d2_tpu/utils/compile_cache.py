"""Persistent XLA compilation cache.

The flagship train step / super-step are multi-second XLA compiles; every
benchmark run, smoke run and restarted trainer pays them again.  JAX ships a
persistent on-disk compilation cache — this module decides where it lives.
The reference has no analogue (torch eager); for a jitted framework it is
the difference between a cold and a warm start on repeat runs.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it natively; this module
  sets no directory in code.
- unset: :data:`CACHE_ROOT`, one fixed git-ignored path inside the
  checkout.  The path never depends on ``~``, a temp name, a pid or the
  time, so every process of a run — and the next run from the same
  checkout — finds the same entries.

Every process that compiles for the accelerator calls :func:`enable`
before its first jit compilation (``cli.main`` does, so every
``python -m r2d2_tpu …`` child does).
"""
from __future__ import annotations

import os

# <repo>/.jax_cache — also the home of the on-demand native builds
# (r2d2_tpu/native), so nothing the program builds lands outside the tree
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _configured_platform() -> str:
    """The first platform this process is configured for, WITHOUT
    initialising a backend ("" = JAX auto-detection)."""
    import jax

    plat = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    return plat.split(",")[0]


def enable() -> str | None:
    """Enable the persistent compilation cache; returns the dir or None.

    **Off on explicitly CPU-pinned processes** (tests, the CPU tools):
    XLA:CPU persists AOT results keyed loosely enough that a cached
    executable can reload under *mismatched host machine features*
    ("could lead to execution errors such as SIGILL") and run
    pathologically slowly — a cached actor act-fn degraded ~30x and
    starved the actor plane.  CPU compiles are cheap anyway; the cache's
    purpose is the accelerator compiles.

    EVERY compile is persisted (minimum compile time 0): on the v5e a
    trainer process compiles ~300-370 sub-second programs (eager ops,
    PRNG, casts) that together cost as much as the big ones — a warm
    start at a 1 s threshold still paid 27-33 s of them per process
    (PERF.md Findings, PR 21) — and a threshold makes what a run persists
    depend on timing jitter, so a second identical run could add entries.
    """
    if _configured_platform() == "cpu":
        return None
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir  # JAX already honours it; set nothing in code
    os.makedirs(CACHE_ROOT, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_ROOT)
    return CACHE_ROOT
