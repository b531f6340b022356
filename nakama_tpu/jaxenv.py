"""Process-level JAX set-up shared by every entry point.

Two decisions live here so that no launcher makes them on its own:
where compiled programs are kept between runs, and what a launcher
does when it finds fewer devices than it was asked to use.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called first thing by `server.main`, `bench.py`, `benchmark/run.py`
    and `chip_smoke.py`. Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads
    it itself and nothing is set in code. Otherwise the cache lives at
    the fixed `<checkout>/.jax_cache`: the path is part of the cache
    key, so a directory that moved between runs would never hit."""
    import jax

    # A Pallas kernel's cache key carries the source locations of its
    # ops, by default with every Python frame above the call: an edit to
    # any launcher, or a bucket compiled on the prewarm thread in one
    # run and on the interval loop in the next, then never hits (chip
    # runs of PR 21: 1 hit in 28 requests). The nearest frame is enough.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_devices(n: int) -> list:
    """The first `n` JAX devices, or a clear error: a launcher that was
    asked for a mesh never swaps the accelerator for virtual CPU devices
    on its own. A caller that set `JAX_PLATFORMS=cpu` itself (the test
    rig) gets `n` host devices, and reports its results as CPU ones."""
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        try:
            jax.config.update("jax_num_cpu_devices", n)
        except RuntimeError:
            pass  # backend already up: its device count stands
    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"need {n} devices, JAX reports {len(devices)}"
            f" ({devices[0].platform}); for a CPU rehearsal set"
            " JAX_PLATFORMS=cpu yourself"
        )
    return devices[:n]
