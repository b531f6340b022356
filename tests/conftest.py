"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU mesh so sharding/collective tests run
without TPU hardware (mirrors the reference's in-memory test style,
reference server/match_common_test.go:34-81, but adds the multi-device tier
the reference lacks — see SURVEY.md §4).

Must set XLA_FLAGS before jax initialises, hence this lives at the very top
of conftest and tests must not import jax before pytest collects us.
"""

import asyncio
import inspect
import os

_TPU_TIER = bool(os.environ.get("NAKAMA_TPU_TESTS"))

if not _TPU_TIER:
    os.environ["JAX_PLATFORMS"] = "cpu"  # hermetic: never grab the real TPU
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# The live config says the same as the env vars above, whichever of the
# two jax reads first; a jax that cannot be held to the CPU must fail
# here rather than let the tests take an accelerator.
import jax

if not _TPU_TIER:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import pytest


def pytest_collection_modifyitems(config, items):
    """tpu-marked tests run only in the chip tier
    (NAKAMA_TPU_TESTS=1 pytest -m tpu); the default CPU-forced run
    skips them."""
    if _TPU_TIER:
        return
    skip = pytest.mark.skip(reason="chip tier: NAKAMA_TPU_TESTS=1 -m tpu")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no pytest-asyncio here)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
