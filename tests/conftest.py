"""Test harness: force an 8-device virtual CPU platform before JAX import.

Mirrors the reference's GPU-free CI strategy (SURVEY.md §4): all tests run
without TPU hardware; sharding/mesh logic is exercised on a virtual 8-device
CPU mesh. Real-TPU tests are opt-in via the ``tpu`` marker.
"""

import os
import sys

# DYN_TPU_TESTS=1 opts into real-TPU tests; otherwise everything is pinned
# to the virtual 8-device CPU platform.
if not os.environ.get("DYN_TPU_TESTS"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: requires real TPU hardware (opt-in)")
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line("markers", "asyncio: run test in a fresh event loop")
    config.addinivalue_line(
        "markers",
        "dynlint: static-analysis enforcement gate (pure AST walk — "
        "no network, no TPU, no heavy imports; always on in tier-1)",
    )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests in a fresh event loop (no pytest-asyncio in env)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=120))
        return True
    return None


def pytest_collection_modifyitems(config, items):
    if os.environ.get("DYN_TPU_TESTS"):
        return
    skip_tpu = pytest.mark.skip(reason="TPU tests disabled (set DYN_TPU_TESTS=1)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)
