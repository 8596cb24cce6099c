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
import gc  # noqa: E402
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


# A worker of a whole run lives for hundreds of tests, and every program
# XLA compiles on the CPU holds a handful of memory mappings until its
# executable is freed. Engines end in reference cycles (runner, scheduler,
# tracker), which only the collector's oldest generation frees, and that
# runs rarely: a worker was seen at 64,074 mappings of the kernel's 65,530
# (``vm.max_map_count``) near the end of a run, where the next compile's
# ``mmap`` fails and LLVM takes the process down with a segmentation fault
# inside ``backend_compile_and_load`` ("node down" under xdist; PR 57 saw
# it twice in tests/test_spec_draft.py, which compiles most and runs
# late). So: past half the limit, collect after the test; if that is not
# enough, drop jax's own caches too, between two modules (a module's
# fixtures hold warmed engines whose tests count compiles), and inside a
# module only past 70 % of the limit (one test of tests/test_spec_draft.py
# adds 8,000).

def _map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 0        # no such limit to read here: nothing to guard


def _mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


_MAP_LIMIT = _map_limit()
_collect_from = _MAP_LIMIT // 2     # the count at which to collect next


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    global _collect_from
    yield
    if not _MAP_LIMIT or _mappings() < _collect_from:
        return
    gc.collect()
    between_modules = nextitem is None or (
        getattr(nextitem, "module", None) is not getattr(item, "module", None))
    left = _mappings()
    # (inside a module only in need: a recompile beats a dead worker)
    if left >= _MAP_LIMIT * (1 if between_modules else 1.4) // 2:
        import jax

        jax.clear_caches()
        gc.collect()
        left = _mappings()
    # what a collection leaves is held by live engines: collect again
    # only once a tenth of the limit has been added to it
    _collect_from = max(_MAP_LIMIT // 2, left + _MAP_LIMIT // 10)
