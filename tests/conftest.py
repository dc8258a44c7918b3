"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the JAX analogue of the reference's envtest trick (a real
kube-apiserver without a cluster; reference:
deploy/k8s-operator/kube-trailblazer/controllers/suite_test.go:50-60) —
multi-chip behavior without chips, via
``--xla_force_host_platform_device_count``.

Must set env BEFORE jax is imported anywhere.
"""

import os
import sys

# Force CPU, whatever the ambient environment says: tests need the
# 8-device virtual CPU mesh (and fp32 determinism), and must never take
# a chip another process may hold. JAX reads JAX_PLATFORMS at import.
os.environ["JAX_PLATFORMS"] = "cpu"
import re as _re  # noqa: E402

_flags = os.environ.get("XLA_FLAGS", "")
_flags = _re.sub(r"--xla_force_host_platform_device_count=\d+", "", _flags)
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop a test module's compiled programs when it is done. Every
    XLA:CPU executable a process keeps is a handful of memory mappings;
    a module of model tests leaves ~10 000 (tests/test_recurrent_layers.py
    alone), a worker of the tier-1 run goes through a dozen and a half
    such modules, and the kernel allows a process 65 530
    (``vm.max_map_count``): past it ``mmap`` fails inside the next
    compile, whichever test it is, and the worker dies of a segmentation
    fault in ``backend_compile_and_load`` (seen at PR 49 in four runs of
    four, each in another plain-model test; ``jax.clear_caches()`` takes
    a process from 3308 mappings back to 688). Set up first, so torn
    down after the module's own fixtures have stopped their engines."""
    yield
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def cpu_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def repo_root():
    import pathlib
    return pathlib.Path(__file__).resolve().parent.parent


@contextlib.contextmanager
def serve_app(app, timeout: float = 30.0):
    """Run an aiohttp app on an ephemeral port in a background thread;
    yields the base URL. Shared by every test that drives a live HTTP
    surface (score endpoint, real-weights gate, ...)."""
    import asyncio
    import threading

    from aiohttp import web

    loop = asyncio.new_event_loop()
    box: dict = {}
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            box["port"] = runner.addresses[0][1]
        loop.run_until_complete(boot())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(timeout), "HTTP server failed to boot in time"
    try:
        yield f"http://127.0.0.1:{box['port']}"
    finally:
        loop.call_soon_threadsafe(loop.stop)
