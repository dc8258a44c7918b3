"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the JAX analogue of the reference's envtest trick (a real
kube-apiserver without a cluster; reference:
deploy/k8s-operator/kube-trailblazer/controllers/suite_test.go:50-60) —
multi-chip behavior without chips, via
``--xla_force_host_platform_device_count``.

Must set env BEFORE jax is imported anywhere.

Nothing imports a name from this file (``from conftest import …``):
``tests/`` and ``tests/benchmarks/`` are not packages, so both
``conftest.py`` files are the module ``conftest``, and the name means
whichever of them a worker loaded last. What tests share is a fixture.
"""

import os
import signal
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

# Compile without XLA's expensive passes (backend optimisation level 0,
# no expensive LLVM passes). Tier-1 checks what programs COMPUTE, at toy
# sizes; each program runs once or twice, so the suite's time is its
# XLA:CPU compile time, and a fifth of that is optimisation nothing here
# reads (docs/testing.md). Through the environment, so that the
# processes some cases start (eval.py's dev stack) compile as lightly.
# A module whose subject is the compiler's product asks for
# ``full_optimisation``.
_LIGHT_COMPILES = "JAX_DISABLE_MOST_OPTIMIZATIONS"
os.environ[_LIGHT_COMPILES] = "1"

#: Seconds one case may run before it fails by name. More than twice the
#: longest case of a whole run on a loaded sandbox (128 s, ISSUE 56): for
#: a case that waits on a thread or a socket for ever, not to police slow
#: ones.
CASE_LIMIT_S = 300

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop a test module's compiled programs when it is done. Every
    XLA:CPU executable a process keeps is a handful of memory mappings;
    a module of model tests leaves ~12 000 (tests/test_recurrent_layers.py
    alone: 12 380 at its end, 703 after this, and the same with and
    without XLA's expensive passes — they go by the executable, not by
    its size: PR 56), a worker of the tier-1 run goes through a dozen
    and a half such modules, and the kernel allows a process 65 530
    (``vm.max_map_count``): past it ``mmap`` fails inside the next
    compile, whichever test it is, and the worker dies of a segmentation
    fault in ``backend_compile_and_load`` (seen at PR 49 in four runs of
    four, each in another plain-model test). Set up first, so torn down
    after the module's own fixtures have stopped their engines."""
    yield
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def full_optimisation(_release_compiled_programs):
    """For a module whose subject IS the compiler's product (a program
    compiled for a described TPU, an optimised program's text, cost or
    memory, two programs held equal to the bit): its programs compile
    with every pass, in this process and in the ones it starts. Asked for
    by name (``pytestmark = pytest.mark.usefixtures("full_optimisation")``),
    so it is set up before the module's own fixtures; the flag is not
    part of a jitted function's cache key, so the caches go first (the
    module's own go with ``_release_compiled_programs``, after this)."""
    import jax
    del os.environ[_LIGHT_COMPILES]
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_disable_most_optimizations", True)
    os.environ[_LIGHT_COMPILES] = "1"


@pytest.fixture(autouse=True)
def _case_limit(request):
    """A case that hangs fails by its own name after ``CASE_LIMIT_S``,
    and the rest of its module runs: without this the run's clock cuts
    the whole suite and names nobody. The alarm is raised in the main
    thread, where pytest runs the case (under xdist too). A module
    fixture's set-up runs before this and is not under the limit."""
    if not hasattr(signal, "SIGALRM"):
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran past {CASE_LIMIT_S} s",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, CASE_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


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


@pytest.fixture(scope="session")
def serve_app():
    """``with serve_app(app) as base_url``: an aiohttp app on an
    ephemeral port in a background thread, for a test that drives a live
    HTTP surface. A fixture, for the reason in this file's docstring."""
    return _serve_app


@contextlib.contextmanager
def _serve_app(app, timeout: float = 30.0):
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
