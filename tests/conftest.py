"""Test configuration: force a virtual 8-device CPU mesh.

Mirrors the reference's strategy of faking multi-device with multiple CPU
contexts in one process (tests/python/unittest/test_multi_device_exec.py):
here we give XLA 8 host devices so jax.sharding Meshes exercise real
collectives without TPU hardware. Both variables are set before jax is
imported, so the tests never reach for a chip even where one exists.
"""
import os
import signal
import threading

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()


# ---------------------------------------------------------------------------
# fast/slow partition (docs/testing.md): tier-1 is `-m 'not slow'`. A test
# is slow only by a `@pytest.mark.slow` (or a file's `pytestmark`) of its
# own, with one of four reasons beside it: it builds native code or needs
# a toolchain; it launches several processes; it takes over 30 s alone;
# or it is named in ROADMAP.md C10 with its failure. Everything else is
# `fast`, the complement.
# ---------------------------------------------------------------------------

# seconds one test may take before it fails by name, so that a hanging
# test costs a run one test and not its clock
TEST_LIMIT_S = 120


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: native toolchain, several processes, over 30 s "
                   "alone, or named in ROADMAP.md C10 (not in tier-1)")
    config.addinivalue_line(
        "markers", "fast: tier-1, the complement of slow")
    # lock witness (docs/static_analysis.md "Lock witness"): armed
    # BEFORE any mxtpu import, and loaded by FILE PATH — `import
    # mxtpu.devtools.lockwitness` would run mxtpu/__init__ first and
    # every lock created during that import would be born unwrapped,
    # making accesses under those locks look unguarded.
    if os.environ.get("MXTPU_LOCK_WITNESS") == "1":
        import importlib.util
        import pathlib
        lw = pathlib.Path(__file__).resolve().parent.parent / \
            "mxtpu" / "devtools" / "lockwitness.py"
        spec = importlib.util.spec_from_file_location(
            "_mxtpu_lockwitness", str(lw))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.install()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if not item.get_closest_marker("slow"):
            item.add_marker(pytest.mark.fast)


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test that runs over TEST_LIMIT_S, by name (pytest-timeout is
    not installed). Armed only where SIGALRM can reach the test, on the
    main thread of a platform that has it, and not for a `slow` test,
    which may take longer by its own mark."""
    if not hasattr(signal, "SIGALRM") or \
            threading.current_thread() is not threading.main_thread() or \
            request.node.get_closest_marker("slow"):
        yield
        return

    def over(signum, frame):
        pytest.fail("%s ran over the per-test limit of %d s"
                    % (request.node.nodeid, TEST_LIMIT_S), pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def tiny_windows(monkeypatch):
    """``parallel/moe.py::held_window``'s rule brought down to the serving
    tests' tiny widths for one test: tiles of 8 rows where the chip's are
    128, 16 rows to spare where the chip's rule asks 512. A chunk of 32
    rows then walks its held rows in windows; a decode step of 4 slots and
    a layer that holds every expert still make their one pass."""
    from mxtpu.parallel import moe
    monkeypatch.setattr(moe, "GROUPED_TILING", (8,) + moe.GROUPED_TILING[1:])
    monkeypatch.setattr(moe, "HELD_WINDOW_MIN_SPARED", 16)
