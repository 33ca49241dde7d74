"""Test configuration: hermetic 8-device CPU mesh.

Mirrors the reference's gloo-spawn multi-device testing pattern
(SURVEY.md §4): JAX on CPU with ``--xla_force_host_platform_device_count=8``
gives multi-device semantics without TPU hardware.
"""

import os

# Per-xdist-worker resource scoping: /dev/shm segment names and the
# default IPC dir both derive from DLROVER_TPU_SHM_PREFIX (read at
# dlrover_tpu.common.constants import time — this assignment must come
# first), so two workers' fixed node-id arenas (ckpt_node3 etc.) can
# never collide. Serial runs are untouched.
_xdist_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _xdist_worker:
    os.environ["DLROVER_TPU_SHM_PREFIX"] = f"dlrover_tpu_{_xdist_worker}"

# Tests are hermetic and multi-device: JAX is held to the CPU with 8
# virtual devices, here and in every child a test starts (they inherit
# the environment).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Per-run compile cache: the AOT artifact layer (and anything else
# under parallel/compile_cache.cache_root) lands in a directory of this
# run's own, so no test reads what an earlier run left. XLA's own
# persistent cache stays off on the CPU (trainer/bootstrap.py).
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_cache_dir = tempfile.mkdtemp(prefix="dlrover_tpu_test_cache_")
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import signal  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): abort the test with a TimeoutError if it runs "
        "longer than the given number of seconds (SIGALRM-based; main "
        "thread only, like the reference's pytest-timeout usage)",
    )


# Modules that spawn the elastic example as subprocesses AND clean up
# with broad `pkill -f <example>` patterns: under OPT-IN xdist
# (`-n 2 --dist loadgroup`; serial is the default — see pytest.ini)
# those pkills would kill a SIBLING worker's children, so they all pin
# to one worker via xdist_group. Measured r5: two workers on this
# one-core host save only ~10% wall clock (jax compiles are CPU-bound)
# and the sibling's compiles can starve these very e2e jobs.
_E2E_GROUP_FILES = {
    "test_buddy.py", "test_chaos.py", "test_e2e.py", "test_goodput.py",
    "test_hang_detector.py", "test_multinode_e2e.py",
    "test_node_relaunch_e2e.py", "test_preemption_e2e.py",
    "test_soak.py",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if os.path.basename(str(item.fspath)) in _E2E_GROUP_FILES:
            item.add_marker(pytest.mark.xdist_group("elastic_e2e"))


def _alarm_guard(item):
    """SIGALRM guard for one test phase, honoring ``@pytest.mark.timeout``.

    pytest-timeout is not vendored in this image; without this guard the
    mark would be silently inert and one wedged e2e subprocess could hang
    the whole suite forever. setitimer (not alarm) so fractional-second
    timeouts work.
    """
    marker = item.get_closest_marker("timeout")
    if marker is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    seconds = float(marker.args[0]) if marker.args else float(
        marker.kwargs.get("seconds", 300)
    )
    if seconds <= 0:
        raise ValueError(
            f"{item.nodeid}: timeout mark must be positive, got {seconds}"
        )

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded its {seconds}s timeout mark"
        )

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


# Cover every phase a test can wedge in — fixture setup and teardown hang
# just as hard as the call body (pytest-timeout covers all three too).
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _alarm_guard(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _alarm_guard(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _alarm_guard(item)


@pytest.fixture()
def tmp_ipc_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_IPC_DIR", str(tmp_path / "ipc"))
    return tmp_path


@pytest.fixture(autouse=True)
def _kv_page_ledger_guard():
    """§31 conservation invariant, asserted after EVERY test that
    touched a serving engine: each physical KV page is exactly one of
    free or leased-with-positive-refcount, and the COW sharing index
    round-trips. Keyed off sys.modules so the ~90% of tests that never
    import the serving engine pay nothing. Replica threads may still be
    retiring when the test body returns, so one short retry absorbs
    in-flight teardown before the failure is real."""
    yield
    import sys
    import time as _time

    em = sys.modules.get("dlrover_tpu.serving.engine")
    if em is None:
        return
    bad = em.check_kv_ledgers()
    if bad:
        _time.sleep(0.05)
        bad = em.check_kv_ledgers()
    assert not bad, f"kv page ledger violated: {bad}"
