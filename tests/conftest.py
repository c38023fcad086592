"""Shared test configuration: a per-test wall-clock timeout guard.

``pytest-timeout`` is not available in this container, so the guard uses
SIGALRM (no-op on platforms without it).  The default keeps any single test
from stalling the tier-1 verify loop; override per test with
``@pytest.mark.timeout(seconds)`` or the REPRO_TEST_TIMEOUT env var.
"""
import os
import signal

import pytest

DEFAULT_TIMEOUT = int(os.environ.get("REPRO_TEST_TIMEOUT", "120"))

# The suite must be hermetic: a warm ~/.cache/repro-hls from an earlier run
# (or another test) would skip compiles that tests count (e.g. the
# hls.data_pairs_enumerated probes).  The persistent compile cache is therefore
# OFF for every test; dedicated cache tests re-enable it against a tmpdir
# via monkeypatch (REPRO_HLS_CACHE=1 + REPRO_HLS_CACHE_DIR).
os.environ["REPRO_HLS_CACHE"] = "0"

# No fault plan leaks in from the calling environment: chaos tests opt in
# explicitly via repro.core.faults.inject(...).
os.environ.pop("REPRO_HLS_FAULTS", None)


@pytest.fixture(autouse=True)
def _fault_free():
    """Reset the fault-injection harness around every test so a failing
    chaos test can never leave a plan armed for its neighbours."""
    from repro.core import faults
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _timeout_guard(request):
    if not hasattr(signal, "SIGALRM"):
        yield
        return
    marker = request.node.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args else DEFAULT_TIMEOUT
    if request.node.get_closest_marker("slow"):
        seconds = max(seconds, 600)

    def _on_alarm(signum, frame):
        pytest.fail(f"timeout guard: test exceeded {seconds}s", pytrace=False)

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
