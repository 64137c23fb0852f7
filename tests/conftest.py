import os

# Smoke tests and benches must see ONE device; only launch/dryrun.py sets
# xla_force_host_platform_device_count (as its first two lines).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# FABRIC_SANITIZE=1 turns every lock the fabric creates into a tracked
# proxy feeding a global acquisition-order graph, so the whole suite
# doubles as a deadlock detector.  Install BEFORE jax/test imports so no
# fabric lock predates the patch (stdlib/third-party locks are never
# wrapped).  See docs/concurrency.md.
from repro.analysis import sanitizer as _sanitizer  # noqa: E402

_SANITIZE = _sanitizer.enabled_by_env()
if _SANITIZE:
    _SAN_GRAPH = _sanitizer.install()

from concurrent.futures import Future  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _fabric_sanitize_check():
    """Under FABRIC_SANITIZE=1, fail the test that first produced a lock
    ordering violation or an acquisition-graph cycle."""
    yield
    if _SANITIZE:
        _SAN_GRAPH.assert_clean()


class FakeClock:
    """Deterministic test clock for keep-alive / reap / demotion timing.

    Callable, so it drops straight into ``InstancePool(..., clock=clock)``
    (or a live ``pool.clock = clock``); tests then move time explicitly
    with ``advance``/``set`` instead of sleeping.  Pure state, no
    threading — hypothesis-driven tests construct it directly
    (``from conftest import FakeClock``) since ``@given`` cannot take
    function-scoped fixtures."""

    def __init__(self, start: float = 0.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> float:
        self.now += seconds
        return self.now

    def set(self, t: float) -> float:
        self.now = t
        return self.now


@pytest.fixture
def fake_clock() -> FakeClock:
    return FakeClock()


def sync_batches(handler):
    """``EndpointBatcher`` ``run_batch`` that runs ``handler(payloads)``
    on the batcher's thread and returns an already-resolved Future."""
    def run_batch(payloads):
        fut = Future()
        try:
            fut.set_result(handler(payloads))
        except BaseException as e:       # noqa: BLE001
            fut.set_exception(e)
        return fut
    return run_batch
