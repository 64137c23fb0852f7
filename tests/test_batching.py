"""Endpoint batcher lifecycle without a fabric signal (no ``capacity``):
the close()/flush race (queued requests must never be dropped) and
partial-batch deadline handling.  Pure-python handlers, timing-robust
margins."""
import threading
import time
from concurrent.futures import wait

import pytest

from conftest import sync_batches

from repro.serving import EndpointBatcher


def _echo_handler(payloads):
    return list(payloads)


@pytest.mark.parametrize("rep", range(3))
def test_close_flushes_all_queued_requests(rep):
    """Regression: close() used to set a stop flag and join, abandoning
    anything still queued — callers hung forever on their Futures."""
    started = threading.Event()

    def slow_handler(payloads):
        started.set()
        time.sleep(0.05)
        return list(payloads)

    b = EndpointBatcher("t", sync_batches(slow_handler), batch_size=4,
                        max_wait=0.2)
    futs = [b.submit(i) for i in range(11)]
    started.wait(timeout=5)
    b.close()                          # worker mid-batch, 7 still queued
    done, not_done = wait(futs, timeout=10)
    assert not not_done, "close() dropped queued requests"
    assert sorted(f.result() for f in futs) == list(range(11))
    assert b.stats()["requests"] == 11


def test_submit_after_close_raises():
    b = EndpointBatcher("t", sync_batches(_echo_handler), batch_size=2)
    f = b.submit("x")
    b.close()
    assert f.result(timeout=5) == "x"
    with pytest.raises(RuntimeError):
        b.submit("y")
    b.close()                          # idempotent


def test_partial_batch_flushes_at_deadline():
    """A lone request must flush ~max_wait after arrival, not wait for the
    batch to fill."""
    b = EndpointBatcher("t", sync_batches(_echo_handler), batch_size=8,
                        max_wait=0.05)
    t0 = time.monotonic()
    f = b.submit("only")
    assert f.result(timeout=5) == "only"
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0               # deadline honored, no indefinite wait
    b.close()                          # joins the worker: its counts landed
    assert b.batch_fill[-1] == 1
    assert b.stats()["dispatch"]["deadline"] == 1


def test_trickling_requests_do_not_extend_deadline():
    """The flush deadline is anchored at the OLDEST queued request; a
    trickle arriving every ~max_wait/2 must not postpone it forever."""
    b = EndpointBatcher("t", sync_batches(_echo_handler), batch_size=64,
                        max_wait=0.1)
    stop = threading.Event()

    def trickle():
        while not stop.is_set():
            b.submit("t")
            time.sleep(0.04)

    th = threading.Thread(target=trickle, daemon=True)
    th.start()
    deadline = time.monotonic() + 5.0
    while b.stats()["batches"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    th.join(timeout=5)
    assert b.stats()["batches"] >= 1, "trickle starved the flush deadline"
    assert max(b.batch_fill) < 64      # flushed partial, on time
    b.close()


def test_full_batches_and_handler_errors():
    """A handler error fails only the batch it ran; the next batch
    resolves normally."""
    calls = {"n": 0}

    def handler(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("boom")
        return [p * 2 for p in payloads]

    b = EndpointBatcher("t", sync_batches(handler), batch_size=2,
                        max_wait=0.02)
    f1, f2 = b.submit(1), b.submit(2)
    with pytest.raises(ValueError):
        f1.result(timeout=5)
    with pytest.raises(ValueError):
        f2.result(timeout=5)
    f3, f4 = b.submit(3), b.submit(4)
    assert f3.result(timeout=5) == 6 and f4.result(timeout=5) == 8
    b.close()
    assert calls["n"] == 2
    assert b.stats()["batches"] == 1   # only the batch that resolved counts
