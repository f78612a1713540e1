"""The traffic generator: the same seed gives the same traffic, and an
open-loop request is timed from when it was due."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from benchmark import loops


def test_arrivals_fixed_by_the_seed():
    a = loops.arrivals(500.0, 2.0, 2**35 + 1)
    b = loops.arrivals(500.0, 2.0, 2**35 + 1)
    c = loops.arrivals(500.0, 2.0, 2**35 + 2)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 1000  # every seed offers the same count
    assert not np.array_equal(a, c)
    assert (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] <= 2.0


def test_due_time_latency_counts_a_stall():
    """The server stalls 0.2 s on the first request; the requests due
    during the stall are late by what remains of it, although each is
    answered at once once sent."""
    due = np.array([0.0, 0.05, 0.1, 0.15, 0.5])
    queries = np.zeros((1, 2), np.float32)
    picks = np.zeros(len(due), int)
    first = [True]

    def submit(q, k):
        if first[0]:
            first[0] = False
            time.sleep(0.2)  # the generator is stuck in the server
        f = Future()
        f.set_running_or_notify_cancel()
        f.set_result((np.zeros(k), np.zeros(k, int)))
        return f

    res = loops.open_loop(submit, queries, picks, due, 3, wait_s=5)
    lat = res["latency_s"]
    assert res["answered"].all() and (res["ids"] == 0).all()
    assert lat[0] >= 0.2
    assert lat[1] >= 0.14 and lat[2] >= 0.09 and lat[3] >= 0.04
    assert lat[4] < 0.05
    assert res["late_s"][1] >= 0.14  # the generator's own lateness
    assert loops.percentile(lat, 50) == pytest.approx(np.sort(lat)[2])


def test_failed_request_is_infinitely_late():
    def submit(q, k):
        raise RuntimeError("shed")

    res = loops.open_loop(submit, np.zeros((1, 2)), np.zeros(2, int),
                          np.array([0.0, 0.01]), 3, wait_s=1)
    assert not res["answered"].any() and len(res["errors"]) == 2
    assert loops.percentile(res["latency_s"], 99) == float("inf")


def test_closed_loop_keeps_inflight_and_counts_all():
    import jax.numpy as jnp

    live = []
    lock = threading.Lock()

    def call(j):
        with lock:
            live.append(j)
        return jnp.full((4,), j)

    res = loops.closed(call, 3, 0.2, 2)
    assert len(res["outs"]) == len(res["which"]) == len(res["done"])
    assert res["which"][:4] == [0, 1, 2, 0]
    assert res["window_s"] >= 0.2
