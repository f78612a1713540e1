"""The one general traffic generator. A mix is a data file under
``traffic/`` that names its ``loop`` and that loop's parameters:

- ``closed``: batches of the configuration's query batch, issued back to
  back with at most ``inflight`` batches dispatched and not yet fenced
  (raft-ann-bench's throughput mode). The window ends at the fence of
  the last batch issued before ``--seconds`` ran out, so the rate counts
  all the work and all the time.
- ``open``: one query per request at the times of a Poisson process of
  ``rate_per_s``, conditioned on its count: ``round(rate * seconds)``
  arrival times drawn uniformly over the window from the seed and sorted,
  so every seed offers the same number of requests. Each request is timed
  from when it was due, not from when it was sent.

Host spans (``jax.profiler.TraceAnnotation``) mark what the generator is
doing, so a traced run can say what the host did while the chip idled:
``bench.dispatch``, ``bench.fence``, ``bench.submit``, ``bench.sleep``.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def closed(call, n_distinct: int, seconds: float, inflight: int) -> dict:
    """Run ``call(b)`` for b = 0, 1, ... (input ``b % n_distinct``) until
    ``seconds`` have passed, keeping at most ``inflight`` calls unfenced.
    Returns the outputs in issue order, their input numbers, the window's
    length and the time each call was fenced."""
    import jax

    outs, which, done = [], [], []
    pending = collections.deque()
    t0 = time.perf_counter()
    stop = t0 + seconds
    b = 0

    def fence_oldest():
        j, out = pending.popleft()
        with _span("bench.fence"):
            jax.block_until_ready(out)
        done.append(time.perf_counter())

    while time.perf_counter() < stop:
        with _span("bench.dispatch"):
            out = call(b % n_distinct)
        outs.append(out)
        which.append(b % n_distinct)
        pending.append((b, out))
        b += 1
        if len(pending) >= inflight:
            fence_oldest()
    while pending:
        fence_oldest()
    return {"outs": outs, "which": which, "t0": t0,
            "window_s": done[-1] - t0, "done": done}


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson process of
    ``rate_per_s`` over ``seconds``, conditioned on its expected count."""
    n = int(round(rate_per_s * seconds))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    return np.sort(rng.uniform(0.0, seconds, n))


def open_loop(submit, queries: np.ndarray, picks: np.ndarray,
              due: np.ndarray, k: int, wait_s: float = 60.0) -> dict:
    """Submit ``queries[picks[i]]`` at ``due[i]`` seconds after the start
    from one generator thread; a request that is due while the generator
    is behind goes at once. ``submit(query, k)`` returns a future whose
    result is ``(distances, ids)``. Waits up to ``wait_s`` past the last
    due time for every answer. Latency runs from the due time to the
    moment the answer was set; a request that was refused (``submit``
    raised, or its future holds an error) or never came has none (NaN). Answers are copied into preallocated arrays as they come
    and no future is kept, so the run holds no growing heap of objects
    for the collector to walk."""
    n = len(due)
    t_done = np.full(n, np.nan)
    t_sent = np.full(n, np.nan)
    dists = np.zeros((n, k), np.float32)
    ids = np.full((n, k), -1, np.int64)
    answered = np.zeros(n, bool)
    refused = np.zeros(n, bool)
    errors = []
    all_done = threading.Event()
    remaining = [n]
    lock = threading.Lock()

    def settle():
        with lock:
            remaining[0] -= 1
            if remaining[0] == 0:
                all_done.set()

    def on_done(i, fut):
        try:
            d, j = fut.result(timeout=0)
        except BaseException as e:  # noqa: B036 — recorded, not raised
            errors.append(f"{type(e).__name__}: {e}")
            refused[i] = True
        else:
            t_done[i] = time.perf_counter()
            dists[i], ids[i] = d, j
            answered[i] = True
        settle()

    t0 = time.perf_counter()

    def generate():
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                with _span("bench.sleep"):
                    time.sleep(wait)
            t_sent[i] = time.perf_counter()
            try:
                with _span("bench.submit"):
                    fut = submit(queries[picks[i]], k)
            except Exception as e:  # refused: counts as failed
                errors.append(f"{type(e).__name__}: {e}")
                refused[i] = True
                settle()
                continue
            fut.add_done_callback(lambda f, i=i: on_done(i, f))

    if n == 0:
        all_done.set()
    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    gen.join()
    all_done.wait(max(t0 + (due[-1] if n else 0) + wait_s
                      - time.perf_counter(), 0.0))
    t_end = time.perf_counter()
    return {"dists": dists, "ids": ids, "answered": answered.copy(),
            "refused": refused.copy(),
            "latency_s": t_done - (t0 + due), "late_s": t_sent - (t0 + due),
            "errors": list(errors), "t0": t0,
            "window_s": float(np.nanmax(t_done) - t0) if answered.any()
            else t_end - t0}


def percentile(latency_s: np.ndarray, q: float) -> float:
    """The ``q``-th percentile (nearest rank, no interpolation) over every
    request; a request with no answer counts as infinitely late."""
    lat = np.where(np.isfinite(latency_s), latency_s, np.inf)
    if not len(lat):
        return float("nan")
    srt = np.sort(lat)
    rank = int(np.ceil(q / 100.0 * len(srt))) - 1
    return float(srt[min(max(rank, 0), len(srt) - 1)])
