"""Serving-engine load generator: closed-loop and open-loop (Poisson)
benchmarks of raft_tpu.serving against the b1-dispatch baseline.

Measures, per index family (brute_force / ivf_flat / ivf_pq / cagra):

- ``baseline_b1``: the naive request path — one query per search, host
  sync per call (what every concurrent user pays today without the
  engine). Also a chained-latency variant that amortizes the readback
  round trip (the device-latency floor).
- ``closed_loop``: N submitter threads, each submit→result→next through
  one Engine. QPS, speedup vs b1, recall, and a full bit-identity sweep:
  every coalesced result is compared against a solo search of the same
  query at the same bucket shape and row (``serving.solo_reference``).
- ``open_loop``: Poisson arrivals at fractions of the closed-loop QPS;
  per-rate p50/p95/p99 queue-wait / device / total latency and achieved
  throughput — the latency-throughput curve whose knee is the per-replica
  capacity number the ROADMAP's traffic story needs.
- ``overload``: Poisson arrivals at a MULTIPLE of capacity (default 2x)
  against an engine with tight admission watermarks and per-request
  deadlines — the docs/serving.md "Overload & failure semantics" story
  measured: shed rate, goodput, and the p99 of ADMITTED requests, which
  must stay within ~2x of the at-capacity p99 instead of diverging with
  the queue. Every shed is a typed rejection (Overloaded / QueueFull /
  DeadlineExceeded); an untyped wait-timeout fails the run.
- ``fleet`` (first family only): Poisson arrivals at 10x ONE replica's
  capacity against a 3-replica :class:`~raft_tpu.serving.fleet.Fleet`
  while a rolling swap of every replica runs mid-load and two replicas
  are killed mid-run — the docs/serving.md "Fleet" story measured:
  exact typed accounting (every submitted request resolves ok / typed
  shed / typed failure; zero silent losses), ``kind="fleet"`` spans
  reconciling 1:1 under one trace id per request, the swap completing
  with zero drops, and the quorum gauge never below its threshold
  (``--fleet-replicas 0`` disables the arm).
- ``adaptive``: the same 2x overload against an engine with an
  ``raft_tpu.planner.AdaptivePlanner`` (the committed
  ``PARETO_<platform>.json``, or an inline mini sweep when the platform
  has none): batches degrade nprobe/itopk to fit their riders' remaining
  deadlines instead of shedding — goodput must meet or beat the
  shed-only baseline while shadow-sampled online recall stays at or
  above the ``--recall-floor``, with every operating-point choice
  attributed in ``raft_tpu_adaptive_choice_total`` (``--no-adaptive``
  skips the arm).

- ``mutable_soak``: writer threads upsert/delete a
  :class:`~raft_tpu.neighbors.mutable.MutableIvf` while submitters
  search it through a full Engine and a background Compactor publishes
  re-clustered bases via hot swap — zero untyped failures, zero dropped
  requests, and post-soak recall within ``--soak-tolerance`` of a
  freshly rebuilt brute-force oracle over the surviving rows
  (``--soak-writes 0`` disables the arm).

Telemetry (docs/observability.md): every engine in the bench runs with a
span sink writing ``<out>.spans.jsonl`` (one record per request with its
trace id, phase decomposition, and typed outcome; ``--spans ''``
disables). After each family the span file is read back and reconciled
against the engines' counters — ok spans must equal completed requests.
For the first family the bench also measures the cost of that
instrumentation: best-of-N closed-loop QPS with the full telemetry stack
on (span sink + shadow sampling) vs off, asserted < 2% apart
(``--no-overhead-check`` skips the gate, ``--overhead-tolerance`` moves
it). A ``--shadow-sample`` arm (default 5%) re-runs the closed loop with
online recall estimation against a brute-force oracle and gates the
online estimate within ``--shadow-tolerance`` (default ±0.02) of the
offline ground-truth recall for ivf_flat and ivf_pq.

Artifact: SERVING_cpu.json / SERVING_tpu.json (name follows the measured
platform unless --out is given).

Usage::

    JAX_PLATFORMS=cpu python tools/serving_bench.py --families ivf_flat
    python tools/serving_bench.py            # all families, active backend
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_family(family, db, res):
    """Build one index + serving searcher at bench-shaped parameters."""
    from raft_tpu import serving
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    t0 = time.perf_counter()
    if family == "brute_force":
        index = brute_force.build(db, metric="sqeuclidean", res=res)
        searcher = serving.brute_force_searcher(index, res=res)
    elif family == "ivf_flat":
        index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=128),
                               res=res)
        searcher = serving.ivf_flat_searcher(
            index, ivf_flat.SearchParams(n_probes=32), res=res)
    elif family == "ivf_pq":
        index = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=128, pq_dim=32),
                             res=res)
        searcher = serving.ivf_pq_searcher(
            index, ivf_pq.SearchParams(n_probes=32), res=res)
    elif family == "cagra":
        index = cagra.build(db, cagra.IndexParams(
            graph_degree=32, intermediate_graph_degree=64), res=res)
        searcher = serving.cagra_searcher(
            index, cagra.SearchParams(itopk_size=64, search_width=4),
            res=res)
    else:
        raise ValueError(f"unknown family {family!r}")
    return searcher, round(time.perf_counter() - t0, 2)


def bench_baseline_b1(searcher, queries, k):
    """Sequential single-query dispatch with a host sync per call — the
    per-request path a request handler without the engine runs."""
    from raft_tpu.bench import timing

    # warm the b1 bucket (engine warmup already compiled it; this is for
    # a standalone run of only this function)
    timing.fence(searcher.search(queries[:1], k))
    indices = []
    t0 = time.perf_counter()
    for q in queries:
        d, i = searcher.search(q[None], k)
        indices.append(np.asarray(i)[0])  # per-call sync: the naive path
    elapsed = time.perf_counter() - t0
    # RTT-amortized chained variant: the device-latency floor (the
    # readback is paid once, bench/timing.py)
    q0 = timing.prepare(queries[:1])
    chained_s = timing.time_latency_chained(
        lambda qq: timing.chain_perturb(q0, searcher.search(qq, k)),
        q0, iters=8)
    return {
        "qps": round(len(queries) / elapsed, 1),
        "mean_ms": round(elapsed / len(queries) * 1e3, 3),
        "chained_ms": round(chained_s * 1e3, 3),
    }, np.stack(indices)


def bench_closed_loop(engine, queries, k, submitters):
    """N threads, each submit→result→next over its share of ``queries``.
    Returns (summary, indices in query order, placements)."""
    shares = np.array_split(np.arange(len(queries)), submitters)
    results = [None] * len(queries)
    placements = [None] * len(queries)
    barrier = threading.Barrier(submitters + 1)

    def worker(ids):
        barrier.wait()
        for qi in ids:
            fut = engine.submit(queries[qi], k)
            results[qi] = fut.result()
            placements[qi] = fut.placement

    threads = [threading.Thread(target=worker, args=(ids,))
               for ids in shares if len(ids)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    indices = np.stack([r[1] for r in results])
    summary = {
        "submitters": submitters,
        "n": len(queries),
        "qps": round(len(queries) / elapsed, 1),
        "mean_ms": round(elapsed / len(queries) * submitters * 1e3, 3),
    }
    return summary, indices, results, placements


def bench_open_loop(engine, queries, k, rate_qps, n_requests, rng):
    """Poisson arrivals at ``rate_qps``; per-request latency percentiles
    from the engine's ServingStats over exactly this run's samples."""
    engine.stats.reset_samples()
    futs = []
    gaps = rng.exponential(1.0 / rate_qps, n_requests)
    t0 = time.perf_counter()
    next_t = t0
    for j in range(n_requests):
        next_t += gaps[j]
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        futs.append(engine.submit(queries[j % len(queries)], k))
    for f in futs:
        f.result()
    elapsed = time.perf_counter() - t0
    snap = engine.stats.snapshot()
    row = {
        "offered_qps": round(rate_qps, 1),
        "achieved_qps": round(n_requests / elapsed, 1),
        "n": n_requests,
        "mean_batch_size": snap.get("mean_batch_size"),
    }
    for key in ("queue_wait_ms", "device_ms", "total_ms"):
        if key in snap:
            row[key] = snap[key]
    return row


def bench_overload(engine, queries, k, rate_qps, n_requests, rng,
                   deadline_ms=None):
    """Open-loop Poisson at ``rate_qps`` with non-blocking admission and
    an optional per-request deadline. Unlike :func:`bench_open_loop`,
    arrivals past capacity are EXPECTED to shed — the contract measured
    here is that every shed is a typed rejection, never a silent drop or
    an untyped timeout, and that the admitted requests' latency stays
    bounded by the admission watermarks + deadline instead of growing
    with the backlog."""
    from concurrent.futures import TimeoutError as FutTimeout

    from raft_tpu import serving
    from raft_tpu.serving.batcher import DeadlineExceeded, QueueFull

    engine.stats.reset_samples()
    shed = {"breaker": 0, "overload": 0, "queue_full": 0, "deadline": 0}
    futs = []
    gaps = rng.exponential(1.0 / rate_qps, n_requests)
    t0 = time.perf_counter()
    next_t = t0
    for j in range(n_requests):
        next_t += gaps[j]
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        try:
            futs.append(engine.submit(queries[j % len(queries)], k,
                                      block=False,
                                      deadline_ms=deadline_ms))
        except serving.CircuitOpen:
            shed["breaker"] += 1
        except serving.Overloaded:
            shed["overload"] += 1
        except QueueFull:
            shed["queue_full"] += 1
    served = 0
    for f in futs:
        try:
            # generous completion bound: the engine must resolve every
            # admitted future (served or typed-shed) long before this —
            # hitting it means a request was neither, which is the bug
            # the chaos suite exists to prevent
            f.result(timeout=120)
            served += 1
        except DeadlineExceeded:
            shed["deadline"] += 1
        except FutTimeout:
            raise AssertionError(
                "admitted request neither served nor typed-shed within "
                "120 s — untyped timeout, shed contract broken") from None
    elapsed = time.perf_counter() - t0
    snap = engine.stats.snapshot()
    n_shed = sum(shed.values())
    assert served + n_shed == n_requests  # no silent drops
    row = {
        "offered_qps": round(rate_qps, 1),
        "n": n_requests,
        "served": served,
        "shed": shed,
        "shed_rate": round(n_shed / n_requests, 4),
        "goodput_qps": round(served / elapsed, 1),
        "deadline_ms": deadline_ms,
        "mean_batch_size": snap.get("mean_batch_size"),
    }
    if "total_ms" in snap:
        row["admitted_total_ms"] = snap["total_ms"]
    return row


def bench_fleet(searcher, cfg_kwargs, queries, k, capacity_qps,
                phase_queries, rng, replicas=3, kills=2, factor=10.0,
                max_batch=64, sink=None):
    """Fleet arm: Poisson open-loop at ``factor``x ONE replica's
    measured closed-loop capacity against a ``replicas``-wide
    :class:`~raft_tpu.serving.fleet.Fleet`, while the run degrades it on
    purpose — a rolling swap of every replica mid-load, then ``kills``
    staggered replica kills (docs/serving.md "Fleet").

    The contracts asserted here are the fleet's whole reason to exist:

    - exact accounting — every submitted request resolves to ok, a
      typed shed, or a typed failure; an untyped wait-timeout or an
      unexpected exception type fails the run (zero silent losses),
      and the ``raft_tpu_fleet_requests_total`` outcome counters must
      reconcile exactly (submitted == sum of resolutions, ok == served);
    - the rolling swap completes all ``replicas`` rotations under load
      with zero drops (no skipped replica, every displaced handle
      returned);
    - the quorum gauge (sampled via ``healthy_count()``, the same
      callback ``raft_tpu_fleet_quorum_healthy`` reads) never dips
      below the configured threshold at any point in the run.

    Arrival pacing is phase-driven, not a fixed count: ``phase_queries``
    arrivals warm the overload, then arrivals continue for as long as
    the swap is in flight (so the drain + warm happen under real
    traffic), then ``phase_queries`` more after each kill and a final
    tail. Span reconciliation (one ``kind="fleet"`` record per request
    under one trace id) happens in ``main`` from the JSONL file.

    Returns ``(row, fleet_engine_completed)`` — the second term feeds
    the caller's engine-level span/counter reconciliation.
    """
    import dataclasses as _dc
    from concurrent.futures import TimeoutError as FutTimeout

    from raft_tpu import serving
    from raft_tpu.testing import faults

    if not 0 < kills < replicas:
        raise ValueError(f"need 0 < kills < replicas, got {kills} of "
                         f"{replicas}")
    quorum = replicas - kills
    rate = factor * capacity_qps
    # one handle per replica over the SAME built index (a Searcher is a
    # stateless shallow view; replicas must not share the handle object
    # itself or a swap/injector on one would touch all)
    engine_cfg = serving.EngineConfig(
        queue_limit=max(4 * max_batch, 64),
        queue_high_watermark=max_batch, **cfg_kwargs)
    fleet = serving.Fleet.from_searchers(
        [_dc.replace(searcher) for _ in range(replicas)],
        engine_config=engine_cfg,
        config=serving.FleetConfig(quorum=quorum, span_sink=sink))
    fleet.start()

    samples = {"min": replicas, "n": 0}
    stop_sampling = threading.Event()

    def sampler():
        while not stop_sampling.is_set():
            samples["min"] = min(samples["min"], fleet.healthy_count())
            samples["n"] += 1
            time.sleep(0.002)

    futs = []
    state = {"next_t": time.perf_counter()}

    def pump(n=None, until=None, max_n=None):
        j = 0
        while (j < n if n is not None else
               (max_n is None or j < max_n)):
            if until is not None and until():
                break
            state["next_t"] += rng.exponential(1.0 / rate)
            now = time.perf_counter()
            if state["next_t"] > now:
                time.sleep(state["next_t"] - now)
            elif state["next_t"] < now - 0.5:
                state["next_t"] = now  # cap the arrival debt
            futs.append(fleet.submit(queries[len(futs) % len(queries)],
                                     k))
            j += 1
        return j

    swap_info = {}

    def do_swap():
        t0 = time.perf_counter()
        displaced = fleet.rolling_swap(
            [_dc.replace(searcher) for _ in range(replicas)], warm=True)
        swap_info["duration_s"] = round(time.perf_counter() - t0, 3)
        swap_info["displaced"] = displaced

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()
    t0 = time.perf_counter()
    killed = []
    try:
        pump(n=phase_queries)                 # all replicas healthy
        swap_t = threading.Thread(target=do_swap)
        swap_t.start()
        # load DURING the swap; the drain makes the swap's duration
        # load-dependent, so bound the arrivals and SAY SO when the
        # bound engages (the swap then finishes against a quiet fleet
        # instead of the run growing without limit)
        swap_cap = 20 * phase_queries
        swap_pumped = pump(until=lambda: not swap_t.is_alive(),
                           max_n=swap_cap)
        swap_load_capped = swap_pumped >= swap_cap
        if swap_load_capped:
            print(f"  fleet: swap outlived the load window "
                  f"({swap_pumped} arrivals) — remainder drains "
                  f"unloaded", flush=True)
        swap_t.join()
        in_flight_at_kill = []
        for i in range(kills):
            victim = replicas - 1 - i         # replica0 survives the run
            in_flight_at_kill.append(
                len(fleet.replicas[victim].engine.batcher))
            faults.kill_replica(fleet, victim)
            killed.append(fleet.replicas[victim].name)
            pump(n=phase_queries)             # load on the shrunken fleet
        pump(n=phase_queries)                 # tail
        n_total = len(futs)

        served = 0
        shed = {}
        untyped = 0
        for f in futs:
            try:
                # same generous bound as bench_overload: hitting it
                # means a request was neither served nor typed-shed —
                # exactly the silent loss the fleet must never produce
                f.result(timeout=120)
                served += 1
            except FutTimeout:
                raise AssertionError(
                    "fleet request neither served nor typed-shed "
                    "within 120 s — untyped timeout, shed contract "
                    "broken") from None
            except (serving.Overloaded, serving.QueueFull,
                    serving.BatchFailed, serving.EngineStopped,
                    serving.DeadlineExceeded,
                    serving.IntegrityError) as e:
                kind = serving.failure_kind(e)
                shed[kind] = shed.get(kind, 0) + 1
            except BaseException:
                untyped += 1
        elapsed = time.perf_counter() - t0
        assert untyped == 0, (
            f"{untyped} requests resolved with an UNTYPED exception — "
            "every fleet failure must be classifiable by isinstance")
        n_shed = sum(shed.values())
        assert served + n_shed == n_total  # zero silent losses

        assert fleet.drain(120), "fleet did not quiesce after the run"
        counts = fleet.stats.outcome_counts()
        resolved = sum(v for ev, v in counts.items()
                       if ev != "submitted")
        assert counts["submitted"] == n_total == resolved, (
            f"fleet counters do not reconcile: submitted="
            f"{counts['submitted']}, resolved={resolved}, "
            f"futures={n_total}")
        assert counts["ok"] == served, (
            f"ok counter {counts['ok']} != served futures {served}")

        assert swap_info.get("displaced") is not None, (
            "rolling swap did not complete during the run")
        skipped = sum(1 for d in swap_info["displaced"] if d is None)
        assert skipped == 0, (
            f"rolling swap skipped {skipped} replicas — expected all "
            f"{replicas} rotations to land before the kills")
    finally:
        stop_sampling.set()
        sampler_t.join()
        fleet.stop(drain=False)
    assert samples["min"] >= quorum, (
        f"quorum gauge dipped to {samples['min']} < threshold {quorum}")

    fleet_completed = sum(r.engine.stats.n_completed
                          for r in fleet.replicas)
    row = {
        "replicas": replicas,
        "quorum": quorum,
        "factor": factor,
        "offered_qps": round(rate, 1),
        "n": n_total,
        "served": served,
        "shed": shed,
        "shed_rate": round(n_shed / n_total, 4),
        "goodput_qps": round(served / elapsed, 1),
        "outcomes": counts,
        "rolling_swap": {"swapped": replicas,
                         "duration_s": swap_info["duration_s"],
                         "arrivals_during": swap_pumped,
                         "load_capped": swap_load_capped},
        "kills": {"replicas": killed,
                  "in_flight_at_kill": in_flight_at_kill},
        "quorum_gauge": {"min": samples["min"], "threshold": quorum,
                         "samples": samples["n"]},
    }
    return row, fleet_completed


def bench_remote_fleet(dim, k, base_port=None, chaos_n=40, kill_at=10,
                       up_window_s=0.6, down_window_s=2.5):
    """Remote-fleet arm (docs/serving.md "Remote fleet"): one local
    replica plus one real ``replica_main`` child process over loopback
    ``host_p2p``, with the :class:`~raft_tpu.serving.autoscaler.
    Autoscaler` as a live actuator. Three contracts, each the remote
    stack's reason to exist:

    - **stepped load curve** — a sustained overload step (slowed local
      searcher + bursts) must grow the fleet within ~one ``up_window_s``
      of hysteresis, attributed by a ``kind="autoscale"`` span with
      reason ``scale_up_pressure``; going quiet must shrink it again
      ONLY after the full ``down_window_s`` cooldown
      (``scale_down_idle``), and the ``spawned``/``retired`` lifecycle
      counters must reconcile 1:1 with those spans. The windows are
      scoped by ``reset_samples()`` on every replica — the remote one
      re-baselines over the wire (the ``reset_samples`` op), which is
      what lets pressure FALL when offered load falls;
    - **kill -9 chaos** — SIGKILL of the child mid-load yields ZERO
      untyped failures: every future resolves served or to a typed
      failure from the closed transport table, and
      ``submitted == sum(outcomes)`` exactly;
    - **span accounting** — one ``kind="fleet"`` span per request under
      a unique trace id, ok spans == ok counter, across ALL phases
      including the partition.

    Self-contained: builds its own deterministic index (the same
    ``replica_main.build_searcher`` spec on both sides, so siblings are
    bit-identical) and reconciles against its own span sink.
    """
    import random as _random
    import signal
    import subprocess
    import sys

    from raft_tpu import serving
    from raft_tpu.obs import spans as obs_spans
    from raft_tpu.parallel.host_p2p import HostP2P
    from raft_tpu.serving.replica_main import build_searcher
    from raft_tpu.testing import faults

    spec = {"family": "brute_force", "dim": dim, "rows": 1024, "seed": 0}
    engine_cfg = serving.EngineConfig(
        max_batch=16, max_wait_us=500, deadline_budget_ms=20.0,
        warm_ks=(k,))
    base_port = base_port or _random.randint(42000, 55000)
    sink = obs_spans.ListSink()

    # the replica child must be a separate process (the kill -9 chaos
    # needs one), and the chip belongs to one process — so the child is
    # pinned to the CPU and this arm says so in what it prints
    print("serving_bench: remote arm: replica 'remote1' is a CPU replica "
          "in a child process (JAX_PLATFORMS=cpu), not the chip",
          file=sys.stderr)
    child = subprocess.Popen(
        [sys.executable, "-m", "raft_tpu.serving.replica_main",
         "--rank", "1", "--size", "2", "--base-port", str(base_port),
         "--family", spec["family"], "--dim", str(dim),
         "--rows", str(spec["rows"]), "--seed", str(spec["seed"]),
         "--max-batch", "16", "--max-wait-us", "2000",
         "--peer-grace", "1.0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    t0 = time.perf_counter()
    ready = False
    for line in child.stdout:
        if "REPLICA_READY" in line:
            ready = True
            break
        if time.perf_counter() - t0 > 90:
            break
    if not ready:
        child.kill()
        raise AssertionError("replica child never became ready")

    ep0 = HostP2P(rank=0, size=2, base_port=base_port, peer_grace=1.0)
    proxy = serving.RemoteReplica(ep0, peer=1, dim=dim, name="remote1",
                                  rpc_timeout_s=10.0, rpc_slack_s=1.0)
    local = serving.Engine(build_searcher(spec), engine_cfg)
    fleet = serving.Fleet(
        [local, proxy], names=["local0", "remote1"],
        config=serving.FleetConfig(quorum=1, probe_interval_s=0.25,
                                   span_sink=sink))
    futs = []
    row = {}
    try:
        fleet.start()

        # ---- warm: cross-process traffic + sibling bit-identity
        rng = np.random.default_rng(7)
        warm_q = rng.standard_normal(dim).astype(np.float32)
        d0, i0 = proxy.submit(warm_q, k, deadline_ms=10_000).result(60)
        d1, i1 = local.submit(warm_q, k, deadline_ms=10_000).result(60)
        assert np.array_equal(np.asarray(i0), np.asarray(i1)) and \
            np.allclose(np.asarray(d0), np.asarray(d1)), (
                "remote and local siblings disagree on the same query — "
                "the shared build spec did not produce identical indexes")
        for _ in range(10):
            futs.append(fleet.submit(
                rng.standard_normal(dim).astype(np.float32), k,
                deadline_ms=10_000))

        # ---- stepped load curve under a live autoscaler
        asc = serving.Autoscaler(
            fleet,
            spawn=lambda: serving.Engine(build_searcher(spec),
                                         engine_cfg),
            config=serving.AutoscalerConfig(
                min_replicas=2, max_replicas=3, high_watermark=0.8,
                low_watermark=0.2, up_window_s=up_window_s,
                down_window_s=down_window_s, tick_s=0.05,
                span_sink=sink))
        for r in fleet.replicas:
            r.engine.stats.reset_samples()
        asc.start()
        t_high = time.perf_counter()
        with faults.slow_searcher(local.searcher, 0.012):
            while len(fleet.replicas) < 3:
                for _ in range(24):
                    futs.append(fleet.submit(
                        rng.standard_normal(dim).astype(np.float32), k))
                time.sleep(0.02)
                assert time.perf_counter() - t_high < 30, (
                    "sustained overload never triggered a scale-up")
        rise_s = time.perf_counter() - t_high
        assert rise_s <= up_window_s + 15.0, (
            f"scale-up took {rise_s:.2f}s — not within one hysteresis "
            f"window of the load step (window {up_window_s}s)")
        typed = (serving.Overloaded, serving.QueueFull,
                 serving.BatchFailed, serving.EngineStopped,
                 serving.DeadlineExceeded, serving.IntegrityError)
        for f in futs:  # drain the high step; typed sheds recount below
            try:
                f.result(timeout=120)
            except typed:
                pass
        # quiesce, then re-baseline EVERY window — remote over the wire
        for r in fleet.replicas:
            r.engine.stats.reset_samples()
        proxy.scrape(timeout=10)  # fresh piggyback carries window=0
        t_low = time.perf_counter()
        while len(fleet.replicas) > 2:  # silence: pressure reads 0.0
            time.sleep(0.05)
            assert time.perf_counter() - t_low < down_window_s + 20, (
                "idle fleet never scaled back down")
        fall_s = time.perf_counter() - t_low
        asc.stop()
        assert fall_s >= down_window_s, (
            f"scale-down after {fall_s:.2f}s — inside the "
            f"{down_window_s}s cooldown, hysteresis violated")
        ascs = sink.by_kind("autoscale")
        reasons = {}
        for rec in ascs:
            reasons[rec["reason"]] = reasons.get(rec["reason"], 0) + 1
        assert reasons.get("scale_up_pressure", 0) == 1, reasons
        assert reasons.get("scale_down_idle", 0) == 1, reasons
        assert reasons.get("spawn_failed", 0) == 0, reasons
        lc = {ev: fleet.stats._lifecycle[ev].value
              for ev in ("spawned", "retired", "spawn_failed")}
        assert lc["spawned"] == reasons["scale_up_pressure"], (lc, reasons)
        assert lc["retired"] == reasons["scale_down_idle"], (lc, reasons)
        assert lc["spawn_failed"] == 0, lc

        # ---- kill -9 the child mid-load: typed or served, nothing else
        n_before_chaos = len(futs)
        served = untyped = 0
        shed = {}
        for i in range(chaos_n):
            if i == kill_at:
                os.kill(child.pid, signal.SIGKILL)
            futs.append(fleet.submit(
                rng.standard_normal(dim).astype(np.float32), k,
                deadline_ms=2000))
            time.sleep(0.01)
        for f in futs:
            try:
                f.result(timeout=120)
                served += 1
            except typed as e:
                kind = serving.failure_kind(e)
                shed[kind] = shed.get(kind, 0) + 1
            except BaseException:
                untyped += 1
        assert untyped == 0, (
            f"{untyped} requests resolved UNTYPED after kill -9 — the "
            "closed transport table leaked")
        n_total = len(futs)
        assert served + sum(shed.values()) == n_total

        # ---- exact counter + span reconciliation across all phases
        counts = fleet.stats.outcome_counts()
        resolved = sum(v for ev, v in counts.items() if ev != "submitted")
        assert counts["submitted"] == n_total == resolved, (
            f"counters do not reconcile: {counts} vs {n_total} futures")
        assert counts["ok"] == served, (counts, served)
        fspans = sink.by_kind("fleet")
        traces = {rec["trace_id"] for rec in fspans}
        ok_spans = sum(1 for rec in fspans if rec["outcome"] == "ok")
        assert len(fspans) == n_total == len(traces), (
            f"fleet spans do not reconcile 1:1: {len(fspans)} spans / "
            f"{len(traces)} trace ids for {n_total} requests")
        assert ok_spans == served, (ok_spans, served)

        row = {
            "remote_replica_platform": "cpu",
            "n": n_total,
            "served": served,
            "shed": shed,
            "untyped": untyped,
            "chaos": {"kill": "SIGKILL", "at": n_before_chaos + kill_at,
                      "arrivals_after": chaos_n},
            "autoscale": {
                "rise_s": round(rise_s, 3),
                "up_window_s": up_window_s,
                "fall_s": round(fall_s, 3),
                "down_window_s": down_window_s,
                "reasons": reasons,
                "lifecycle": lc,
            },
            "outcomes": counts,
            "spans": {"records": len(fspans), "trace_ids": len(traces),
                      "ok": ok_spans},
        }
    finally:
        try:
            fleet.stop(drain=False)
        finally:
            ep0.close()
            child.kill()
            child.wait(timeout=30)
    return row


def make_planner(family, k, db, queries, artifact_path, recall_floor,
                 res):
    """AdaptivePlanner for the adaptive-overload arm: the committed
    ``PARETO_<platform>.json`` when it covers (family, k), else an
    inline mini sweep on the bench's own data (CI machines without a
    committed artifact for their platform still measure the policy)."""
    from raft_tpu.planner import (AdaptivePlanner, Frontier,
                                  sweep as planner_sweep)

    planner = AdaptivePlanner.from_artifact(artifact_path,
                                            recall_floor=recall_floor)
    if planner.frontier is not None and planner.warm_points(family, int(k)):
        return planner, f"artifact:{artifact_path}"
    fam = planner_sweep.sweep_family(family, db, queries[:64], [int(k)],
                                     [8, 64], mini=True, res=res)
    doc = planner_sweep.build_artifact("inline", {family: fam})
    return AdaptivePlanner(Frontier(doc),
                           recall_floor=recall_floor), "inline_mini_sweep"


def bench_adaptive_overload(searcher, overload_cfg, planner, queries, k,
                            rate_qps, n_requests, rng, deadline_ms,
                            oracle, shadow_rate=0.25):
    """The degrade-instead-of-shed arm: the same Poisson overload as
    :func:`bench_overload`, against an engine whose batches resolve
    their operating point from the riders' remaining deadlines
    (docs/serving.md "Degradation vs shedding"). Shadow sampling grades
    the degraded answers online, so the row carries proof that goodput
    was not bought below the recall floor."""
    import dataclasses as _dc

    from raft_tpu import serving
    from raft_tpu.planner.adaptive import adaptive_choice_counts

    before = dict(adaptive_choice_counts())
    cfg = _dc.replace(overload_cfg, planner=planner,
                      shadow_oracle=oracle, shadow_sample_rate=shadow_rate,
                      shadow_deadline_ms=30_000.0, shadow_queue_limit=256)
    engine = serving.Engine(searcher, cfg)
    engine.start()
    try:
        over = bench_overload(engine, queries, k, rate_qps, n_requests,
                              rng, deadline_ms=deadline_ms)
    finally:
        engine.stop()
    choices = {}
    for (fam, reason), n in adaptive_choice_counts().items():
        delta = n - before.get((fam, reason), 0)
        if fam == searcher.family and delta:
            choices[reason] = delta
    online = None
    if engine.shadow is not None:
        est = engine.shadow.estimator.snapshot()
        n_total = sum(n for n, _ in est.values())
        if n_total:
            online = round(sum(n * mean for n, mean in est.values())
                           / n_total, 4)
    over["choices"] = choices
    over["online_recall"] = online
    over["recall_floor"] = planner.recall_floor
    over["calibration_scale"] = round(planner.calibration.scale, 4)
    return over


class _TaggedSink:
    """Stamps every span record with the family before forwarding, so
    one spans file serves the whole bench and reads back per-family."""

    def __init__(self, inner, family):
        self._inner = inner
        self._family = family

    def emit(self, record):
        record["family"] = self._family
        self._inner.emit(record)


def bench_telemetry_overhead(searcher, cfg_kwargs, queries, k, submitters,
                             reps, tmpdir, shadow_oracle=None,
                             shadow_rate=0.0):
    """Best-of-``reps`` closed-loop QPS with the full telemetry stack on
    (span sink writing JSONL + shadow sampling at ``shadow_rate``) vs
    telemetry-silent, arms alternated per rep so thermal/load drift hits
    both equally. The registry counters and the per-search explain
    attribution stay on in both arms (they are not optional); the
    measured delta is the span-emission + shadow-sampling hot-path
    cost — the oracle itself runs on the shadow worker thread, and what
    this gate bounds is what that background work steals from serving."""
    from raft_tpu import serving
    from raft_tpu.obs import spans as obs_spans

    def one_run(sink, rate):
        eng = serving.Engine(searcher, serving.EngineConfig(
            span_sink=sink, shadow_oracle=shadow_oracle if rate else None,
            shadow_sample_rate=rate, **cfg_kwargs))
        eng.start()
        try:
            summary, _, _, _ = bench_closed_loop(eng, queries, k,
                                                 submitters)
        finally:
            eng.stop()
        return summary["qps"]

    rate = shadow_rate if shadow_oracle is not None else 0.0
    qps = {"plain": 0.0, "telemetry": 0.0}
    for rep in range(reps):
        qps["plain"] = max(qps["plain"], one_run(None, 0.0))
        path = os.path.join(tmpdir, f"overhead_{rep}.jsonl")
        with obs_spans.JsonlSink(path) as sink:
            qps["telemetry"] = max(qps["telemetry"], one_run(sink, rate))
    overhead = 1.0 - qps["telemetry"] / qps["plain"]
    return {
        "reps": reps,
        "shadow_rate": rate,
        "qps_plain": qps["plain"],
        "qps_telemetry": qps["telemetry"],
        "overhead": round(overhead, 4),
    }


def make_exact_oracle(db):
    """Exact sqeuclidean top-k oracle for the shadow worker — pure
    numpy on purpose. A jitted oracle (e.g. ``brute_force.knn``) would
    recompile per distinct batch shape on the worker thread and compete
    with serving for the same dispatch path, so the overhead gate would
    measure XLA compile storms instead of the telemetry plumbing it
    claims to bound. Production oracles that do run on-device should pad
    to a fixed query shape for the same reason (docs/observability.md)."""
    db = np.asarray(db, np.float32)
    db_sq = (db * db).sum(axis=1)

    def oracle(qs, k):
        qs = np.asarray(qs, np.float32)
        # |q|^2 is constant per row: rank-equivalent, skip it
        d = db_sq[None, :] - 2.0 * (qs @ db.T)
        idx = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        top = np.take_along_axis(d, idx, axis=1)
        order = np.argsort(top, axis=1, kind="stable")
        return (np.take_along_axis(top, order, axis=1),
                np.take_along_axis(idx, order, axis=1))

    return oracle


def bench_shadow_recall(searcher, cfg_kwargs, queries, k, submitters,
                        rate, oracle, gt, passes=3):
    """Closed loop with shadow sampling on: the engine grades ``rate``
    of its completed batches against the exact ``oracle`` on the shadow
    worker, and this returns the online estimate next to the offline
    ground-truth recall of everything actually served. ``passes``
    repeats the query set so a 5% sample still lands enough batches for
    the windowed mean to settle. The shed counters ride along: a shed-
    heavy row means the estimate is biased toward calm periods (see
    docs/observability.md) and the deadline/queue knobs need air."""
    from raft_tpu import serving
    from raft_tpu.stats import neighborhood_recall

    eng = serving.Engine(searcher, serving.EngineConfig(
        shadow_oracle=oracle, shadow_sample_rate=rate,
        # bench grading is offline-quality analysis, not SLO freshness:
        # give the oracle air so sheds reflect pressure, not the gap
        # between serving QPS and a CPU oracle
        shadow_deadline_ms=30_000.0, shadow_queue_limit=256,
        **cfg_kwargs))
    eng.start()
    try:
        tiled = np.concatenate([queries] * passes)
        closed, idx, _, _ = bench_closed_loop(eng, tiled, k, submitters)
    finally:
        eng.stop()  # closes the sampler: queued samples drain first
    est = eng.shadow.estimator.snapshot()
    n_total = sum(n for n, _ in est.values())
    online = (sum(n * mean for n, mean in est.values()) / n_total
              if n_total else None)
    offline = float(neighborhood_recall(idx, np.concatenate([gt] * passes)))
    return {
        "rate": rate,
        "passes": passes,
        "qps": closed["qps"],
        "samples": n_total,
        "online_recall": round(online, 4) if online is not None else None,
        "offline_recall": round(offline, 4),
        "delta": (round(abs(online - offline), 4)
                  if online is not None else None),
        "shadow": eng.stats.shadow_counts,
    }


def bench_tiered(db, queries, k, res, rng, pressures=(2.0, 8.0),
                 n_requests=200, n_lists=256, n_probes=4, max_batch=8):
    """HBM-as-cache arm: the same index served through ``TieredIvfPq``
    at 2x and 8x arena pressure (``n_lists / arena_slots``), a full
    Engine with the batcher-driven :class:`~raft_tpu.neighbors.tiered.
    TierPrefetcher` attached, and the deadline/shed policy engaged.

    What the row gates:

    - **exact typed accounting** — every arrival is served or a typed
      shed (``bench_overload``'s assertion), no untyped failures;
    - **tier_hit_rate** (higher-better bench_gate token) — demand hits
      over demand resolutions, straight off the arena counters, which
      must themselves reconcile exactly (hits + misses + prefetch_hits
      + prefetch_fetches == resolved);
    - **fetch_stall_p50_ms / _p99_ms** (lower-better ``_ms`` tokens) —
      host→device copy stalls measured from the arena's own
      ``tier_fetch`` spans, demand path only (prefetch stalls overlap
      device time by design and are reported separately).

    The per-batch distinct-list bound ``query_bucket(max_batch) *
    n_probes`` sizes the deepest arena so the arm can never trip
    ``TieredArenaError`` — that ceiling is printed, not silent.
    """
    from raft_tpu import serving
    from raft_tpu.neighbors import ivf_pq, tiered
    from raft_tpu.obs import spans as obs_spans
    from raft_tpu.serving.stats import percentiles
    from raft_tpu.utils.shape import query_bucket

    t0 = time.perf_counter()
    index = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=n_lists, pq_dim=32),
                         res=res)
    build_s = round(time.perf_counter() - t0, 2)
    params = ivf_pq.SearchParams(n_probes=n_probes)
    # a bucketed batch resolves at most this many distinct lists; every
    # arena below must hold one full batch or the demand path raises
    distinct_bound = min(n_lists, query_bucket(max_batch) * n_probes)
    out = {"build_s": build_s, "n_lists": n_lists, "n_probes": n_probes,
           "max_batch": max_batch, "distinct_bound": distinct_bound,
           "runs": []}
    extra = {}
    for pressure in pressures:
        slots = max(int(round(n_lists / pressure)), distinct_bound)
        if slots * pressure != n_lists:
            print(f"  tiered: pressure {pressure}x floored to "
                  f"{n_lists / slots:.1f}x by the per-batch distinct "
                  f"bound ({distinct_bound} lists)", flush=True)
        sink = obs_spans.ListSink()
        arena = tiered.SlabArena(
            slots, int(index.list_codes.shape[1]), index.rot_dim,
            label=f"bench{pressure:g}x", span_sink=sink)
        t = tiered.TieredIvfPq.from_index(index, res=res, arena=arena,
                                          namespace=f"bench{pressure:g}x")
        searcher = serving.tiered_ivf_pq_searcher(t, params, res=res)
        engine = serving.Engine(searcher, serving.EngineConfig(
            max_batch=max_batch, max_wait_us=2000, max_inflight=2,
            warm_ks=(k,), queue_limit=max(4 * max_batch, 64),
            queue_high_watermark=max_batch))
        engine.start()
        pf = tiered.attach_prefetcher(engine, t, params=params)
        try:
            base = arena.snapshot_counts()
            closed, _, _, _ = bench_closed_loop(engine, queries, k, 4)
            cap_qps = closed["qps"]
            over = bench_overload(engine, queries, k, 2.0 * cap_qps,
                                  n_requests, rng, deadline_ms=2000.0)
        finally:
            pf.close()
            engine.stop()
        counts = arena.snapshot_counts()
        phase = {key: counts[key] - base.get(key, 0)
                 for key in counts if key != "occupancy"}
        # the reconciliation the interleave suite pins, re-checked live:
        # a bench row with unaccounted resolutions is a finding, not data
        assert (phase["hits"] + phase["misses"] + phase["prefetch_hits"]
                + phase["prefetch_fetches"] == phase["resolved"]), phase
        demand = phase["hits"] + phase["misses"]
        hit_rate = phase["hits"] / demand if demand else None
        stalls_ms = {
            path: sorted(float(s["stall_s"]) * 1e3 for s in sink.records
                         if s.get("kind") == "tier_fetch"
                         and s.get("path") == path)
            for path in ("demand", "prefetch")}
        demand_pcts = percentiles(stalls_ms["demand"]) \
            if stalls_ms["demand"] else {}
        row = {
            "pressure": round(n_lists / slots, 2),
            "arena_slots": slots,
            "arena_bytes": arena.nbytes,
            "closed_loop_qps": cap_qps,
            "overload": over,
            "counts": phase,
            "occupancy": counts["occupancy"],
            "tier_hit_rate": round(hit_rate, 4) if hit_rate is not None
            else None,
            "demand_fetches": len(stalls_ms["demand"]),
            "prefetch_fetches_spanned": len(stalls_ms["prefetch"]),
            "prefetcher": {"passes": pf.n_passes, "capped": pf.n_capped,
                           "errors": pf.n_errors},
        }
        if demand_pcts:
            row["fetch_stall_p50_ms"] = round(demand_pcts["p50"], 3)
            row["fetch_stall_p99_ms"] = round(demand_pcts["p99"], 3)
        if pf.n_capped:
            print(f"  tiered: prefetch depth cap engaged {pf.n_capped} "
                  f"times — staged coverage was partial", flush=True)
        out["runs"].append(row)
        fam = f"tiered_{pressure:g}x"
        extra[fam] = {"goodput_qps": over["goodput_qps"]}
        if hit_rate is not None:
            extra[fam]["tier_hit_rate"] = round(hit_rate, 4)
        for key in ("fetch_stall_p50_ms", "fetch_stall_p99_ms"):
            if key in row:
                extra[fam][key] = row[key]
        print(f"  tiered @{row['pressure']}x pressure: "
              f"hit_rate={row['tier_hit_rate']}, "
              f"stall p99={row.get('fetch_stall_p99_ms')} ms, "
              f"shed_rate={over['shed_rate']}, "
              f"prefetch useful={phase['useful_prefetch']}", flush=True)
    return out, extra


def bench_mutable_soak(db, queries, k, res, rng, *, writers=2,
                       writes_per_writer=150, submitters=4,
                       max_batch=8, tolerance=0.02, sink=None):
    """Mixed read/write soak: writer threads upsert/delete through a
    :class:`~raft_tpu.neighbors.mutable.MutableIvf` while submitter
    threads search it through a full Engine and a background Compactor
    re-clusters and publishes via hot swap — the docs/robustness.md
    "Write path & recovery" story under live traffic.

    What the row gates:

    - **zero untyped failures** — every search resolves with a result
      or a typed :class:`~raft_tpu.core.errors.RaftError`; every write
      acks or raises typed; any other exception fails the arm;
    - **zero dropped requests** — submits in equals results out,
      across however many hot swaps the compactor publishes mid-soak;
    - **shadow recall vs a fresh oracle** — after the soak quiesces,
      the engine's served answers over the FINAL state are graded
      against a freshly rebuilt brute-force oracle on the surviving
      rows; recall must sit within ``tolerance`` of exact. The search
      params probe every list, so this measures the merged
      base+delta+tombstone read path, not clustering luck;
    - **counter/span reconciliation** — ``compactions_total`` equals
      the ``kind="compaction"`` span count, and acks equal writes.
    """
    import tempfile

    from raft_tpu import serving
    from raft_tpu.core.errors import RaftError
    from raft_tpu.neighbors import ivf_flat, mutable
    from raft_tpu.obs import metrics as obs_metrics
    from raft_tpu.obs import spans as obs_spans

    dim = db.shape[1]
    n_lists = 16
    reg = obs_metrics.Registry()
    span_sink = obs_spans.ListSink()
    td = tempfile.TemporaryDirectory()
    w = mutable.MutableIvf(
        os.path.join(td.name, "soak"), dim=dim, registry=reg,
        span_sink=span_sink, name="soak",
        index_params=ivf_flat.IndexParams(n_lists=n_lists),
        search_params=ivf_flat.SearchParams(n_probes=n_lists))
    seed_rows = len(db) // 2
    w.add(np.asarray(db[:seed_rows], np.float32))
    oracle_lock = threading.Lock()
    oracle_state = {i: np.asarray(db[i], np.float32)
                    for i in range(seed_rows)}

    searcher = serving.mutable_ivf_searcher(w, res=res)
    eng = serving.Engine(searcher, serving.EngineConfig(
        max_batch=max_batch, max_wait_us=2000, warm_ks=(k,),
        span_sink=sink))
    untyped, typed = [], []
    served = [0]
    stop = threading.Event()

    def writer_thread(tid):
        trng = np.random.default_rng(1000 + tid)
        pool = list(range(seed_rows + tid, len(db), writers))
        try:
            for i in range(writes_per_writer):
                if trng.random() < 0.25 and i > 4:
                    victim = int(pool[int(trng.integers(len(pool)))])
                    with oracle_lock:
                        if victim not in oracle_state:
                            continue
                        del oracle_state[victim]
                    w.delete([victim])
                else:
                    id_ = int(pool[int(trng.integers(len(pool)))])
                    vec = np.asarray(db[id_], np.float32) \
                        + trng.standard_normal(dim).astype(np.float32) * 0.01
                    with oracle_lock:
                        oracle_state[id_] = vec
                    w.upsert(vec[None, :], [id_])
        except RaftError as e:
            typed.append(e)
        except Exception as e:  # noqa: BLE001 — the zero-untyped gate
            untyped.append(e)

    def submit_thread(tid):
        trng = np.random.default_rng(2000 + tid)
        try:
            while not stop.is_set():
                q = queries[int(trng.integers(len(queries)))]
                eng.submit(np.asarray(q, np.float32), k).result(timeout=60)
                served[0] += 1
        except RaftError as e:
            typed.append(e)
        except Exception as e:  # noqa: BLE001
            untyped.append(e)

    comp = mutable.Compactor(w, publish=eng, delta_threshold=64,
                             tombstone_ratio=0.1, poll_s=0.01, min_rows=8)
    t0 = time.perf_counter()
    with eng:
        comp.start()
        try:
            wthreads = [threading.Thread(target=writer_thread, args=(t,))
                        for t in range(writers)]
            sthreads = [threading.Thread(target=submit_thread, args=(t,))
                        for t in range(submitters)]
            for t in wthreads + sthreads:
                t.start()
            for t in wthreads:
                t.join()
            stop.set()
            for t in sthreads:
                t.join()
        finally:
            comp.stop()
        soak_s = time.perf_counter() - t0
        assert not untyped, f"untyped failures in soak: {untyped!r}"

        # quiesced read pass over the FINAL state, graded against a
        # freshly rebuilt exact oracle on the rows that survived
        with oracle_lock:
            final = sorted(oracle_state.items())
        live_ids = np.asarray([i for i, _ in final], np.int64)
        live_rows = np.stack([v for _, v in final])
        oracle = make_exact_oracle(live_rows)
        grade_q = queries[: min(len(queries), 128)]
        _, oracle_pos = oracle(np.asarray(grade_q, np.float32), k)
        want = live_ids[oracle_pos]
        futs = [eng.submit(np.asarray(q, np.float32), k) for q in grade_q]
        got = np.stack([np.asarray(f.result(timeout=60)[1]).ravel()
                        for f in futs])
        hits = sum(len(set(g.tolist()) & set(ww.tolist()))
                   for g, ww in zip(got, want))
        recall = hits / float(want.size)
        generations = eng.searcher_generation

    n_writes = int(sum(c.value for _, c in reg.get(
        "raft_tpu_mutable_writes_total").collect()))
    n_acks = int(sum(c.value for _, c in reg.get(
        "raft_tpu_mutable_acks_total").collect()))
    comp_spans = [s for s in span_sink.records if s["kind"] == "compaction"]
    n_comp = int(sum(c.value for _, c in reg.get(
        "raft_tpu_mutable_compactions_total").collect()))
    assert n_acks == n_writes, (
        f"{n_writes} writes but {n_acks} acks — a write neither acked "
        f"nor raised typed")
    assert n_comp == len(comp_spans), (
        f"compaction counters ({n_comp}) and spans ({len(comp_spans)}) "
        f"do not reconcile 1:1")
    assert recall >= 1.0 - tolerance, (
        f"soak recall {recall:.4f} fell more than {tolerance} below the "
        f"fresh oracle — the merged base+delta+tombstone read path is "
        f"losing rows")
    w.close()
    td.cleanup()
    return {
        "soak_s": round(soak_s, 2),
        "writers": writers,
        "writes": n_writes,
        "acks": n_acks,
        "searches": served[0],
        "typed_failures": len(typed),
        "untyped_failures": len(untyped),
        "live_rows": len(live_ids),
        "compactions": n_comp,
        "compaction_spans": len(comp_spans),
        "swaps": generations if isinstance(generations, int) else None,
        "recall_vs_fresh_oracle": round(recall, 4),
        "tolerance": tolerance,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="artifact path (default SERVING_<platform>.json)")
    ap.add_argument("--families", nargs="*", default=[
        "brute_force", "ivf_flat", "ivf_pq", "cagra"])
    ap.add_argument("--rows", type=int, default=10_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--submitters", type=int, default=8)
    ap.add_argument("--queries-per-submitter", type=int, default=50)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--max-inflight", type=int, default=2)
    ap.add_argument("--open-loop-fractions", type=float, nargs="*",
                    default=[0.25, 0.5, 0.75, 0.9])
    ap.add_argument("--open-loop-queries", type=int, default=200)
    ap.add_argument("--overload-factors", type=float, nargs="*",
                    default=[2.0, 12.0],
                    help="overload scenario offered loads as multiples "
                         "of measured closed-loop capacity (2x is the "
                         "acceptance point; the deep factor pushes past "
                         "what coalescing + max_inflight*max_batch "
                         "in-flight slots absorb, so the watermark shed "
                         "actually engages; empty disables)")
    ap.add_argument("--overload-queries", type=int, default=300)
    ap.add_argument("--fleet-replicas", type=int, default=3,
                    help="fleet arm (first family only): replicas in "
                         "the chaos fleet; 0 disables the arm")
    ap.add_argument("--fleet-kills", type=int, default=2,
                    help="replicas killed mid-run in the fleet arm "
                         "(must stay below --fleet-replicas; the "
                         "difference is the quorum threshold)")
    ap.add_argument("--fleet-factor", type=float, default=10.0,
                    help="fleet arm offered load as a multiple of ONE "
                         "replica's closed-loop capacity")
    ap.add_argument("--fleet-queries", type=int, default=400,
                    help="fleet arm arrivals per phase (warm-up, after "
                         "each kill, tail); the swap phase is paced by "
                         "the swap itself")
    ap.add_argument("--no-remote-fleet", action="store_true",
                    help="skip the two-process remote-fleet arm "
                         "(replica_main child over loopback host_p2p: "
                         "autoscaler stepped-curve tracking + kill -9 "
                         "typed accounting)")
    ap.add_argument("--remote-fleet-port", type=int, default=0,
                    help="base port for the remote-fleet arm's host_p2p "
                         "pair (0 picks a random high port)")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the per-request bit-identity sweep")
    ap.add_argument("--spans", default=None,
                    help="span JSONL path (default <out>.spans.jsonl; "
                         "'' disables span emission)")
    ap.add_argument("--overhead-reps", type=int, default=3,
                    help="best-of-N reps per arm of the telemetry "
                         "overhead measurement")
    ap.add_argument("--overhead-tolerance", type=float, default=0.02,
                    help="maximum allowed closed-loop QPS loss with the "
                         "span sink enabled (fraction)")
    ap.add_argument("--no-overhead-check", action="store_true",
                    help="skip the telemetry overhead measurement + gate "
                         "(noisy shared machines)")
    ap.add_argument("--shadow-sample", type=float, default=0.05,
                    help="shadow sampling rate for the online-recall arm "
                         "(0 disables the arm)")
    ap.add_argument("--shadow-passes", type=int, default=3,
                    help="closed-loop passes over the query set in the "
                         "shadow arm (more passes -> more graded samples)")
    ap.add_argument("--shadow-tolerance", type=float, default=0.02,
                    help="max |online - offline| recall gap gated for "
                         "ivf_flat / ivf_pq")
    ap.add_argument("--no-adaptive", action="store_true",
                    help="skip the adaptive (degrade-vs-shed) overload "
                         "arm")
    ap.add_argument("--pareto", default=None,
                    help="committed Pareto artifact for the adaptive arm "
                         "(default PARETO_<platform>.json next to this "
                         "script's repo; missing -> inline mini sweep)")
    ap.add_argument("--recall-floor", type=float, default=0.9,
                    help="adaptive arm: degradation never picks a point "
                         "below this recall")
    ap.add_argument("--tiered-pressures", type=float, nargs="*",
                    default=[2.0, 8.0],
                    help="HBM-as-cache arm arena pressures (n_lists / "
                         "arena_slots); empty disables the arm")
    ap.add_argument("--tiered-queries", type=int, default=200,
                    help="tiered arm overload-phase arrivals per "
                         "pressure level")
    ap.add_argument("--soak-writes", type=int, default=150,
                    help="mutable soak arm: writes per writer thread "
                         "(0 disables the arm)")
    ap.add_argument("--soak-writers", type=int, default=2,
                    help="mutable soak arm: concurrent writer threads")
    ap.add_argument("--soak-tolerance", type=float, default=0.02,
                    help="mutable soak arm: max recall gap vs the "
                         "freshly rebuilt exact oracle")
    args = ap.parse_args()

    if os.environ.get("RAFT_TPU_BENCH_PLATFORM", "default") != "default":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    from raft_tpu import Resources, serving
    from raft_tpu.bench.datagen import low_rank_clusters
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    platform = jax.devices()[0].platform
    out_path = args.out or f"SERVING_{platform}.json"
    rng = np.random.default_rng(0)
    n_q = args.submitters * args.queries_per_submitter
    both = low_rank_clusters(rng, args.rows + n_q, args.dim, n_centers=64)
    db, queries = both[:args.rows], both[args.rows:]
    res = Resources(seed=0)
    _, gt_j = brute_force.knn(queries, db, k=args.k, metric="sqeuclidean",
                              res=res)
    gt = np.asarray(gt_j)

    from raft_tpu.obs import spans as obs_spans

    cfg_kwargs = dict(
        max_batch=args.max_batch, max_wait_us=args.max_wait_us,
        max_inflight=args.max_inflight, warm_ks=(args.k,))
    spans_path = args.spans if args.spans is not None \
        else out_path + ".spans.jsonl"
    # JsonlSink appends; the reconciliation below assumes this run's
    # spans only, so a leftover file from a prior run must not survive
    if spans_path and os.path.exists(spans_path):
        os.remove(spans_path)
    spans_sink = obs_spans.JsonlSink(spans_path) if spans_path else None
    art = {
        "platform": platform,
        "rows": args.rows, "dim": args.dim, "k": args.k,
        "config": {"max_batch": args.max_batch,
                   "max_wait_us": args.max_wait_us,
                   "max_inflight": args.max_inflight},
        "spans": spans_path or None,
        "families": {},
    }

    for fi, family in enumerate(args.families):
        print(f"=== {family}", flush=True)
        searcher, build_s = build_family(family, db, res)
        row = {"build_s": build_s}
        fam_sink = _TaggedSink(spans_sink, family) if spans_sink else None
        config = serving.EngineConfig(span_sink=fam_sink, **cfg_kwargs)
        base, base_idx = bench_baseline_b1(searcher, queries, args.k)
        base["recall"] = round(
            float(neighborhood_recall(base_idx, gt)), 4)
        row["baseline_b1"] = base
        print(f"  b1 baseline: {base}", flush=True)

        engine = serving.Engine(searcher, config)
        engine.start()
        row["warmup"] = engine.warmup_info
        try:
            closed, idx, results, placements = bench_closed_loop(
                engine, queries, args.k, args.submitters)
            closed["recall"] = round(float(neighborhood_recall(idx, gt)), 4)
            closed["speedup_vs_b1"] = round(closed["qps"] / base["qps"], 2)
            closed["stats"] = engine.stats.snapshot()
            if not args.no_verify:
                mismatches = serving.verify_bit_identity(
                    searcher, queries, results, args.k, placements)
                closed["verified"] = len(results)
                closed["mismatches"] = mismatches
                closed["bit_identical"] = mismatches == 0
            row["closed_loop"] = closed
            print(f"  closed loop: qps={closed['qps']} "
                  f"({closed['speedup_vs_b1']}x b1), "
                  f"recall={closed['recall']}, "
                  f"mismatches={closed.get('mismatches')}", flush=True)

            row["open_loop"] = []
            for frac in args.open_loop_fractions:
                rate = max(closed["qps"] * frac, 1.0)
                ol = bench_open_loop(engine, queries, args.k, rate,
                                     args.open_loop_queries, rng)
                row["open_loop"].append(ol)
                print(f"  open loop @{ol['offered_qps']} qps: "
                      f"total p99={ol.get('total_ms', {}).get('p99')} ms",
                      flush=True)
        finally:
            engine.stop()
        completed_total = engine.stats.n_completed

        if args.overload_factors and "closed_loop" in row:
            # fresh engine with the shedding knobs engaged: the high
            # watermark admits ONE full batch of backlog, so an admitted
            # request waits at most ~one batch-time behind the one in
            # flight — queue latency stays bounded by design, not luck.
            # (The serving default of 16*max_batch is for engines sized
            # well below capacity; max_batch-64 coalescing absorbs many
            # multiples of the closed-loop rate before a deep queue
            # would even move, as the factor sweep below shows.)
            overload_cfg = serving.EngineConfig(
                max_batch=args.max_batch, max_wait_us=args.max_wait_us,
                max_inflight=args.max_inflight, warm_ks=(args.k,),
                queue_limit=max(4 * args.max_batch, 64),
                queue_high_watermark=args.max_batch,
                span_sink=fam_sink)
            ov_engine = serving.Engine(searcher, overload_cfg)
            ov_engine.start()
            try:
                cap = row["closed_loop"]["qps"]
                at_cap = bench_overload(ov_engine, queries, args.k, cap,
                                        args.overload_queries, rng)
                p99_cap = at_cap.get("admitted_total_ms", {}).get("p99")
                deadline_ms = (round(1.5 * p99_cap, 1) if p99_cap
                               else None)
                row["overload"] = {
                    "capacity_qps": cap,
                    "queue_high_watermark":
                        overload_cfg.queue_high_watermark,
                    "queue_limit": overload_cfg.queue_limit,
                    "deadline_ms": deadline_ms,
                    "at_capacity": at_cap,
                    "runs": [],
                }
                for factor in args.overload_factors:
                    over = bench_overload(
                        ov_engine, queries, args.k, factor * cap,
                        args.overload_queries, rng,
                        deadline_ms=deadline_ms)
                    p99_over = over.get("admitted_total_ms", {}).get(
                        "p99")
                    # the load-shedding claim: the p99 an ADMITTED
                    # request sees stays bounded as offered load grows —
                    # overload turns into shed rate, not tail latency
                    over["factor"] = factor
                    over["admitted_p99_ratio_vs_capacity"] = (
                        round(p99_over / p99_cap, 2)
                        if p99_cap and p99_over else None)
                    row["overload"]["runs"].append(over)
                    print(f"  overload @{factor}x: "
                          f"shed_rate={over['shed_rate']}, "
                          f"goodput={over['goodput_qps']} qps, "
                          f"admitted p99 {p99_over} ms "
                          f"({over['admitted_p99_ratio_vs_capacity']}x "
                          f"of at-capacity {p99_cap} ms)", flush=True)
            finally:
                ov_engine.stop()
            completed_total += ov_engine.stats.n_completed

            if not args.no_adaptive and deadline_ms is not None:
                # degrade-vs-shed: same 2x Poisson overload + deadlines,
                # but the engine spends each batch's remaining budget on
                # recall instead of serving static params and shedding
                pareto_path = args.pareto or os.path.join(
                    os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    f"PARETO_{platform}.json")
                planner, source = make_planner(
                    family, args.k, db, queries, pareto_path,
                    args.recall_floor, res)
                factor = (2.0 if 2.0 in args.overload_factors
                          else args.overload_factors[0])
                ada = bench_adaptive_overload(
                    searcher, overload_cfg, planner, queries, args.k,
                    factor * cap, args.overload_queries, rng,
                    deadline_ms, make_exact_oracle(db))
                shed_run = next(
                    (r for r in row["overload"]["runs"]
                     if r.get("factor") == factor), None)
                ada["factor"] = factor
                ada["frontier_source"] = source
                if shed_run is not None:
                    ada["goodput_vs_shed_only"] = round(
                        ada["goodput_qps"]
                        / max(shed_run["goodput_qps"], 1e-9), 3)
                row["overload"]["adaptive"] = ada
                completed_total += ada["served"]
                print(f"  adaptive @{factor}x: goodput="
                      f"{ada['goodput_qps']} qps "
                      f"({ada.get('goodput_vs_shed_only')}x shed-only), "
                      f"online recall {ada['online_recall']} "
                      f"(floor {args.recall_floor}), "
                      f"choices={ada['choices']}", flush=True)
                # every decision is visible, never below the floor
                assert sum(ada["choices"].values()) > 0, (
                    "adaptive arm ran but no choice was attributed")
                if (family in ("ivf_flat", "ivf_pq")
                        and ada["online_recall"] is not None):
                    assert ada["online_recall"] >= args.recall_floor \
                        - args.shadow_tolerance, (
                        f"adaptive goodput bought below the floor: "
                        f"online recall {ada['online_recall']} < "
                        f"{args.recall_floor}")
                if (family in ("ivf_flat", "ivf_pq")
                        and shed_run is not None
                        and shed_run["shed_rate"] > 0.05):
                    assert ada["goodput_qps"] >= shed_run["goodput_qps"], (
                        f"degradation goodput {ada['goodput_qps']} < "
                        f"shed-only {shed_run['goodput_qps']} at "
                        f"{factor}x — the adaptive policy is not "
                        f"paying for itself")

        if (fi == 0 and args.fleet_replicas > 0
                and "closed_loop" in row):
            fl, fleet_completed = bench_fleet(
                searcher, cfg_kwargs, queries, args.k,
                row["closed_loop"]["qps"], args.fleet_queries, rng,
                replicas=args.fleet_replicas, kills=args.fleet_kills,
                factor=args.fleet_factor, max_batch=args.max_batch,
                sink=fam_sink)
            completed_total += fleet_completed
            print(f"  fleet @{fl['factor']}x * {fl['replicas']} "
                  f"replicas: n={fl['n']}, served={fl['served']}, "
                  f"shed={fl['shed']}, goodput={fl['goodput_qps']} "
                  f"qps, swap {fl['rolling_swap']['duration_s']} s, "
                  f"kills={fl['kills']['replicas']}, quorum gauge "
                  f"min {fl['quorum_gauge']['min']} >= "
                  f"{fl['quorum_gauge']['threshold']}", flush=True)
            if spans_sink is not None:
                # one kind="fleet" span per request under ONE fleet
                # trace id, tying every retry to its final outcome
                fspans = [r for r in obs_spans.read_jsonl(
                              spans_path, kind="fleet")
                          if r.get("family") == family]
                traces = {r["trace_id"] for r in fspans}
                ok_spans = sum(1 for r in fspans
                               if r["outcome"] == "ok")
                assert len(fspans) == fl["n"] == len(traces), (
                    f"fleet spans do not reconcile 1:1: {len(fspans)} "
                    f"spans / {len(traces)} trace ids for {fl['n']} "
                    f"requests")
                assert ok_spans == fl["served"], (
                    f"{ok_spans} ok fleet spans vs {fl['served']} "
                    f"served requests")
                fl["spans"] = {"records": len(fspans),
                               "trace_ids": len(traces),
                               "ok": ok_spans}
                print(f"  fleet spans: {len(fspans)} records, "
                      f"{len(traces)} trace ids, {ok_spans} ok — "
                      f"reconciled", flush=True)
            row["fleet"] = fl

        if fi == 0 and not args.no_remote_fleet:
            rf = bench_remote_fleet(
                args.dim, args.k,
                base_port=args.remote_fleet_port or None)
            a = rf["autoscale"]
            print(f"  remote fleet: n={rf['n']}, served={rf['served']}, "
                  f"shed={rf['shed']}, untyped={rf['untyped']}; "
                  f"autoscale rise {a['rise_s']}s (window "
                  f"{a['up_window_s']}s), fall {a['fall_s']}s (cooldown "
                  f"{a['down_window_s']}s), reasons={a['reasons']}; "
                  f"spans {rf['spans']['records']} records / "
                  f"{rf['spans']['trace_ids']} trace ids — reconciled",
                  flush=True)
            row["remote_fleet"] = rf

        if spans_sink is not None:
            # consume the span file back: the ok spans must reconcile
            # 1:1 with what the engines' counters say completed
            reqs = [r for r in obs_spans.read_jsonl(spans_path,
                                                    kind="request")
                    if r.get("family") == family]
            outcomes = {}
            for r in reqs:
                outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
            assert outcomes.get("ok", 0) == completed_total, (
                f"span/counter mismatch for {family}: "
                f"{outcomes.get('ok', 0)} ok spans vs "
                f"{completed_total} completed requests")
            row["spans"] = {"requests": len(reqs), "outcomes": outcomes}
            print(f"  spans: {len(reqs)} request records reconciled, "
                  f"outcomes={outcomes}", flush=True)

        if args.shadow_sample > 0:
            oracle = make_exact_oracle(db)
            sh = bench_shadow_recall(
                searcher, cfg_kwargs, queries, args.k, args.submitters,
                args.shadow_sample, oracle, gt,
                passes=args.shadow_passes)
            row["shadow_recall"] = sh
            print(f"  shadow arm @{sh['rate']}: online recall "
                  f"{sh['online_recall']} vs offline "
                  f"{sh['offline_recall']} (delta {sh['delta']}, "
                  f"{sh['samples']} samples, shed="
                  f"{sh['shadow']['shed_queue'] + sh['shadow']['shed_deadline']})",
                  flush=True)
            if family in ("ivf_flat", "ivf_pq") and sh["delta"] is not None:
                assert sh["delta"] <= args.shadow_tolerance, (
                    f"online recall estimate off by {sh['delta']} "
                    f"(> {args.shadow_tolerance}) for {family}: the "
                    "shadow estimator disagrees with the offline oracle")

        if fi == 0 and not args.no_overhead_check:
            import tempfile

            oracle = make_exact_oracle(db)
            with tempfile.TemporaryDirectory() as td:
                oh = bench_telemetry_overhead(
                    searcher, cfg_kwargs, queries, args.k,
                    args.submitters, args.overhead_reps, td,
                    shadow_oracle=(oracle if args.shadow_sample > 0
                                   else None),
                    shadow_rate=args.shadow_sample)
            row["telemetry_overhead"] = oh
            print(f"  telemetry overhead: {oh['overhead'] * 100:.2f}% "
                  f"(plain {oh['qps_plain']} qps vs spans-on "
                  f"{oh['qps_telemetry']} qps, best of "
                  f"{oh['reps']})", flush=True)
            assert oh["overhead"] <= args.overhead_tolerance, (
                f"telemetry overhead {oh['overhead'] * 100:.2f}% exceeds "
                f"{args.overhead_tolerance * 100:.1f}% of closed-loop "
                f"QPS (rerun with --overhead-reps higher on a noisy "
                f"machine, or --no-overhead-check to skip the gate)")
        art["families"][family] = row

    if args.tiered_pressures:
        print("=== tiered (HBM-as-cache)", flush=True)
        tiered_row, tiered_extra = bench_tiered(
            db, queries, args.k, res, rng,
            pressures=tuple(args.tiered_pressures),
            n_requests=args.tiered_queries)
        art["tiered"] = tiered_row
        # bench_gate.flatten_metrics reads ``extra`` as {family: fields},
        # so the hit-rate / stall tokens gate direction-aware
        art["extra"] = tiered_extra

    if args.soak_writes > 0:
        print("=== mutable soak (mixed read/write)", flush=True)
        soak = bench_mutable_soak(
            db, queries, args.k, res, rng, writers=args.soak_writers,
            writes_per_writer=args.soak_writes,
            submitters=args.submitters, max_batch=args.max_batch,
            tolerance=args.soak_tolerance, sink=spans_sink)
        art["mutable_soak"] = soak
        print(f"  soak {soak['soak_s']}s: {soak['writes']} writes "
              f"({soak['acks']} acked), {soak['searches']} searches, "
              f"{soak['compactions']} compactions / "
              f"{soak['swaps']} swaps, recall vs fresh oracle "
              f"{soak['recall_vs_fresh_oracle']} "
              f"(tolerance {soak['tolerance']}), untyped failures "
              f"{soak['untyped_failures']}", flush=True)

    if spans_sink is not None:
        spans_sink.close()
    art["when"] = time.strftime("%Y-%m-%dT%H:%M:%S%z")
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(f"-> {out_path}")
    return art


if __name__ == "__main__":
    main()
