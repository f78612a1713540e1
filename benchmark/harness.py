"""One cell, once: set-up, the measured window, the comparison with the
plain reference, and the result line.

Everything a cell is made of is found by name, so that a later PR adds a
configuration, a traffic mix or a metric by adding files:

- ``BENCHMARK.json`` (the checkout's root): the cells and the metrics;
- ``configs/<config>.json``: the deployment, with the ``system`` under
  test (``systems/<system>.py``), its ``reference``
  (``references/<reference>.py``) and the ``check`` limits;
- ``traffic/<mix>.json``: the loop and its parameters (``loops.py``);
- ``metrics/<metric>.py``: ``read(ctx)`` returns the metric's value, or
  None where the run holds nothing to read (the metric is then left out);
- ``counts/<family>.py``: FLOPs and bytes an algorithm needs;
- ``peaks.json``: the chip's published peaks by ``device_kind``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------ finding


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = BENCH):
    """``<bench>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(bench, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, mix and
    metrics resolved from their files."""

    def __init__(self, spec: dict, name: str, root: str = ROOT):
        bench = os.path.join(root, "benchmark")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[name]
        self.name = name
        self.bench = bench
        self.chips = int(w["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[w["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = load_json(os.path.join(bench, "traffic",
                                              w["traffic"] + ".json"))

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in spec["end_to_end"] if applies(m)]
        self.per_layer = [m for m in spec["per_layer"] if applies(m)]

    def reader(self, metric_name: str):
        return load_module("metrics", metric_name, self.bench)


# ------------------------------------------------------------ devices


def chip_devices(chips: int):
    """The first ``chips`` TPU devices; raises where JAX finds no TPU or
    too few of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devs[0].platform} devices; "
                           "the benchmark runs only on the chip")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` if
    set, else at the fixed ``<checkout>/.jax_cache`` — the directory the
    program itself would choose — so only a checkout's first run of a
    cell compiles."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def peaks_for(kind: str, bench: str = BENCH) -> dict:
    table = load_json(os.path.join(bench, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


# ------------------------------------------------------------ data


class Data:
    """The collection, row-sharded over ``devices`` (one shard each, made
    on its own device), and the query pool on the host. The collection
    comes from ``dataset.base_seed`` where the configuration fixes one,
    else from ``seed``; the queries always come from ``seed``."""

    def __init__(self, cfg: dict, seed: int, devices, annotate):
        import jax

        from benchmark import datagen

        ds = cfg["dataset"]
        gen = cfg["assumed"]["generator"]
        rows, dim, nq = int(ds["rows"]), int(ds["dim"]), int(ds["queries"])
        per = rows // len(devices)
        if per * len(devices) != rows:
            raise ValueError(f"{rows} rows do not split over "
                             f"{len(devices)} chips")
        base_seed = int(ds.get("base_seed", seed))
        with annotate("bench.generate"):
            self.shards = [(datagen.make_rows(base_seed, "base", r * per, per,
                                              dim, gen, ds["chunk"], dev),
                            r * per)
                           for r, dev in enumerate(devices)]
            q = datagen.make_rows(seed, "queries", 0, nq, dim, gen, nq,
                                  devices[0], model_seed=base_seed)
            jax.block_until_ready([a for a, _ in self.shards])
            self.queries = np.asarray(q)
        self.n_rows = rows


# ------------------------------------------------------------ the run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float,
             system_factory: Optional[Callable] = None,
             config_overrides: Optional[dict] = None) -> dict:
    """Run ``cell`` once on ``devices``; return the result object (the
    dict printed as the last line) and print the compared numbers on
    stderr. ``system_factory`` and ``config_overrides`` are for the
    controls and the tests: they put another system, or another setting
    of the same one, in the program's place."""
    import jax

    from raft_tpu.obs.device import compile_count

    from benchmark import check, loops
    from benchmark import trace as trace_mod

    cfg = _merged(cell.config, config_overrides or {})
    traffic = cell.traffic
    kind = devices[0].device_kind
    peaks = peaks_for(kind, cell.bench) if devices[0].platform == "tpu" \
        else None
    annotate = jax.profiler.TraceAnnotation
    k = int(cfg["search"]["k"])

    data = Data(cfg, seed, devices, annotate)
    make = system_factory or load_module("systems", cfg["system"],
                                         cell.bench).System
    system = make(cfg, data.shards, devices, annotate)
    build_s = system.build_s
    spans = []

    class Sink:
        def emit(self, record):
            spans.append(record)

    loop = traffic["loop"]
    if loop == "closed":
        batch = int(cfg["search"]["batch"])
        n_distinct = system.stage(data.queries, batch)
    elif loop == "open":
        # the Engine's request and batch spans feed per-layer metrics,
        # which only a traced run reads; an untraced run keeps none
        submit = system.serve(Sink() if trace else None)
        due = loops.arrivals(float(traffic["rate_per_s"]), seconds, seed)
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
        picks = rng.integers(0, len(data.queries), len(due))
    else:
        raise ValueError(f"unknown loop {loop!r}")
    # what set-up made lives for the whole run: out of the collector's
    # sight, so a collection in the window does not walk it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (build {build_s})")

    compiles0 = compile_count()
    profile = None
    if trace:
        cap = trace_mod.capture()
        profile = cap.__enter__()
    try:
        with annotate(trace_mod.WINDOW):
            if loop == "closed":
                res = loops.closed(system.call, n_distinct, seconds,
                                   int(traffic["inflight"]))
            else:
                res = loops.open_loop(submit, data.queries, picks, due, k)
    finally:
        if trace:
            cap.__exit__(None, None, None)
    window_compiles = compile_count() - compiles0
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices)

    # answers to the host, program state freed, then the reference
    if loop == "closed":
        answers = [(j, np.asarray(d), np.asarray(i))
                   for j, (d, i) in zip(res["which"], res["outs"])]
        res["outs"] = None
    adc_name = cfg["check"].get("adc")
    adc_view = system.adc_view() if adc_name else None
    system.release()
    system = None
    spans_window = list(spans)
    gc.unfreeze()
    gc.collect()

    pool = len(data.queries)
    n_sample = min(int(cfg["check"]["sample"]), pool)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
    sample = np.sort(rng.choice(pool, n_sample, replace=False))
    pos = np.full(pool, -1)
    pos[sample] = np.arange(n_sample)
    a_q, a_d, a_i = [], [], []
    missing = 0
    if loop == "closed":
        for j, d, i in answers:
            rows = np.arange(j * batch, (j + 1) * batch)
            keep = pos[rows] >= 0
            a_q.append(rows[keep]), a_d.append(d[keep]), a_i.append(i[keep])
        n_done = len(answers) * batch
    else:
        # a refusal is an answer (a typed failure, counted in "failed"
        # and as infinitely late); only a request with neither an answer
        # nor a refusal never came
        answered = res["answered"]
        missing = int((~answered & ~res["refused"]).sum())
        keep = answered & (pos[picks] >= 0)
        a_q.append(picks[keep]), a_d.append(res["dists"][keep])
        a_i.append(res["ids"][keep])
        n_done = int(answered.sum())
    a_q = np.concatenate(a_q) if a_q else np.zeros(0, np.int64)
    a_d = np.concatenate(a_d) if a_d else np.zeros((0, k))
    a_i = np.concatenate(a_i) if a_i else np.zeros((0, k), np.int64)

    ref = load_module("references", cfg["reference"], cell.bench)
    t_ref = time.perf_counter()
    _, ref_ids, ref_true = ref.knn(data.shards, data.queries[sample], k,
                                   margin=int(cfg["check"].get("margin", 10)))
    # true distance of each distinct (query, answered ids) row
    key = np.concatenate([a_q[:, None], a_i.astype(np.int64)], axis=1)
    uniq, inv = (np.unique(key, axis=0, return_inverse=True)
                 if len(key) else (key, np.zeros(0, np.int64)))
    true_u = ref.true_distances(data.shards, data.queries[uniq[:, 0]],
                                uniq[:, 1:]) if len(uniq) else uniq[:, 1:]
    true_of = true_u[inv.reshape(-1)]
    adc_of = None
    if adc_view is not None and len(uniq):
        adc = load_module("references", adc_name, cell.bench)
        adc_of = adc.distances(adc_view, data.queries[uniq[:, 0]],
                               uniq[:, 1:])[inv.reshape(-1)]
        adc_view = None
    got_nums = check.numbers(a_i, a_d, ref_ids[pos[a_q]], ref_true[pos[a_q]],
                             true_of, data.n_rows, adc_of) if len(a_q) else {
        n: float("inf") for n in check.NUMBERS}
    ref_s = time.perf_counter() - t_ref
    correct, lines = check.judge(got_nums, cfg["check"]["limits"], missing)
    log(f"reference and comparison {ref_s:.3f} s over {len(a_q)} answers "
        f"to {n_sample} queries")

    ctx = {
        "cell": cell.name, "config": cfg, "traffic": traffic, "seed": seed,
        "seconds": seconds, "setup_s": setup_s, "build_s": build_s, "loop": loop, "result": res,
        "recall": 1.0 - got_nums["recall_gap"], "n_answers": len(a_q),
        "n_done": n_done, "spans": spans_window, "trace": None,
        "peaks": peaks, "device_kind": kind, "n_devices": len(devices),
        "window_compiles": window_compiles,
        "counts": lambda fam: load_module("counts", fam, cell.bench),
    }
    out = {"correct": bool(correct), "attempted": 0, "failed": 0,
           "metrics": {}, "device": {
               "platform": devices[0].platform, "kind": kind,
               "count": len(jax.devices()), "memory_peak_bytes": memory_peak}}
    if loop == "closed":
        out["attempted"] = n_done
    else:
        out["attempted"] = len(picks)
        out["failed"] = len(picks) - n_done
        lat = res["latency_s"]
        log(f"latency from the due time over {len(lat)} requests: p50 "
            f"{loops.percentile(lat, 50) * 1e3!r} ms, p99 "
            f"{loops.percentile(lat, 99) * 1e3!r} ms, p99.9 "
            f"{loops.percentile(lat, 99.9) * 1e3!r} ms")
        late = res["late_s"]
        if res["errors"]:
            log(f"{len(res['errors'])} requests refused; the first: "
                f"{res['errors'][0]}")
        log(f"generator lateness: mean {np.nanmean(late) * 1e3:.4f} ms, "
            f"p99 {np.nanpercentile(late, 99) * 1e3:.4f} ms, max "
            f"{np.nanmax(late) * 1e3:.4f} ms over {len(late)} requests")
        worst = np.argsort(np.nan_to_num(late, nan=-1.0))[::-1][:200]
        stalls = sorted({round(float(due[i]), 1) for i in worst
                         if late[i] > 0.02})
        log(f"the generator ran > 20 ms late around {stalls[:20]} s "
            "into the window")
    log(f"window {res['window_s']:.6f} s, {window_compiles} compiles in it")

    defs = cell.per_layer if trace else cell.end_to_end
    if trace:
        summary = trace_mod.reduce(trace_mod.load(profile["path"]))
        trace_mod.cleanup(profile)
        ctx["trace"] = summary
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.top_gaps(10)}
        for name, ds in summary.devices.items():
            log(f"idle_share {name} {summary.idle_share(name) * 100.0!r} %")
    for m in defs:
        v = cell.reader(m["name"]).read(ctx)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    for name, row in lines.items():
        log(f"check {name} {row['value']!r} limit {row['limit']!r}")
    out["numbers"] = got_nums  # all of them, limited or not (controls.py)
    out["check"] = lines
    return out


def _merged(base: dict, over: dict) -> dict:
    out = json.loads(json.dumps(base))
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _merged(out[key], val)
        else:
            out[key] = val
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(spec, args.workload)
    load_module("systems", cell.config["system"])  # the program is here
    use_compile_cache()
    devices = chip_devices(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   t_start)
    print(json.dumps(out), flush=True)
    return 0
