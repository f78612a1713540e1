"""System under test: raft_tpu's IVF-PQ on one chip — ``ivf_pq.build``,
``ivf_pq.search`` (closed loop) and ``serving.Engine`` over
``serving.ivf_pq_searcher`` (open loop).

The configuration's ``index`` and ``search`` keys use raft-ann-bench's
names; :data:`DTYPES` maps its dtype words to JAX's. ``smemLutDtype`` sets
the LUT dtype and the dtype of the decoded scan cache, which is this
engine's form of the LUT.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from raft_tpu import serving
from raft_tpu.bench.timing import fence_index
from raft_tpu.neighbors import ivf_pq

DTYPES = {"float": "float32", "half": "bfloat16", "fp8": "float8_e4m3fn"}


def _params(cfg: dict):
    ix, sx = cfg["index"], cfg["search"]
    build = ivf_pq.IndexParams(
        n_lists=int(ix["nlist"]), pq_dim=int(ix["pq_dim"]),
        pq_bits=int(ix["pq_bits"]), kmeans_n_iters=int(ix["niter"]))
    lut = jnp.dtype(DTYPES[sx["smemLutDtype"]])
    search = ivf_pq.SearchParams(
        n_probes=int(sx["nprobe"]), scan_mode=sx["scan_mode"],
        lut_dtype=lut, scan_cache_dtype=lut,
        internal_distance_dtype=jnp.dtype(
            DTYPES[sx["internalDistanceDtype"]]))
    return build, search


class System:
    def __init__(self, cfg: dict, shards, devices, annotate):
        if len(shards) != 1:
            raise ValueError("ivf_pq runs on one chip")
        self.cfg = cfg
        self.k = int(cfg["search"]["k"])
        self.device = devices[0]
        self.build_params, self.search_params = _params(cfg)
        base = shards[0][0]
        with annotate("bench.build"):
            t = time.perf_counter()
            self.index = ivf_pq.build(base, self.build_params)
            fence_index(self.index)
            self.build_s = time.perf_counter() - t
        with annotate("bench.scan_cache"):
            # the decoded cache the first search would fill lazily
            ivf_pq.ensure_scan_cache(self.index,
                                     self.search_params.scan_cache_dtype)
            fence_index(self.index)
        self.engine = None
        self.batches = []

    # ---- closed loop: direct ivf_pq.search on staged device batches
    def stage(self, queries, batch: int) -> int:
        n = len(queries) // batch
        self.batches = [jax.device_put(queries[j * batch:(j + 1) * batch],
                                       self.device) for j in range(n)]
        jax.block_until_ready(self.batches)
        for j in range(n):  # warm the shape (and any per-input state)
            jax.block_until_ready(self.call(j))
        return n

    def call(self, j: int):
        return ivf_pq.search(self.index, self.batches[j], self.k,
                             self.search_params)

    # ---- open loop: the serving Engine with its defaults
    def serve(self, span_sink):
        searcher = serving.ivf_pq_searcher(self.index, self.search_params)
        # persistent_cache=False: the harness has already pointed JAX's
        # compile cache at the checkout; the Engine would only set it again
        self.engine = serving.Engine(searcher, serving.EngineConfig(
            span_sink=span_sink, persistent_cache=False))
        self.engine.start()
        return self.engine.submit

    def adc_view(self) -> dict:
        """The index as host arrays, for the float64 ADC distances of
        ``references/ivf_pq_adc.py``: centres, rotation, codebooks, and
        each list's ids and code bytes (with the overflow block's)."""
        ix = self.index
        names = ("centers", "rotation", "codebooks", "list_codes",
                 "list_indices", "list_sizes", "overflow_codes",
                 "overflow_labels", "overflow_indices")
        view = dict(zip(names, jax.device_get(
            [getattr(ix, n) for n in names])))
        view.update(n_rows=ix.n_rows, pq_dim=ix.pq_dim, pq_bits=ix.pq_bits,
                    per_cluster=ix.params.codebook_kind
                    == ivf_pq.CodebookGen.PER_CLUSTER)
        return view

    def release(self) -> None:
        if self.engine is not None:
            self.engine.stop()
            self.engine = None
        self.index = None
        self.batches = []
