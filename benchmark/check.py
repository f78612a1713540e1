"""The comparison that decides ``correct``.

Every number compares answers of the timed path with the plain reference
of the configuration (``references/<name>.py``) on the same queries. A
configuration lists the numbers it is held to under ``check``, each with
its limit; a run is correct when every listed number is at or under its
limit and every answer due in the window came.

- ``invalid_rows``: answers with an id outside the rows, a repeated id or
  a missing one (-1). Exact: limit 0.
- ``recall_gap``: 1 - recall@k of the answers against the reference ids.
- ``dist_error``: the widest gap between the distance an answer reports
  for an id and that id's true (direct-form) distance, over the
  reference's k-th distance of the query.
- ``rank_excess``: the widest gap, rank by rank, between the true
  distances of the answer's ids in ascending order and the reference's,
  over the reference's k-th distance of the query.
- ``adc_error``: where the configuration names an ``adc`` reference, the
  widest gap between the distance an answer reports for an id and the
  float64 distance the index's own codes give that id, over the
  reference's k-th distance of the query: the precision the search
  computed in, which the approximation hides from ``dist_error``.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("invalid_rows", "recall_gap", "dist_error", "rank_excess",
           "adc_error")


def numbers(ids, dists, ref_ids, ref_true, true_of_ids, n_rows: int,
            adc_of_ids=None) -> dict:
    """All comparison numbers for answers ``ids``/``dists`` [m, k] against
    the reference's ``ref_ids``/``ref_true`` [m, k] (ascending), where
    ``true_of_ids`` [m, k] holds the true distance of each answered id
    and ``adc_of_ids`` (or None) its float64 ADC distance."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    ref_ids = np.asarray(ref_ids, np.int64)
    ref_true = np.asarray(ref_true, np.float64)
    true_of_ids = np.asarray(true_of_ids, np.float64)
    m, k = ids.shape
    bad = (ids < 0) | (ids >= n_rows)
    srt = np.sort(ids, axis=1)
    dup = np.zeros_like(bad)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    invalid = int((bad.any(1) | dup.any(1)).sum())
    hits = (ids[:, :, None] == ref_ids[:, None, :]).any(2).sum()
    scale = np.maximum(ref_true[:, -1:], np.finfo(np.float64).tiny)
    with np.errstate(invalid="ignore"):
        err = np.abs(dists - true_of_ids) / scale
        excess = (np.sort(true_of_ids, axis=1) - ref_true) / scale
        adc = (np.abs(dists - np.asarray(adc_of_ids, np.float64)) / scale
               if adc_of_ids is not None else None)
    out = {
        "invalid_rows": float(invalid),
        "recall_gap": 1.0 - float(hits) / float(m * k),
        "dist_error": float(np.nan_to_num(err, nan=np.inf).max()),
        "rank_excess": float(np.nan_to_num(excess, nan=np.inf).max()),
    }
    if adc is not None:
        out["adc_error"] = float(np.nan_to_num(adc, nan=np.inf).max())
    return out


def judge(got: dict, limits: dict, missing: int) -> tuple:
    """(correct, lines): each limited number beside its limit, plus the
    count of answers that never came (limit 0)."""
    rows = [("missing_answers", float(missing), 0.0)]
    rows += [(name, got[name], float(limits[name])) for name in limits]
    ok = all(v <= lim for _, v, lim in rows)
    return ok, {name: {"value": v, "limit": lim} for name, v, lim in rows}
