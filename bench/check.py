"""The comparison that decides ``correct``.

Each number is held to the limit the configuration's ``limits`` gives it:

* ``failed``: requests of the window whose future raised;
* ``unanswered``: requests of the window with no reply a minute after the
  window closed;
* ``bad_ids``: sampled answers with an id out of range, repeated in one
  answer, or fewer than ``k`` hits;
* ``rank_gap``: over the sampled answers and ranks r, the largest amount by
  which the answer's r-th best exact cosine lies below the reference's
  ``lo`` r-th best, or above its ``hi`` r-th best (float64 cosines, see
  ``refs/token_match.py``);
* ``score_err``: the largest |reported score - float64 cosine| over every
  sampled hit.

A sampled query with a feature within rounding of a bucket edge or of the
trim threshold may tokenise either way in float32; it is left out of
``rank_gap`` (not of the others) and counted as ``ambiguous``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _sorted_desc(c: np.ndarray) -> np.ndarray:
    return -np.sort(-c, axis=1)


def compare(ref, queries, ids, scores, cfg: dict) -> Dict[str, float]:
    """Numbers of a sample of served answers against the reference ``ref``
    (a ``Reference`` of ``refs/token_match.py``)."""
    ids = np.asarray(ids, np.int64)
    scores = np.asarray(scores, np.float64)
    k, n = cfg["k"], cfg["n_docs"]
    bad = (ids < 0) | (ids >= n) | ~np.isfinite(scores)
    dup = np.array([len(set(r.tolist())) < len(r) for r in ids])
    bad_rows = bad.any(axis=1) | dup | (ids.shape[1] < k)
    c_sys = ref.cosines(queries, ids)
    lo, hi = ref.envelope(queries)
    c_lo = _sorted_desc(ref.cosines(queries, lo))
    c_hi = _sorted_desc(ref.cosines(queries, hi))
    s_sys = _sorted_desc(np.where(bad, -np.inf, c_sys))
    amb = ref.ambiguous(queries)
    keep = ~amb & ~bad_rows
    with np.errstate(invalid="ignore"):
        below = np.where(np.isfinite(c_lo), c_lo - s_sys, -np.inf)
        above = s_sys - c_hi
    gap = np.maximum(below, above)[keep]
    err = np.abs(scores - c_sys)[~bad]
    return {
        "bad_ids": float(bad_rows.sum()),
        "rank_gap": float(gap.max()) if gap.size else 0.0,
        "score_err": float(err.max()) if err.size else 0.0,
        "ambiguous": float(amb.sum()),
        "sampled": float(len(ids)),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: {"value", "limit"}}) for the numbers that have a
    limit; a number that is not finite fails."""
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits if name in numbers}
    missing = [name for name in limits if name not in numbers]
    ok = not missing and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks
