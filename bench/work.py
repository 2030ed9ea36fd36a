"""Work a kernel call needs, computed from shapes alone.

The count is of the work, not of what today's implementation does: the
bytes a kernel must move at the least, whatever tiles or re-reads it uses.
"""

from __future__ import annotations


def code_columns(cfg: dict) -> int:
    """Token columns per doc: one rounding and one interval token per
    feature (the configuration's combined encoder)."""
    return 2 * cfg["n_features"]


def phase1_bytes(cfg: dict) -> int:
    """HBM bytes of one phase-1 call on one shard for one batch: one pass
    over the shard's int8 doc token table and its live mask (one byte per
    doc), the batch's int8 query tokens and f32 weights, and the page of
    (f32 score, int32 id) pairs per query written back."""
    dp = cfg["n_docs"] // cfg["n_shards"]
    c = code_columns(cfg)
    b = cfg["batch_size"]
    table = dp * c + dp
    queries = b * c * (1 + 4)
    page = b * cfg["page"] * (4 + 4)
    return table + queries + page


def phase1_least_s(cfg: dict, peaks: dict) -> float:
    """Least time of one phase-1 call: its bytes at the HBM peak.  The
    compare/select/add work runs on the vector unit, which has no
    published peak, so no operations bound enters."""
    return phase1_bytes(cfg) / peaks["hbm_bytes_s"]
