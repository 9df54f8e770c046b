"""Operations and bytes the scan requires, counted from the index.

These are the work the algorithm needs, not what an implementation
happens to do: the scan over the valid rows of the routed clusters
(padding slots are waste), and each distinct routed cluster's rows read
from HBM once per step (re-reading a cluster is waste). The query
tower's work depends on the tower, so each reference counts its own
(``query_flops``, on each query's real tokens: padding to ``max_len`` is
waste).
"""
from __future__ import annotations

import numpy as np

STORED_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def scan_row_bytes(precision, d):
    """HBM bytes of one stored row the scan must read: the embedding in
    its storage type, the location (2 f32), the id (i32), and the int8
    tier's per-row scale (f32)."""
    return d * STORED_BYTES[precision] + 8 + 4 + (4 if precision == "int8"
                                                  else 0)


def scan_work(routes, counts, *, d, precision):
    """(FLOPs, bytes) a step's scan needs: ``2 d`` per (query, route,
    valid row), and the valid rows of each DISTINCT routed cluster read
    once. ``routes`` (n, cr) cluster ids, ``counts`` valid rows per
    cluster."""
    routes = np.asarray(routes)
    counts = np.asarray(counts, np.float64)
    flops = 2.0 * d * counts[routes].sum()
    rows = counts[np.unique(routes)].sum()
    return float(flops), float(rows * scan_row_bytes(precision, d))


def bound_seconds(flops, nbytes, *, peak_flops, hbm_bytes_per_s):
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / hbm_bytes_per_s)
