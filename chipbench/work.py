"""Operations and bytes the query phase requires, counted from its shapes.

These are the work the algorithm needs, not what an implementation
happens to do: the tower on each query's real tokens (padding to
``max_len`` is waste), the scan over the valid rows of the routed
clusters (padding slots are waste), and each distinct routed cluster's
rows read from HBM once per step (re-reading a cluster is waste).
"""
from __future__ import annotations

import numpy as np

STORED_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def tower_dense_flops_per_token(model):
    """Projection and feed-forward FLOPs of one token through the tower:
    per layer ``2 * (4 d^2 + 2 d d_ff)``."""
    d, f = model["d_model"], model["d_ff"]
    return 2 * (4 * d * d + 2 * d * f) * model["n_layers"]


def query_flops(model, lengths):
    """FLOPs one query of ``lengths`` real tokens needs before the scan:
    the tower's projections and feed-forward per token, attention scores
    and values (``4 n d`` per token per layer), the CLS head, the mixing
    MLP and the router MLP. ``lengths`` may be an array (summed)."""
    n = np.asarray(lengths, np.float64)
    d, layers = model["d_model"], model["n_layers"]
    tower = n * tower_dense_flops_per_token(model) + 4.0 * n * n * d * layers
    head = 2.0 * d * d
    mix = 2.0 * (d * model["weight_mlp_hidden"]
                 + model["weight_mlp_hidden"] * 2)
    dims = (d + 2, *model["index_mlp_hidden"], model["n_clusters"])
    router = 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return float(np.sum(tower + head + mix + router))


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
