"""Distributed LIST query phase: clusters as experts (DESIGN.md §3/§5).

The paper serves queries on one CPU: route each query to a cluster, scan
that cluster's inverted list. On a TPU pod the cluster buffers are sharded
over all chips, so "scan the routed cluster" becomes a data-movement
problem. Our TPU-native mapping treats it as **expert-parallel dispatch**
(exactly the MoE pattern): clusters are experts, queries are tokens,
capacity = ceil(B·cr/c · balance) — the paper's learned balance (low IF(C))
is precisely what keeps the capacity (and thus the dispatch cost) tight.

  1. route: tiny replicated MLP → top-cr clusters per query
  2. dispatch: sort-based scatter of queries into a (c, Qcap, d) buffer,
     sharded cluster-major over all chips (all-to-all under GSPMD)
  3. score: per-cluster batched matmul (c, Qcap, d)×(c, cap, d) — each chip
     multiplies only ITS clusters against ITS resident buffer shard; the
     object corpus never moves
  4. per-cluster top-k, undispatch back to queries, merge the cr lists

Compute cost: c·Qcap·cap·d ≈ (balance·cr)·B·(n/c)·d = the paper's 1/c
search-space reduction, now bandwidth-local per chip.

The same sort-based scatter core (:func:`_sorted_runs`) also builds the
single-host CLUSTER-MAJOR batch plan (:func:`cluster_major_plan`,
DESIGN.md §10): instead of one roster row per cluster shard, one row
per *distinct routed* cluster, so the engine's ``pallas-cm`` backend
streams each distinct cluster's resident tiles once per batch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core import index as index_lib
from repro.core import relevance
from repro.distributed.sharding import constrain


def query_capacity(batch: int, n_clusters: int, cr: int,
                   balance: float = 2.0) -> int:
    c = int(batch * cr / n_clusters * balance)
    return max(8, -(-c // 8) * 8)


def _sorted_runs(flat):
    """Stable-sort a flat vector of routed cluster ids and mark its runs.

    → (sort_idx, sorted_c, is_start, pos): ``sort_idx`` the stable
    argsort, ``sorted_c`` the sorted cluster ids, ``is_start`` True at
    the first element of each equal-cluster run, ``pos`` each element's
    rank within its run. This is the sort-based scatter core shared by
    :func:`dispatch_queries` (one roster row per cluster, all ``c`` of
    them) and :func:`cluster_major_plan` (one roster row per DISTINCT
    routed cluster).
    """
    n = flat.shape[0]
    sort_idx = jnp.argsort(flat, stable=True)
    sorted_c = flat[sort_idx]
    ar = jnp.arange(n)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_c[1:] != sorted_c[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, ar, 0))
    pos = ar - run_start
    return sort_idx, sorted_c, is_start, pos


def dispatch_queries(top_c, q_feat, *, n_clusters: int, capacity: int):
    """Sort-based dispatch (mirrors models/moe.py).

    top_c: (B, cr) routed clusters; q_feat: (B, f) payload to dispatch.
    Returns (q_buf (c, Qcap, f), origin (c, Qcap) int32 in [0, B·cr],
    pad row = B·cr, n_dropped () int32).

    ``n_dropped`` counts (query, route) pairs that exceeded a cluster's
    capacity and were NOT placed — overflow is surfaced, never silently
    truncated. Callers decide whether to raise capacity or accept the
    recall loss (the merged cr lists degrade gracefully).
    """
    b, cr = top_c.shape
    n = b * cr
    flat = top_c.reshape(n)
    sort_idx, sorted_c, _, pos = _sorted_runs(flat)
    keep = pos < capacity
    slot = jnp.where(keep, sorted_c * capacity + pos, n_clusters * capacity)
    n_dropped = jnp.sum(~keep).astype(jnp.int32)

    origin = jnp.full((n_clusters * capacity + 1,), n, jnp.int32)
    origin = origin.at[slot].set(sort_idx.astype(jnp.int32))
    origin = origin[:-1].reshape(n_clusters, capacity)

    fpad = jnp.concatenate([q_feat[jnp.repeat(jnp.arange(b), cr)],
                            jnp.zeros((1,) + q_feat.shape[1:], q_feat.dtype)])
    q_buf = fpad[jnp.where(origin < n, origin, n)]
    return q_buf, origin, n_dropped


def cluster_major_plan(top_c, *, n_clusters: int,
                       qcap: Optional[int] = None,
                       u_max: Optional[int] = None):
    """Batch execution plan for CLUSTER-MAJOR scanning (DESIGN.md §10).

    Where :func:`dispatch_queries` builds one roster row for every one
    of the ``c`` clusters (the sharded all-to-all layout), this dedupes
    the batch's routed clusters and gives each **distinct** routed
    cluster its own roster rows — the plan the cluster-major kernel
    (``kernels.fused_topk_score_cluster_major``) streams: each row's
    cluster tiles cross HBM once per batch, scored against that row's
    whole query roster.

    top_c: (B, cr) routed cluster ids (duplicates allowed — a query
    routed twice to one cluster occupies two roster slots, preserving
    the query-major duplicate semantics). ``qcap`` (default ``B·cr``)
    is the width of a roster row: a cluster routed by more pairs than
    that spills onto further rows, ``ceil(occupancy / qcap)`` in all.
    Returns

      u          (u_max,) int32 — the cluster each roster row scans, in
                 ascending cluster order. Rows past the realized count
                 hold cluster 0 with an empty roster (static shapes:
                 ``u_max`` defaults to the structural upper bound on the
                 row count, ``min(B·cr, n_clusters)`` when nothing
                 spills).
      roster     (u_max, qcap) int32 — the inverse map: flattened
                 (query, route) indices in ``[0, B·cr)`` assigned to
                 each row, ``B·cr`` marking empty slots.
      n_distinct () int32 — the realized distinct count U; the batch
                 dedup factor is ``B·cr / U`` (the auto heuristic's
                 signal).
      n_dropped  () int32 — (query, route) pairs past a caller-forced
                 ``u_max`` that were NOT placed; surfaced, never
                 silently truncated, exactly like the dispatch path.
                 The default ``u_max`` places every pair.
    """
    b, cr = top_c.shape
    n = b * cr
    qcap = n if qcap is None else qcap
    if u_max is None:
        # rows = Σ_u ceil(o_u / qcap) ≤ U + (n - U)/qcap, increasing in U
        u_max = min(n, n_clusters)
        if qcap < n:
            u_max += -(-(n - u_max) // qcap)
    flat = top_c.reshape(n)
    sort_idx, sorted_c, is_start, pos = _sorted_runs(flat)
    n_distinct = jnp.sum(is_start).astype(jnp.int32)
    row_start = pos % qcap == 0                   # includes every run start
    row_of = jnp.cumsum(row_start) - 1            # roster row per pair
    keep = row_of < u_max
    dest = jnp.where(keep, row_of * qcap + pos % qcap, u_max * qcap)
    n_dropped = jnp.sum(~keep).astype(jnp.int32)

    roster = jnp.full((u_max * qcap + 1,), n, jnp.int32)
    roster = roster.at[dest].set(sort_idx.astype(jnp.int32))
    roster = roster[:-1].reshape(u_max, qcap)

    u_dest = jnp.where(row_start & keep, row_of, u_max)
    u = jnp.zeros((u_max + 1,), jnp.int32)
    u = u.at[u_dest].set(sorted_c.astype(jnp.int32))[:u_max]
    return u, roster, n_distinct, n_dropped


def localize_routes(top_c, shard_of, local_of, shard: int, *,
                    sentinel: int):
    """Map GLOBAL routed cluster ids to one shard's LOCAL buffer rows
    (host, numpy) — the route-localization step of mesh-sharded serving
    (DESIGN.md §12).

    ``top_c (B, cr)`` global routed cluster ids; ``shard_of`` /
    ``local_of`` the ``(c,)`` placement maps of
    ``sharding.ClusterShards``; ``sentinel`` the shard's appended empty
    cluster row (``ClusterShards.sentinel``). Routes owned by ``shard``
    map to their local row; every other route maps to the sentinel, so
    the per-shard plan keeps its static ``(B, cr)`` shape and off-shard
    candidates mask to ``(−1, NEG_INF)`` exactly like padding slots —
    never clamped into a real cluster by jit's out-of-bounds indexing.

    This is the ONE definition of off-shard route semantics, shared by
    the engine's sharded path and the mesh parity tests. Duplicate
    routes to one cluster land on one shard together, preserving the
    single-device duplicate semantics the cluster-major plan relies on.
    """
    tc = np.asarray(top_c)
    shard_of = np.asarray(shard_of)
    local_of = np.asarray(local_of)
    on = shard_of[tc] == shard
    return np.where(on, local_of[tc], sentinel).astype(np.int32)


def roster_query_rows(roster, *, cr: int, n_total: int):
    """Invert roster slots to query rows: slot value ``o ∈ [0, B·cr)``
    is the flattened (query, route) pair, so the query row is
    ``o // cr``; empty slots (``o == n_total``) clamp to row 0 — mask
    them via ``roster < n_total`` (the kernel and the merge both do).
    The ONE definition of the roster's empty-slot semantics, shared by
    the pallas-cm gather, the dense oracle, and the tests."""
    return jnp.where(roster < n_total, roster, 0) // cr


def cluster_dispatch_query(snapshot, q_tokens, q_mask, q_loc, *,
                           k: int = 20, cr: int = 1,
                           capacity: Optional[int] = None,
                           return_dropped: bool = False):
    """The distributed query phase over an :class:`IndexSnapshot`
    (core/snapshot.py) — the same artifact the gather path's
    ``QueryEngine`` serves, so dispatch and gather share one scoring
    surface (``engine.score_candidates``) *and* one state surface.

    Returns (ids (B, k), scores (B, k)), plus the dispatch overflow
    count n_dropped () when ``return_dropped``. Mesh-parallel plans that
    need explicit array arguments (launch/steps.py builds them from
    abstract shapes) call :func:`dispatch_query_kernel` directly.
    """
    buf = snapshot.buffers
    return dispatch_query_kernel(
        snapshot.rel_params, snapshot.index_params, snapshot.w_hat,
        snapshot.norm, buf["emb"], buf["loc"], buf["ids"],
        q_tokens, q_mask, q_loc, snapshot.cfg, k=k, cr=cr,
        dist_max=snapshot.meta.dist_max, capacity=capacity,
        buf_scale=buf.get("scale"), precision=snapshot.meta.precision,
        return_dropped=return_dropped)


def dispatch_query_kernel(rel_params, index_params, w_hat, norm,
                          buf_emb, buf_loc, buf_ids,
                          q_tokens, q_mask, q_loc, cfg, *,
                          k: int = 20, cr: int = 1, dist_max: float = 1.0,
                          capacity: Optional[int] = None,
                          buf_scale=None, precision: str = "f32",
                          return_dropped: bool = False):
    """Explicit-array form of :func:`cluster_dispatch_query` — the body
    that launch/steps.py stages into sharded meshes. Returns
    (ids (B, k), scores (B, k)), plus the dispatch overflow count
    n_dropped () when ``return_dropped``.

    buf_emb (c, cap, d) / buf_loc (c, cap, 2) / buf_ids (c, cap): the padded
    cluster buffers, sharded cluster-major ("all") on the production mesh.
    Quantized buffers (DESIGN.md §9) pass ``precision`` and, for int8,
    the per-row ``buf_scale (c, cap)`` — dequantization rides the shared
    ``engine.score_candidates`` primitive, so dispatch and gather agree
    per tier. The scale shard is cluster-major like the buffers.
    """
    b = q_tokens.shape[0]
    c, cap, d = buf_emb.shape
    # int8 codes scored unscaled would rank rows on raw code magnitude —
    # refuse loudly instead of silently corrupting top-k results
    if buf_emb.dtype == jnp.int8 and (precision != "int8"
                                      or buf_scale is None):
        raise ValueError(
            "dispatch_query_kernel: buf_emb is int8 but "
            f"precision={precision!r} / buf_scale="
            f"{'set' if buf_scale is not None else 'None'}; quantized "
            "buffers require precision='int8' and their per-row scales "
            "(see DESIGN.md §9)")
    qcap = capacity or query_capacity(b, c, cr)

    # 1. encode + route (replicated tiny MLP)
    q_emb = relevance.encode_queries(rel_params, q_tokens, q_mask, cfg)
    w = relevance.st_weights(rel_params, q_emb)                  # (B, 2)
    feats = index_lib.build_features(q_emb, q_loc, norm)
    top_c, _ = index_lib.route_queries(index_params, feats, cr=cr)

    # 2. dispatch query payloads [emb, loc, w] to their clusters
    payload = jnp.concatenate(
        [q_emb, q_loc.astype(q_emb.dtype), w.astype(q_emb.dtype)], axis=-1)
    q_buf, origin, n_dropped = dispatch_queries(top_c, payload,
                                                n_clusters=c, capacity=qcap)
    q_buf = constrain(q_buf, "all", None, None)     # (c, Qcap, d+4)
    qe = q_buf[..., :d]
    ql = q_buf[..., d:d + 2].astype(jnp.float32)
    qw = q_buf[..., d + 2:].astype(jnp.float32)

    # 3. fused score per cluster — each chip against its resident shard;
    # the engine's score_candidates broadcasts (c, Q, d) × (c, 1, cap, d)
    cand_scale = (buf_scale[:, None]
                  if precision == "int8" and buf_scale is not None else None)
    st = engine_lib.score_candidates(
        qe, ql, qw, buf_emb[:, None], buf_loc[:, None], buf_ids[:, None],
        w_hat, dist_max=dist_max, cand_scale=cand_scale)
    st = constrain(st, "all", None, None)

    # 4. per-cluster top-k, then undispatch + merge the cr candidate lists
    vals, pos = jax.lax.top_k(st, k)                        # (c, Qcap, k)
    ids = jnp.take_along_axis(
        jnp.broadcast_to(buf_ids[:, None, :], st.shape), pos, axis=-1)

    flat_vals = vals.reshape(c * qcap, k)
    flat_ids = ids.reshape(c * qcap, k)
    # origin slot -> row in (B·cr): scatter back
    n = b * cr
    back_v = jnp.full((n + 1, k), -jnp.inf, flat_vals.dtype)
    back_i = jnp.full((n + 1, k), -1, flat_ids.dtype)
    orig = origin.reshape(-1)
    back_v = back_v.at[orig].set(flat_vals)
    back_i = back_i.at[orig].set(flat_ids)
    per_q_v = back_v[:n].reshape(b, cr * k)
    per_q_i = back_i[:n].reshape(b, cr * k)
    fv, fpos = jax.lax.top_k(per_q_v, k)
    fi = jnp.take_along_axis(per_q_i, fpos, axis=1)
    if return_dropped:
        return fi, fv, n_dropped
    return fi, fv
