"""Unified LIST query engine (DESIGN.md §2–§6).

This module is the single entry point to the paper's **query phase**
(Algorithm 1: encode the query → build index features → route to the
top-``cr`` learned clusters → score those clusters' resident objects →
top-k). Every consumer — :class:`~repro.core.pipeline.ListRetriever`,
the streaming server (core/server.py), the distributed dispatch path
(core/serving.py), the baselines' reranker, and the benchmarks — goes
through it, so routing, scoring, and batching each have exactly one
definition.

Public surface
--------------

:func:`make_query_fn`
    Build the jitted end-to-end query function for a model config.
    Returns ``fn(rel_params, index_params, w_hat, norm, buf_emb,
    buf_loc, buf_ids, buf_scale, q_tokens, q_mask, q_loc) ->
    (ids, scores)``. This is the function a serving process compiles
    once and calls on every batch.

:func:`score_candidates`
    The one dense scoring primitive: ST(q, o) over an explicit
    candidate set, used by the dense backend, the dispatch path's
    per-cluster score, and baseline reranking.

:func:`run_batched`
    Static-shape batch execution: map a jitted function over arrays in
    fixed-size chunks, zero-padding the trailing partial chunk so the
    function compiles for exactly one batch shape.

:class:`QueryEngine`
    A stateless executor over an immutable ``IndexSnapshot``
    (core/snapshot.py, DESIGN.md §8) with a cache of traced plans keyed
    ``(batch, k, cr, backend, precision)`` — what the streaming server and the
    retriever hold onto. Snapshot swaps go through
    :meth:`QueryEngine.publish` (atomic, digest-checked); plans survive
    them.

:func:`resolve_backend` / :func:`resolve_cli_backend` /
:data:`BACKENDS`
    Backend-selection rules. ``resolve_cli_backend`` is the ONLY home
    of the deprecated ``--use-pallas`` alias (warns and forwards — see
    below and DESIGN.md §6); library code takes ``backend=`` only.

Inputs, throughout: ``q_tokens (B, L) int32`` hashed token ids with
token 0 = padding, ``q_mask (B, L) bool`` True on real tokens,
``q_loc (B, 2) float32`` locations in the unit box, and the cluster
buffers of ``index.build_cluster_buffers`` — ``buf_emb (c, cap, d)``
(f32, bf16, or int8 per the precision policy, DESIGN.md §9),
``buf_loc (c, cap, 2)``, ``buf_ids (c, cap)`` with ``-1`` marking
padding slots, ``buf_scale (c, cap)`` f32 dequant scales. Outputs:
``ids (B, k)`` **global object ids** with ``-1`` past-the-end, and
``scores (B, k)`` f32 descending.

Backend selection
-----------------

``backend="pallas" | "pallas-cm" | "dense" | "dense-cm" | "auto"``:

* ``"pallas"`` — the gather-free fused kernel
  (kernels/fused_topk_score_routed): routed cluster ids are
  scalar-prefetched and the resident ``(c, cap, d)`` buffers are
  block-indexed directly, so no ``(B, cr·cap, d)`` candidate copy is
  ever materialized and the ``cr`` routed lists merge in-kernel.
  Query-major: a cluster routed by many queries streams once per route.
* ``"pallas-cm"`` — the CLUSTER-MAJOR kernel (DESIGN.md §10): the batch
  plan dedupes the routed clusters (``serving.cluster_major_plan``) and
  each distinct cluster's tiles stream from HBM once per batch, scored
  against that cluster's whole query roster in one MXU matmul; a thin
  scatter + top-k merge (:func:`merge_cluster_major`) folds the ``cr``
  partial lists per query. Wins by the batch dedup factor ``B·cr/U``
  under skewed (or simply cluster-saturating, ``B·cr > c``) routing.
* ``"dense"`` — the pure-jnp reference path (gather + one
  ``jax.lax.top_k``). Always available, and the parity oracle.
* ``"dense-cm"`` — the pure-jnp mirror of the cluster-major plan
  (:func:`dense_cluster_major`): same dedupe/roster/merge, gathering
  each distinct cluster once. The cluster-major parity oracle.
* ``"auto"`` — ``"pallas"`` when a compiled TPU backend is present,
  else ``"dense"`` (interpret-mode Pallas is a correctness tool, not a
  fast path). On top of that, :meth:`QueryEngine.query` upgrades an
  auto-resolved backend to its cluster-major twin per batch when the
  batch dedup factor crosses :data:`CLUSTER_MAJOR_DEDUP_THRESHOLD`
  (structurally, or measured by routing the first chunk — see
  :func:`cluster_major_variant`).

``interpret`` for the Pallas kernels follows the platform alone
(off-TPU ⇒ interpreter), matching kernels/ops.py. Backends
are bit-compatible: parity across shapes, padding, ties, and ``cr`` is
enforced by tests/test_query_engine_parity.py.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import filters as filters_lib
from repro.core import index as index_lib
from repro.core import relevance
from repro.core import spatial as sp

NEG_INF = -1e30

# the named device scopes (``jax.named_scope``) every plan puts its work
# under, so a profile attributes each device op to a stage of the query
# phase: the query tower, routing (features, router, mixing weights),
# the scan (plan, gathers, relayouts and the kernel), and the merge of
# partial top-k lists. An op belongs to the innermost of these in its
# ``op_name`` path.
DEVICE_SCOPES = ("tower", "route", "scan", "merge")

BACKENDS = ("pallas", "pallas-cm", "dense", "dense-cm", "auto")

# query-major backends and their cluster-major twins (DESIGN.md §10)
_CM_TWIN = {"pallas": "pallas-cm", "dense": "dense-cm"}

# auto upgrades to cluster-major when the batch streams each distinct
# cluster at least this many times under query-major execution
CLUSTER_MAJOR_DEDUP_THRESHOLD = 2.0

# roster slots per cluster-major plan row: bounds the (Qcap, d) query
# block the pallas-cm kernel holds in VMEM; a cluster routed by more
# (query, route) pairs spills onto further rows (serving.cluster_major_plan)
CLUSTER_MAJOR_QCAP = 128

# traced plans an engine keeps before evicting least-recently-used ones
DEFAULT_PLAN_CACHE_SIZE = 32

# shard fault tolerance (DESIGN.md §15): per-shard scan retry/backoff
# and the health state machine driving degraded partial-result serving
SHARD_SCAN_RETRIES = 2             # extra attempts per shard per chunk
SHARD_RETRY_BACKOFF_MS = 1.0       # first retry delay; doubles, capped
SHARD_RETRY_BACKOFF_MAX_MS = 20.0
SHARD_DOWN_AFTER = 3               # consecutive scan failures → DOWN
SHARD_HEDGE_PROBE_EVERY = 8        # hedged scans between device probes

# delta-segment scans pad the row count up to a multiple of this, so a
# growing delta retraces the scan once per bucket, not once per insert
DELTA_PAD_BUCKET = 128

# when a snapshot carries tombstones, the base top-k is over-fetched by
# the tombstone count (rounded up to this bucket — bounded recompiles):
# every tombstone can knock one entry out of the base list, so fetching
# k + n_tombstones guarantees the post-filter top-k is exact
TOMBSTONE_K_BUCKET = 32


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------


def default_interpret() -> bool:
    """Interpret-mode default for the Pallas kernels: compiled on TPU,
    interpreted everywhere else. Shared with kernels/ops.py so every
    entry point agrees."""
    from repro.kernels import ops as kops
    return kops._interpret_default()


def resolve_backend(backend: str = "auto",
                    interpret: Optional[bool] = None) -> Tuple[str, bool]:
    """→ (backend ∈ {"pallas", "dense"}, interpret flag for pallas).

    "auto" keys on the HARDWARE (pallas iff a TPU backend is present),
    not on the interpret flag."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    interpret = default_interpret() if interpret is None else interpret
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "dense"
    return backend, interpret


def resolve_cli_backend(backend: Optional[str], use_pallas: bool,
                        *, default: str = "auto") -> str:
    """The CLI flavor of the alias rule, shared by every driver:
    ``--use-pallas`` is deprecated — warn and forward it to
    ``--backend pallas``; an explicit ``--backend`` always wins (with a
    warning that the alias was ignored — the flags never silently
    coexist). Neither flag given → ``default`` ("auto": hardware picks).
    """
    if use_pallas:
        import warnings
        if backend is None:
            warnings.warn("--use-pallas is deprecated; forwarding to "
                          "--backend pallas", DeprecationWarning,
                          stacklevel=2)
            return "pallas"
        if backend != "pallas":
            warnings.warn(f"--use-pallas ignored: explicit --backend "
                          f"{backend} wins", DeprecationWarning,
                          stacklevel=2)
    return backend or default


def cluster_major_variant(backend: str, dedup_factor: float, *,
                          threshold: float = CLUSTER_MAJOR_DEDUP_THRESHOLD
                          ) -> str:
    """The cluster-major auto heuristic (DESIGN.md §10).

    Upgrade a query-major ``backend`` ("pallas" | "dense") to its
    cluster-major twin when the batch dedup factor ``B·cr/U`` (how many
    times query-major execution would re-stream each distinct routed
    cluster) reaches ``threshold``; at lower dedup the roster padding
    overhead isn't paid for. Cluster-major backends and non-upgradable
    names pass through unchanged, so this is safe to apply to any
    resolved backend.
    """
    if dedup_factor >= threshold:
        return _CM_TWIN.get(backend, backend)
    return backend


def cluster_major_feasible(batch: int, cr: int, n_clusters: int,
                           capacity: int) -> bool:
    """Shape guard for the AUTO upgrade: cluster-major pays a static
    roster — a ``(u_max, B·cr, d)`` query-payload gather and a
    ``u_max``-fold matmul over mostly-empty roster rows, with
    ``u_max = min(B·cr, c)``. Requiring ``u_max ≤ cap`` bounds that
    payload by the query-major candidate copy ``(B, cr·cap, d)`` it
    replaces, so auto can never pick a plan whose overhead outgrows the
    stream it saves (large-``c`` small-``cap`` regimes). An explicit
    ``*-cm`` backend bypasses this — callers who know their skew (or
    pass a tight ``qcap`` at the plan level) stay in control.
    """
    return min(batch * cr, n_clusters) <= capacity


# ---------------------------------------------------------------------------
# The one scoring primitive (Eq. 5 serve form)
# ---------------------------------------------------------------------------


def score_candidates(q_emb, q_loc, w_st, cand_emb, cand_loc, cand_ids,
                     w_hat, *, dist_max: float, cand_scale=None,
                     cand_attrs=None, fvals=None):
    """Score an explicit candidate set with the paper's serve-form ST.

    ST(q, o) = w_t·(q·o) + w_s·ŵ_s[⌊S_in·t⌋] (Eq. 5): textual relevance
    is the embedding dot product; spatial relevance looks the normalized
    proximity ``S_in = 1 − clip(dist/dist_max, 0, 1)`` up in the learned
    monotone step table ``w_hat (t,)``; ``w_st (..., 2)`` holds the
    per-query (textual, spatial) mixing weights from
    ``relevance.st_weights``.

    Shapes broadcast over leading dims: q_emb (..., d), q_loc (..., 2),
    w_st (..., 2) against cand_emb (..., N, d), cand_loc (..., N, 2),
    cand_ids (..., N). Returns (..., N) f32 with padding (ids < 0) masked
    to NEG_INF (-1e30, finite — NOT -inf: the Pallas kernels use the same
    sentinel, keeping backends bit-identical; filter results by
    ``ids >= 0``, not ``isfinite(score)``). Callers:

    * engine dense backend:  q (B, d)    × cand (B, N, d)
    * serving per-cluster:   q (c, Q, d) × cand (c, 1, cap, d)
    * baselines rerank:      q (d,)      × cand (N, d)

    ``cand_scale (..., N)`` dequantizes int8 candidate embeddings
    (DESIGN.md §9): ``emb = cand_emb.astype(f32) * scale[..., None]`` —
    the same per-row symmetric scales the Pallas kernels apply in VMEM,
    so dense-vs-pallas parity holds within every precision tier. bf16
    candidates need no scale (the astype below is the whole dequant).

    ``cand_attrs (..., N, 3)`` + ``fvals (..., 4)`` apply the filtered-
    search predicate (core/filters.py, DESIGN.md §13): rows that fail
    score NEG_INF, exactly like padding — the same mask the Pallas
    kernels apply in VMEM. Pass both or neither.

    This is the ONE definition of "the score" — if you are scoring
    (query, object) pairs anywhere, call this, don't re-derive it.
    """
    ce = cand_emb.astype(jnp.float32)
    if cand_scale is not None:
        ce = ce * cand_scale[..., None]
    trel = jnp.einsum("...d,...nd->...n", q_emb.astype(jnp.float32), ce)
    d = jnp.linalg.norm(q_loc[..., None, :].astype(jnp.float32)
                        - cand_loc.astype(jnp.float32), axis=-1)
    s_in = 1.0 - jnp.clip(d / dist_max, 0.0, 1.0)
    srel = sp.spatial_relevance_serve(w_hat, s_in)
    st = w_st[..., :1] * trel + w_st[..., 1:2] * srel
    ok = cand_ids >= 0
    if cand_attrs is not None:
        ok = ok & filters_lib.predicate_mask(cand_attrs,
                                             fvals[..., None, :])
    return jnp.where(ok, st, NEG_INF)


def dense_routed_topk(q_emb, q_loc, w_st, top_c, buf_emb, buf_loc, buf_ids,
                      w_hat, *, k: int, dist_max: float, buf_scale=None,
                      buf_attrs=None, q_filt=None):
    """Dense reference for the routed query phase: gather + one top-k.

    Returns (scores (B, k), ids (B, k) global object ids, -1 past-the-end)
    — the exact contract of kernels/fused_topk_score_routed.
    ``buf_scale (c, cap)`` dequantizes int8 buffers with the same per-row
    scales the kernel applies in VMEM (parity within a precision tier).
    ``buf_attrs (c, cap, 3)`` + ``q_filt (B, 4)`` apply the filtered-
    search predicate (DESIGN.md §13) by nulling failing candidates to
    full padding semantics (id -1, score NEG_INF) — the kernel's rule,
    so filtered parity holds per backend.
    """
    b = q_emb.shape[0]
    cand_emb = buf_emb[top_c].reshape(b, -1, buf_emb.shape[-1])
    cand_loc = buf_loc[top_c].reshape(b, -1, 2)
    cand_ids = buf_ids[top_c].reshape(b, -1)
    cand_scale = (None if buf_scale is None
                  else buf_scale[top_c].reshape(b, -1))
    if buf_attrs is not None:
        cand_attrs = buf_attrs[top_c].reshape(b, -1, buf_attrs.shape[-1])
        pred = filters_lib.predicate_mask(cand_attrs, q_filt[:, None, :])
        cand_ids = jnp.where(pred, cand_ids, -1)
    st = score_candidates(q_emb, q_loc, w_st, cand_emb, cand_loc, cand_ids,
                          w_hat, dist_max=dist_max, cand_scale=cand_scale)
    scores, pos = jax.lax.top_k(st, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    return scores, ids


# ---------------------------------------------------------------------------
# Cluster-major execution (DESIGN.md §10): plan → score once → merge
# ---------------------------------------------------------------------------


def merge_cluster_major(part_scores, part_ids, roster, *, b: int, cr: int,
                        k: int):
    """Fold per-roster-slot partial top-k lists back into per-query ones.

    ``part_scores`` / ``part_ids`` (u_max, Qcap, k) are the cluster-major
    partials (kernel or dense); ``roster`` (u_max, Qcap) maps each slot
    to its flattened (query, route) index in ``[0, B·cr)`` with ``B·cr``
    on empty slots. The inverse scatter drops empty slots into an
    overflow row, reshapes to ``(B, cr·k)``, and one top-k per query
    folds the ``cr`` routes — the same undispatch the distributed path
    uses (core/serving.py step 4). (query, route) pairs dropped at
    ``Qcap`` saturation simply contribute ``(-1, NEG_INF)`` entries:
    graceful degradation, identical to the dispatch path's.

    Returns (scores (B, k) f32 descending, ids (B, k) i32 global object
    ids, -1 past-the-end) — the exact contract of the query-major paths.
    """
    n = b * cr
    with jax.named_scope("merge"):
        flat = roster.reshape(-1)
        back_v = jnp.full((n + 1, k), NEG_INF, jnp.float32)
        back_i = jnp.full((n + 1, k), -1, jnp.int32)
        back_v = back_v.at[flat].set(part_scores.reshape(-1, k))
        back_i = back_i.at[flat].set(
            part_ids.reshape(-1, k).astype(jnp.int32))
        per_q_v = back_v[:n].reshape(b, cr * k)
        per_q_i = back_i[:n].reshape(b, cr * k)
        scores, pos = jax.lax.top_k(per_q_v, k)
        ids = jnp.take_along_axis(per_q_i, pos, axis=1)
    return scores, ids


def dense_cluster_major(q_emb, q_loc, w_st, top_c, buf_emb, buf_loc, buf_ids,
                        w_hat, *, k: int, dist_max: float, buf_scale=None,
                        buf_attrs=None, q_filt=None,
                        qcap: Optional[int] = None):
    """Dense mirror of the cluster-major plan — the parity oracle.

    Same contract as :func:`dense_routed_topk`, same execution model as
    the ``pallas-cm`` kernel: dedupe the batch's routed clusters
    (``serving.cluster_major_plan``), gather each DISTINCT cluster's
    buffer once (``u_max ≤ min(B·cr, c)`` rows instead of ``B·cr``),
    score it against its whole query roster via the shared
    :func:`score_candidates`, and fold the per-slot partial top-k lists
    with :func:`merge_cluster_major`. Results are bit-compatible with
    the query-major backends modulo tie order within equal scores.
    """
    from repro.core import serving as serving_lib   # lazy: serving imports us

    b = q_emb.shape[0]
    c, cap, _ = buf_emb.shape
    cr = top_c.shape[1]
    n = b * cr
    u, roster, _, _ = serving_lib.cluster_major_plan(top_c, n_clusters=c,
                                                     qcap=qcap)
    qidx = serving_lib.roster_query_rows(roster, cr=cr, n_total=n)
    cand_scale = buf_scale[u][:, None] if buf_scale is not None else None
    cand_ids = buf_ids[u][:, None]                        # (u_max, 1, cap)
    if buf_attrs is not None:
        # filtered rows take full padding semantics (id -1 → NEG_INF),
        # exactly the kernel's rule — see dense_routed_topk
        pred = filters_lib.predicate_mask(
            buf_attrs[u][:, None], q_filt[qidx][:, :, None, :])
        cand_ids = jnp.where(pred, cand_ids, -1)   # (u_max, Qcap, cap)
    st = score_candidates(
        q_emb[qidx], q_loc[qidx], w_st[qidx],
        buf_emb[u][:, None], buf_loc[u][:, None], cand_ids,
        w_hat, dist_max=dist_max, cand_scale=cand_scale)  # (u_max, Qcap, cap)
    st = jnp.where((roster < n)[..., None], st, NEG_INF)  # empty roster slots
    kk = min(k, cap)
    vals, pos = jax.lax.top_k(st, kk)
    ids = jnp.take_along_axis(
        jnp.broadcast_to(cand_ids, st.shape), pos, axis=-1)
    ids = jnp.where((roster < n)[..., None], ids, -1)
    if kk < k:                       # k > cap: pad partials like the kernel
        pad = ((0, 0), (0, 0), (0, k - kk))
        vals = jnp.pad(vals, pad, constant_values=NEG_INF)
        ids = jnp.pad(ids, pad, constant_values=-1)
    return merge_cluster_major(vals, ids, roster, b=b, cr=cr, k=k)


# ---------------------------------------------------------------------------
# The routed query phase: encode → route → score → top-k
# ---------------------------------------------------------------------------


def _encode_and_route(cfg, rel_params, index_params, norm, q_tokens,
                      q_mask, q_loc, *, cr: int,
                      weight_mode: Optional[str] = None):
    """The query phase's prefix, shared by every plan that routes:
    encode (scope ``tower``), then index features, the top-``cr``
    clusters and, given ``weight_mode``, the mixing weights (scope
    ``route``). → (q_emb (B, d), top_c (B, cr), w (B, 2) or None)."""
    with jax.named_scope("tower"):
        q_emb = relevance.encode_queries(rel_params, q_tokens, q_mask, cfg)
    with jax.named_scope("route"):
        feats = index_lib.build_features(q_emb, q_loc, norm)
        top_c, _ = index_lib.route_queries(index_params, feats, cr=cr)
        w = (None if weight_mode is None else
             relevance.st_weights(rel_params, q_emb, weight_mode=weight_mode))
    return q_emb, top_c, w


def _routed_topk(q_emb, q_loc, w, top_c, buf_emb, buf_loc, buf_ids,
                 buf_scale, w_hat, *, k: int, backend: str, interpret: bool,
                 dist_max: float, block_n: int, precision: str,
                 buf_attrs=None, q_filt=None, n_valid=None):
    """Backend dispatch for the routed scan: score the ``top_c``-routed
    clusters of an explicit buffer set and keep the top ``k`` — the body
    shared by :func:`make_query_fn` (inline, after encode+route) and
    :func:`make_shard_topk_fn` (per shard, routes pre-localized).
    ``backend`` must be resolved (never "auto"). ``buf_attrs``/``q_filt``
    (pass both or neither) engage the filtered variants (DESIGN.md §13).
    ``n_valid`` (int32 scalar, may be traced; default: all) counts the
    batch's leading rows that are queries: the kernels stream nothing
    for the padding rows after them, whose answers are padding pairs.
    Returns (ids, scores, tiles): ``tiles`` int32 ``(2,)`` counts the
    kernel grid's tiles that were streamed, and all its tiles (both 0
    on the dense backends).
    """
    # f32/bf16 stream no scales: the astype upcast is the whole dequant
    scale = buf_scale if precision == "int8" else None
    with jax.named_scope("scan"):
        n_tiles = None     # the kernels' streamed tiles per grid row
        if backend == "pallas":
            from repro.kernels import fused_topk_score as fts
            score, ids = fts.fused_topk_score_routed(
                q_emb, q_loc, w, top_c, buf_emb, buf_loc, buf_ids, w_hat,
                k=k, dist_max=dist_max, block_n=block_n, buf_scale=scale,
                buf_attrs=buf_attrs, q_filt=q_filt, n_valid=n_valid,
                interpret=interpret)
            n_tiles, per_row = fts.routed_tiles(
                buf_ids, top_c, block_n=block_n, n_valid=n_valid)
        elif backend == "pallas-cm":
            # cluster-major (DESIGN.md §10): dedupe the routed clusters,
            # stream each distinct one ONCE against its query roster
            from repro.core import serving as serving_lib
            from repro.kernels import fused_topk_score as fts
            b = q_emb.shape[0]
            cr = top_c.shape[1]
            n = b * cr
            qcap = min(-(-n // 8) * 8, CLUSTER_MAJOR_QCAP)
            u, roster, _, _ = serving_lib.cluster_major_plan(
                top_c, n_clusters=buf_emb.shape[0], qcap=qcap)
            qidx = serving_lib.roster_query_rows(roster, cr=cr, n_total=n)
            q_filt_r = q_filt[qidx] if q_filt is not None else None
            # pairs of padding rows follow the queries' (roster values)
            n_live = n if n_valid is None else n_valid * cr
            ps, pi = fts.fused_topk_score_cluster_major(
                q_emb[qidx], q_loc[qidx], w[qidx], u, roster,
                buf_emb, buf_loc, buf_ids, w_hat, k=k, dist_max=dist_max,
                n_total=n, block_n=block_n, buf_scale=scale,
                buf_attrs=buf_attrs, q_filt_r=q_filt_r, n_live=n_live,
                interpret=interpret)
            n_tiles, per_row = fts.cluster_major_tiles(
                buf_ids, u, roster, n_live=n_live, block_n=block_n)
            score, ids = merge_cluster_major(ps, pi, roster, b=b, cr=cr, k=k)
        elif backend == "dense-cm":
            score, ids = dense_cluster_major(
                q_emb, q_loc, w, top_c, buf_emb, buf_loc, buf_ids, w_hat,
                k=k, dist_max=dist_max, buf_scale=scale,
                buf_attrs=buf_attrs, q_filt=q_filt)
        else:
            score, ids = dense_routed_topk(
                q_emb, q_loc, w, top_c, buf_emb, buf_loc, buf_ids, w_hat,
                k=k, dist_max=dist_max, buf_scale=scale,
                buf_attrs=buf_attrs, q_filt=q_filt)
        tiles = (jnp.zeros((2,), jnp.int32) if n_tiles is None else
                 jnp.stack([jnp.sum(n_tiles), n_tiles.size * per_row]))
    return ids, score, tiles


def make_query_fn(cfg, *, cr: int = 1, k: int = 20, backend: str = "auto",
                  interpret: Optional[bool] = None,
                  dist_max: float = 1.4142, weight_mode: str = "mlp",
                  block_n: int = 512, precision: str = "f32",
                  filtered: bool = False):
    """Build the jitted query-phase function (paper Algorithm 1).

    The returned function runs the whole serve path in one XLA program:
    encode queries (dual-encoder), build index features (Eq. 9–10),
    route to the top-``cr`` clusters (Eq. 11), score those clusters'
    resident objects, and keep the top ``k``.

    signature: fn(rel_params, index_params, w_hat, norm, buf_emb,
                  buf_loc, buf_ids, buf_scale, q_tokens, q_mask, q_loc,
                  n_valid=None)
               -> (ids (B, k) global object ids, scores (B, k),
                   tiles (2,) int32)

    where ``rel_params`` / ``index_params`` are the trained relevance
    and cluster-classifier params, ``w_hat (t,)`` is the serve-form
    spatial step table (``spatial.extract_lookup``), ``norm`` the
    location normalizer bounds (``index.loc_normalizer``), and
    ``buf_*`` the padded cluster buffers (module docstring) —
    ``buf_scale (c, cap)`` the per-row dequant scales of quantized
    buffers (``index.quantize_rows``; all-ones, and unused, below
    int8). Rows past the valid candidates come back as
    ``(-1, NEG_INF)`` pairs. ``n_valid`` (int32 scalar; default all)
    counts the batch's leading rows that are queries, as
    :func:`run_batched` passes it: the kernels scan nothing for the
    zero-padding rows after them. ``tiles`` counts the scan kernel's
    grid tiles that were streamed (the live tiles of the clusters the
    queries routed to) and all its tiles (zeros on the dense backends).

    Keyword args: ``cr`` routed clusters per query; ``k`` results per
    query; ``backend``/``interpret`` per the module docstring
    (``"pallas"`` runs gather-free — scalar-prefetched routing into the
    resident buffers, in-kernel cr-merge; ``"pallas-cm"`` /
    ``"dense-cm"`` run the cluster-major plan — each distinct routed
    cluster streamed once per batch, DESIGN.md §10; ``"dense"`` is the
    jnp reference; ``"auto"`` picks query-major per platform — the
    per-batch cluster-major upgrade lives in
    :meth:`QueryEngine.query`); ``dist_max`` the
    distance normalizer of Eq. 5 (√2 for the unit box);
    ``weight_mode`` how the (textual, spatial) mixing weights are
    produced; ``block_n`` the Pallas streaming tile size; ``precision``
    the buffers' storage tier (DESIGN.md §9) — routing, SRel, and the
    padding mask are identical across tiers, only TRel dequantizes
    (in-kernel on pallas, via the same per-row scales on dense, so
    backend parity holds *within* every tier).

    ``filtered=True`` is the STATIC filtered-search plan dimension
    (DESIGN.md §13): the signature grows ``buf_attrs (c, cap, 3)`` after
    ``buf_scale`` and ``q_filt (B, 4)`` after ``q_loc``, and the
    predicate mask is applied in-scan. ``filtered=False`` builds the
    exact pre-filter program — zero extra bytes streamed.

    The result is a ``jax.jit`` function: every distinct batch shape
    triggers one compile, so serve fixed shapes via :func:`run_batched`
    (or hold a :class:`QueryEngine`, which does both for you).
    """
    backend, interpret = resolve_backend(backend, interpret)
    if precision not in index_lib.PRECISIONS:
        raise ValueError(f"precision must be one of {index_lib.PRECISIONS}, "
                         f"got {precision!r}")

    def _run(rel_params, index_params, w_hat, norm, buf_emb, buf_loc,
             buf_ids, buf_scale, q_tokens, q_mask, q_loc, buf_attrs, q_filt,
             n_valid):
        q_emb, top_c, w = _encode_and_route(
            cfg, rel_params, index_params, norm, q_tokens, q_mask, q_loc,
            cr=cr, weight_mode=weight_mode)
        return _routed_topk(q_emb, q_loc, w, top_c, buf_emb, buf_loc,
                            buf_ids, buf_scale, w_hat, k=k, backend=backend,
                            interpret=interpret, dist_max=dist_max,
                            block_n=block_n, precision=precision,
                            buf_attrs=buf_attrs, q_filt=q_filt,
                            n_valid=n_valid)

    if filtered:
        def query_fn(rel_params, index_params, w_hat, norm, buf_emb,
                     buf_loc, buf_ids, buf_scale, buf_attrs, q_tokens,
                     q_mask, q_loc, q_filt, n_valid=None):
            return _run(rel_params, index_params, w_hat, norm, buf_emb,
                        buf_loc, buf_ids, buf_scale, q_tokens, q_mask,
                        q_loc, buf_attrs, q_filt, n_valid)
    else:
        def query_fn(rel_params, index_params, w_hat, norm, buf_emb,
                     buf_loc, buf_ids, buf_scale, q_tokens, q_mask, q_loc,
                     n_valid=None):
            return _run(rel_params, index_params, w_hat, norm, buf_emb,
                        buf_loc, buf_ids, buf_scale, q_tokens, q_mask,
                        q_loc, None, None, n_valid)

    return jax.jit(query_fn)


def make_route_fn(cfg, *, cr: int = 1):
    """Build the jitted route-only prefix of the query phase: encode →
    features → top-``cr`` clusters. ``fn(rel_params, index_params, norm,
    q_tokens, q_mask, q_loc) -> top_c (B, cr) int32``.

    The auto heuristic (:func:`cluster_major_variant`) and the skew
    benchmarks use it to measure a batch's dedup factor ``B·cr/U``
    without running the scan."""
    def route_fn(rel_params, index_params, norm, q_tokens, q_mask, q_loc):
        return _encode_and_route(cfg, rel_params, index_params, norm,
                                 q_tokens, q_mask, q_loc, cr=cr)[1]

    return jax.jit(route_fn)


# ---------------------------------------------------------------------------
# Mesh-sharded execution (DESIGN.md §12): shared prefix → per-shard
# scan → host tree merge. The shard_topk idiom of
# pseudo_labels.mine_negatives_sharded, promoted to the serving path.
# ---------------------------------------------------------------------------


def make_prefix_fn(cfg, *, cr: int = 1, weight_mode: str = "mlp"):
    """Build the jitted GLOBAL prefix of the sharded query phase:
    encode → mixing weights → route, run ONCE per chunk on the default
    device (router + relevance params are replicated). ``fn(rel_params,
    index_params, norm, q_tokens, q_mask, q_loc) -> (q_emb (B, d),
    w (B, 2), top_c (B, cr))``.

    One program for EVERY shard count (its shapes don't depend on the
    mesh), so ``q_emb``/``w``/``top_c`` are bit-identical across
    placements — the first leg of the parity contract."""
    def prefix_fn(rel_params, index_params, norm, q_tokens, q_mask, q_loc):
        q_emb, top_c, w = _encode_and_route(
            cfg, rel_params, index_params, norm, q_tokens, q_mask, q_loc,
            cr=cr, weight_mode=weight_mode)
        return q_emb, w, top_c

    return jax.jit(prefix_fn)


def make_shard_topk_fn(*, k: int = 20, backend: str = "dense",
                       interpret: Optional[bool] = None,
                       dist_max: float = 1.4142, block_n: int = 512,
                       precision: str = "f32", filtered: bool = False):
    """Build the jitted PER-SHARD suffix of the sharded query phase:
    score one shard's local cluster buffers against pre-encoded queries
    and pre-localized routes, any backend (DESIGN.md §12).

    signature: fn(w_hat, buf_emb, buf_loc, buf_ids, buf_scale,
                  q_emb, q_loc, w, top_c) -> (ids (B, k), scores (B, k))

    ``buf_*`` are one shard's local buffers (``c_local + 1`` clusters,
    the last the sentinel empty cluster) and ``top_c`` holds LOCAL rows
    (``serving.localize_routes`` — off-shard routes point at the
    sentinel, scoring ``(−1, NEG_INF)`` like padding). Execution is
    pinned by data placement: the buffers are device-committed
    (``sharding.ClusterShards.parts``), so jax runs each shard's call
    on its shard's device — pass the query-side arrays as host numpy
    (uncommitted) or the mixed-commitment check will refuse the call.

    Per-candidate scores are bitwise identical to the single-device
    scan: the same ``cr·cap`` candidate rows (off-shard ones masked),
    the same per-row reductions, so per-shard top-k + the host tree
    merge (:func:`merge_shard_topk`) reproduce the single-device top-k
    exactly whenever scores at the k boundary are distinct.

    ``filtered=True`` grows the signature with ``buf_attrs`` after
    ``buf_scale`` and ``q_filt (B, 4)`` last, mirroring
    :func:`make_query_fn` — the predicate is shard-local like every
    other per-candidate term, so the tree merge composes unchanged."""
    backend, interpret = resolve_backend(backend, interpret)
    if precision not in index_lib.PRECISIONS:
        raise ValueError(f"precision must be one of {index_lib.PRECISIONS}, "
                         f"got {precision!r}")

    if filtered:
        def shard_fn(w_hat, buf_emb, buf_loc, buf_ids, buf_scale, buf_attrs,
                     q_emb, q_loc, w, top_c, q_filt):
            return _routed_topk(q_emb, q_loc, w, top_c, buf_emb, buf_loc,
                                buf_ids, buf_scale, w_hat, k=k,
                                backend=backend, interpret=interpret,
                                dist_max=dist_max, block_n=block_n,
                                precision=precision, buf_attrs=buf_attrs,
                                q_filt=q_filt)[:2]
    else:
        def shard_fn(w_hat, buf_emb, buf_loc, buf_ids, buf_scale,
                     q_emb, q_loc, w, top_c):
            return _routed_topk(q_emb, q_loc, w, top_c, buf_emb, buf_loc,
                                buf_ids, buf_scale, w_hat, k=k,
                                backend=backend, interpret=interpret,
                                dist_max=dist_max, block_n=block_n,
                                precision=precision)[:2]

    return jax.jit(shard_fn)


def merge_shard_topk(parts, *, k: Optional[int] = None):
    """Pairwise tree-reduce per-shard partial top-k lists (host, numpy)
    — ``pseudo_labels.shard_topk``'s merge, promoted to serving.

    ``parts`` is a sequence of per-shard ``(ids (B, m), scores (B, m))``
    pairs in shard order. Pairs are merged pairwise (top-k of top-ks —
    each level keeps the best ``k``) until one list remains; ``k``
    defaults to the partial width. The per-level sort is STABLE with
    the lower-index operand's entries first, so an exact cross-shard
    score tie resolves in shard order — the one documented divergence
    from single-device tie order (DESIGN.md §12); within a shard ties
    already match (same ``jax.lax.top_k``). Returns ``(ids (B, k) i32,
    scores (B, k) f32 descending)`` — the engine's output contract.
    """
    items = [(np.asarray(i), np.asarray(v, np.float32)) for i, v in parts]
    if not items:
        raise ValueError("merge_shard_topk: no partial lists")
    if k is None:
        k = items[0][0].shape[-1]

    def merge2(a, b):
        ci = np.concatenate([a[0], b[0]], axis=-1)
        cv = np.concatenate([a[1], b[1]], axis=-1)
        order = np.argsort(-cv, axis=-1, kind="stable")[..., :k]
        return (np.take_along_axis(ci, order, axis=-1),
                np.take_along_axis(cv, order, axis=-1))

    while len(items) > 1:
        nxt = [merge2(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    ids, scores = items[0]
    return (ids[..., :k].astype(np.int32),
            scores[..., :k].astype(np.float32))


# ---------------------------------------------------------------------------
# Delta-segment scan + merge (the LSM mutation path, DESIGN.md §11)
# ---------------------------------------------------------------------------


def make_delta_scan_fn(cfg, *, k: int = 20, dist_max: float = 1.4142,
                       weight_mode: str = "mlp", precision: str = "f32",
                       filtered: bool = False):
    """Build the jitted brute-force scan over a delta segment's rows.

    The delta is small by construction (the server compacts it past a
    threshold), so it is scored WITHOUT routing: every query sees every
    delta row — a freshly inserted object can never be hidden by a
    routing miss before compaction folds it into its cluster.

    signature: fn(rel_params, w_hat, d_emb (m, d), d_scale (m,),
                  d_loc (m, 2), d_ids (m,), q_tokens, q_mask, q_loc)
               -> (ids (B, k), scores (B, k))

    with the usual ``(-1, NEG_INF)`` padding convention; padding rows in
    the delta arrays (``ids == -1``) mask exactly like buffer padding.
    Scoring goes through :func:`score_candidates` with the same
    precision semantics as the base backends, so a row scores
    bit-identically whether it is delta-resident or compacted (same
    stored quantized values, same dequant, same ST form).

    ``filtered=True`` grows the signature with ``d_attrs (m, 3)`` after
    ``d_ids`` and ``q_filt (B, 4)`` last: delta rows obey the same
    predicate as compacted ones (a fresh insert must never leak across
    a tenant filter while it waits for compaction).
    """
    if precision not in index_lib.PRECISIONS:
        raise ValueError(f"precision must be one of {index_lib.PRECISIONS}, "
                         f"got {precision!r}")

    def _scan(rel_params, w_hat, d_emb, d_scale, d_loc, d_ids, d_attrs,
              q_tokens, q_mask, q_loc, q_filt):
        with jax.named_scope("tower"):
            q_emb = relevance.encode_queries(rel_params, q_tokens, q_mask,
                                             cfg)
        with jax.named_scope("route"):
            w = relevance.st_weights(rel_params, q_emb,
                                     weight_mode=weight_mode)
        scale = d_scale[None] if precision == "int8" else None
        with jax.named_scope("scan"):
            ids_eff = d_ids[None]                           # (1, m)
            if d_attrs is not None:
                # failing rows take full padding semantics (id -1), the
                # shared filtered rule of every scan in this module
                pred = filters_lib.predicate_mask(d_attrs[None],
                                                  q_filt[:, None, :])
                ids_eff = jnp.where(pred, ids_eff, -1)      # (B, m)
            st = score_candidates(q_emb, q_loc, w, d_emb[None], d_loc[None],
                                  ids_eff, w_hat, dist_max=dist_max,
                                  cand_scale=scale)         # (B, m)
            kk = min(k, d_emb.shape[0])
            vals, pos = jax.lax.top_k(st, kk)
            ids = jnp.take_along_axis(
                jnp.broadcast_to(ids_eff, st.shape), pos, axis=1
            ).astype(jnp.int32)
            if kk < k:
                pad = ((0, 0), (0, k - kk))
                vals = jnp.pad(vals, pad, constant_values=NEG_INF)
                ids = jnp.pad(ids, pad, constant_values=-1)
        return ids, vals

    if filtered:
        def scan_fn(rel_params, w_hat, d_emb, d_scale, d_loc, d_ids,
                    d_attrs, q_tokens, q_mask, q_loc, q_filt):
            return _scan(rel_params, w_hat, d_emb, d_scale, d_loc, d_ids,
                         d_attrs, q_tokens, q_mask, q_loc, q_filt)
    else:
        def scan_fn(rel_params, w_hat, d_emb, d_scale, d_loc, d_ids,
                    q_tokens, q_mask, q_loc):
            return _scan(rel_params, w_hat, d_emb, d_scale, d_loc, d_ids,
                         None, q_tokens, q_mask, q_loc, None)

    return jax.jit(scan_fn)


def merge_delta(base_ids, base_scores, delta_ids=None, delta_scores=None, *,
                tombstones=None, k=None):
    """Merge a delta scan's partial top-k into the base engine's (host).

    ``tombstones`` (sorted id array) is applied to the BASE lists only —
    tombstoned entries become ``(-1, NEG_INF)`` pairs and sink out of
    the top-k. Delta rows are live by construction (``DeltaSegment.delete``
    drops them physically), so the delta lists are merged unfiltered.

    Ids may appear in both lists only if the same id was inserted twice
    without an intervening delete — a contract violation upstream
    (``DeltaSegment.insert`` raises on delta-resident duplicates).

    The sort is stable with base entries first: on an exact score tie
    the base row wins, matching the "earlier candidate wins" tie rule of
    ``jax.lax.top_k`` inside the backends. ``k`` defaults to the base
    list width; pass it explicitly when the base lists were over-fetched
    to absorb tombstone kills (:data:`TOMBSTONE_K_BUCKET`). Returns
    ``(ids (B, k) i32, scores (B, k) f32 descending)`` — the engine's
    output contract.
    """
    base_ids = np.asarray(base_ids)
    base_scores = np.asarray(base_scores, np.float32)
    if k is None:
        k = base_ids.shape[-1]
    if tombstones is not None and len(tombstones):
        dead = np.isin(base_ids, np.asarray(tombstones))
        base_ids = np.where(dead, -1, base_ids)
        base_scores = np.where(dead, NEG_INF, base_scores)
    if delta_ids is None:
        cat_i = base_ids
        cat_v = base_scores
    else:
        cat_i = np.concatenate([base_ids, np.asarray(delta_ids)], axis=-1)
        cat_v = np.concatenate(
            [base_scores, np.asarray(delta_scores, np.float32)], axis=-1)
    order = np.argsort(-cat_v, axis=-1, kind="stable")[..., :k]
    ids = np.take_along_axis(cat_i, order, axis=-1).astype(np.int32)
    scores = np.take_along_axis(cat_v, order, axis=-1).astype(np.float32)
    return ids, scores


# ---------------------------------------------------------------------------
# Static-shape batch padding (one compile per batch shape)
# ---------------------------------------------------------------------------


def pad_leading(arr, batch: int):
    """Zero-pad axis 0 of ``arr`` up to ``batch`` rows (numpy, no-op jit)."""
    n = arr.shape[0]
    if n == batch:
        return arr
    assert n < batch, (n, batch)
    return np.pad(arr, ((0, batch - n),) + ((0, 0),) * (arr.ndim - 1))


def run_batched(fn: Callable, arrays: Sequence[np.ndarray], *, batch: int,
                chunk_outputs: int = 0, with_rows: bool = False):
    """Map a jitted ``fn`` over ``arrays`` in static-shape chunks.

    ``arrays`` is a sequence of equal-leading-dim inputs (e.g. tokens,
    mask, locations, each with ``n`` rows). They are walked in lockstep
    ``batch`` rows at a time, and every chunk fed to ``fn`` has exactly
    ``batch`` rows: the trailing partial chunk is zero-padded up to
    ``batch`` (:func:`pad_leading`) and the corresponding output rows
    trimmed. ``fn`` therefore sees ONE batch shape and jit-compiles
    exactly once, no matter what ``n`` is.

    ``fn(*chunks) -> array | tuple of arrays`` (leading dim ``batch``);
    returns the per-chunk outputs concatenated back to leading dim
    ``n`` as ``np.ndarray`` — a single array if ``fn`` returned one,
    else a tuple. The last ``chunk_outputs`` outputs describe a whole
    chunk rather than its rows (a counter, say): they are copied back
    with the rows, never trimmed, and come back stacked, one per chunk.
    ``with_rows=True`` passes ``fn`` one more argument, last: the
    chunk's real row count (an int32 scalar, traced under jit), so it
    can skip the padding rows' work.

    Padding rows are all-zeros; make sure ``fn`` is row-independent
    (every query-phase function here is), so pad rows can't perturb
    real rows. This is the padding rule the whole repo shares: the
    retriever, the brute-force oracle, corpus embedding, and the
    streaming server's micro-batch flushes (core/server.py) — which is
    why a micro-batched result is bit-identical to an offline one at a
    fixed backend. (An AUTO engine picks query- vs cluster-major per
    ``QueryEngine.query`` call, so differently-composed batches may
    take different — bit-compatible modulo tie order — flavors;
    DESIGN.md §10.)

    Execution is pipelined: chunk ``i``'s outputs are materialized on
    the host (``np.asarray`` — a device sync) only *after* chunk
    ``i+1``'s work has been dispatched, so on an async backend the
    device-to-host transfer of one chunk overlaps the next chunk's
    compute instead of serializing the serving path.

    Each chunk's pad, upload and launch runs under the host span
    ``repro.dispatch``, and each chunk's copy back under ``repro.sync``
    (profiler annotations: no cost unless a trace is being taken).
    """
    n = arrays[0].shape[0]
    assert all(a.shape[0] == n for a in arrays), [a.shape for a in arrays]
    annotate = jax.profiler.TraceAnnotation
    outs = None
    pending = None            # chunk i's device results, not yet synced

    def sync(p_res, p_rows):
        with annotate("repro.sync"):
            # one copy back of all outputs: device_get starts every
            # transfer before it waits on the first
            for j, (o, r) in enumerate(zip(outs, jax.device_get(p_res))):
                r = np.asarray(r)
                o.append(r[:p_rows] if j < n_rows else r)

    for s in range(0, n, batch):
        e = min(s + batch, n)
        with annotate("repro.dispatch"):
            chunk = [pad_leading(np.asarray(a[s:e]), batch) for a in arrays]
            if with_rows:
                chunk.append(np.int32(e - s))
            res = fn(*[jnp.asarray(c) for c in chunk])  # dispatch, no sync
        res = res if isinstance(res, (tuple, list)) else (res,)
        if outs is None:
            outs = [[] for _ in res]
            n_rows = len(res) - chunk_outputs
        if pending is not None:
            sync(*pending)                              # chunk i-1
        pending = (res, e - s)
    if pending is not None:
        sync(*pending)
    cat = tuple(np.concatenate(o, axis=0) if j < n_rows else np.stack(o)
                for j, o in enumerate(outs))
    return cat if len(cat) > 1 else cat[0]


# ---------------------------------------------------------------------------
# Stateful façade
# ---------------------------------------------------------------------------


class QueryEngine:
    """Stateless query executor over an immutable :class:`IndexSnapshot`.

    The engine owns exactly two things: a *reference* to the current
    snapshot (core/snapshot.py — all params/buffers live there, frozen)
    and a cache of traced plans keyed ``(batch, k, cr, backend, precision)``. Both
    the single-host path (``ListRetriever.query``) and the streaming
    server (core/server.py, DESIGN.md §7–§8) hold one; the distributed
    dispatch path shares :func:`score_candidates` instead (its data
    movement is the point).

    Snapshot swaps are atomic: :meth:`publish` replaces the reference in
    one assignment (it validates ``meta.cfg_digest`` — params from a
    different model config never sneak in). Every :meth:`query` call
    reads the snapshot reference ONCE up front, so a concurrent publish
    can never tear a batch across two snapshots. Plans survive swaps
    that preserve buffer shapes — snapshot contents are jit *arguments*,
    so same shapes ⇒ no retrace, and a shape-changing swap just
    retraces lazily.
    """

    def __init__(self, snapshot, *, backend: str = "auto",
                 interpret: Optional[bool] = None,
                 max_plans: int = DEFAULT_PLAN_CACHE_SIZE,
                 cm_threshold: float = CLUSTER_MAJOR_DEDUP_THRESHOLD):
        self._snapshot = snapshot
        self.backend, self.interpret = resolve_backend(backend, interpret)
        # "auto" keeps its per-batch cluster-major upgrade (DESIGN.md
        # §10); an explicit backend is always served verbatim
        self._auto_cm = backend == "auto"
        self.cm_threshold = float(cm_threshold)
        self.last_dedup_factor: Optional[float] = None
        # encoder_passes: chunks dispatched of plans that run the query
        # tower (query plans, the auto pick's route, the sharded prefix,
        # delta scans) — a flush that encodes its rows once reads 1;
        # scan_tiles_live / scan_tiles_grid: the scan kernels' grid tiles
        # streamed (live rows of the queries' clusters), and all of them
        self.stats = {"encoder_passes": 0, "scan_tiles_live": 0,
                      "scan_tiles_grid": 0}
        self.max_plans = int(max_plans)
        self._plans: "collections.OrderedDict" = collections.OrderedDict()
        self._route_plans = {}          # keyed cr: tiny, never evicted
        self._delta_plans = {}          # keyed (k, precision): tiny too
        self._prefix_plans = {}         # keyed cr: the sharded-path prefix
        # shard fault tolerance (DESIGN.md §15): health + hedging state
        # for the mesh-sharded scan, plus the last query's coverage
        self.last_coverage: float = 1.0
        self.last_down_shards: Tuple[int, ...] = ()
        self.shard_stats = {"hedged_scans": 0, "scan_retries": 0,
                            "down_skips": 0, "host_scans": 0,
                            "recoveries": 0}
        self.shard_retries = SHARD_SCAN_RETRIES
        self.shard_backoff_ms = SHARD_RETRY_BACKOFF_MS
        self.shard_backoff_max_ms = SHARD_RETRY_BACKOFF_MAX_MS
        self.shard_down_after = SHARD_DOWN_AFTER
        self.hedge_probe_every = SHARD_HEDGE_PROBE_EVERY
        self._shard_health = None       # lazy: sized on first sharded query
        self._shard_monitor = None      # StragglerMonitor over device scans
        self._hedged = {}               # shard → hedged-scan count
        self._host_parts = {}           # host replicas, keyed by placement

    # --- construction -----------------------------------------------------

    @classmethod
    def from_snapshot(cls, snapshot, *, backend: str = "auto",
                      interpret: Optional[bool] = None) -> "QueryEngine":
        return cls(snapshot, backend=backend, interpret=interpret)

    @classmethod
    def from_parts(cls, cfg, rel_params, index_params, norm, buffers, *,
                   dist_max: float, spatial_mode: str = "step",
                   weight_mode: str = "mlp", backend: str = "auto",
                   interpret: Optional[bool] = None) -> "QueryEngine":
        """Convenience: wrap loose artifacts into a version-0 snapshot.
        Serving code should hold real snapshots (repro.api.build/load)."""
        from repro.core import snapshot as snapshot_lib
        snap = snapshot_lib.IndexSnapshot.from_parts(
            cfg, rel_params, index_params, norm, buffers,
            dist_max=dist_max, spatial_mode=spatial_mode,
            weight_mode=weight_mode)
        return cls(snap, backend=backend, interpret=interpret)

    # --- the snapshot reference (the ONLY mutable state) ------------------

    @property
    def snapshot(self):
        return self._snapshot

    def publish(self, snapshot):
        """Atomically swap the served snapshot; returns the old one.

        Refuses a snapshot whose ``meta.cfg_digest`` differs from the
        current one — traced plans close over the model config, so a
        config change requires a NEW engine, not a swap. Single
        reference assignment ⇒ a concurrent :meth:`query` sees either
        the old snapshot or the new one, never a mix.
        """
        old = self._snapshot
        if snapshot.meta.cfg_digest != old.meta.cfg_digest:
            raise ValueError(
                f"publish: snapshot cfg_digest {snapshot.meta.cfg_digest} "
                f"!= engine's {old.meta.cfg_digest}; build a new engine "
                f"for a different model config")
        self._snapshot = snapshot
        return old

    # --- read-only views (back-compat with pre-snapshot callers) ----------

    @property
    def cfg(self):
        return self._snapshot.cfg

    @property
    def rel_params(self):
        return self._snapshot.rel_params

    @property
    def index_params(self):
        return self._snapshot.index_params

    @property
    def norm(self):
        return self._snapshot.norm

    @property
    def buffers(self):
        return self._snapshot.buffers

    @property
    def dist_max(self) -> float:
        return self._snapshot.meta.dist_max

    @property
    def spatial_mode(self) -> str:
        return self._snapshot.meta.spatial_mode

    @property
    def weight_mode(self) -> str:
        return self._snapshot.meta.weight_mode

    @property
    def w_hat(self):
        """Serve-form step table (Eq. 5) of the CURRENT snapshot."""
        return self._snapshot.w_hat

    # --- plans + execution ------------------------------------------------

    def query_fn(self, *, k: int, cr: int, backend: Optional[str] = None,
                 batch: Optional[int] = None,
                 precision: Optional[str] = None, filtered: bool = False):
        """The traced plan for ``(batch, k, cr, backend, precision,
        filtered)``. Plans are keyed on the batch shape too so a serving
        process can see its full plan inventory in ``_plans``; they never
        rebind snapshot state (everything is passed as jit arguments), so
        they survive every publish. ``precision`` defaults to the CURRENT
        snapshot's tier — a publish that changes precision simply traces
        (and caches) new plans under the new key. ``filtered`` is the
        static filtered-search dimension (DESIGN.md §13): filtered and
        unfiltered traffic never share a program."""
        backend = self.backend if backend is None else backend
        if precision is None:
            precision = self._snapshot.meta.precision
        key = (batch, k, cr, backend, precision, filtered)
        if key not in self._plans:
            # bounded LRU: hot-swaps, precision changes, and backend
            # upgrades retrace freely without growing the cache forever
            while len(self._plans) >= self.max_plans:
                self._plans.popitem(last=False)
            self._plans[key] = make_query_fn(
                self.cfg, cr=cr, k=k, backend=backend,
                interpret=self.interpret, dist_max=self.dist_max,
                weight_mode=self.weight_mode, precision=precision,
                filtered=filtered)
        self._plans.move_to_end(key)
        return self._plans[key]

    def route_fn(self, *, cr: int):
        """The jitted route-only plan (:func:`make_route_fn`) for ``cr``:
        one per engine, tiny, never evicted."""
        if cr not in self._route_plans:
            self._route_plans[cr] = make_route_fn(self.cfg, cr=cr)
        return self._route_plans[cr]

    def route(self, q_tokens, q_mask, q_loc, *, cr: int = 1,
              snapshot=None):
        """Route-only prefix: → top_c (n, cr) int32 (device array).

        The auto heuristic and the skew benchmarks measure dedup with
        it (:meth:`route_fn`)."""
        snap = self._snapshot if snapshot is None else snapshot
        return self.route_fn(cr=cr)(
            snap.rel_params, snap.index_params, snap.norm,
            jnp.asarray(q_tokens), jnp.asarray(q_mask), jnp.asarray(q_loc))

    def _run_encoding(self, fn, arrays, *, batch: int, **kw):
        """:func:`run_batched` over a plan that runs the query tower,
        counting one encoder pass per chunk (``stats``)."""
        self.stats["encoder_passes"] += -(-np.shape(arrays[0])[0] // batch)
        return run_batched(fn, arrays, batch=batch, **kw)

    @functools.partial(jax.profiler.annotate_function, name="repro.pick")
    def pick_backend(self, q_tokens, q_mask, q_loc, *, cr: int, batch: int,
                     snapshot=None, base: Optional[str] = None) -> str:
        """Resolve the per-batch backend for an auto request (DESIGN.md
        §10): upgrade the hardware-resolved query-major ``base`` backend
        (default: this engine's own) to its cluster-major twin when the
        batch dedup factor ``B·cr/U`` crosses ``cm_threshold``.

        The structural bound ``batch·cr / min(batch·cr, c)`` is checked
        first — when the batch saturates the cluster set (``B·cr ≥
        threshold·c``, the common serving regime) no measurement is
        needed and the pick is data-independent. Otherwise the FIRST
        chunk is routed (:meth:`route` — the cheap encoder+MLP prefix)
        and the measured distinct-cluster count decides. The last
        factor used is kept in ``last_dedup_factor`` for observability.
        """
        snap = self._snapshot if snapshot is None else snapshot
        base = self.backend if base is None else base
        c, cap = snap.buffers["emb"].shape[:2]
        # shape guard first: refuse plans whose roster overhead outgrows
        # the stream they save (the plan is traced at the PADDED batch)
        if not cluster_major_feasible(batch, cr, c, cap):
            self.last_dedup_factor = None
            return base
        eff = min(batch, q_tokens.shape[0])
        dedup = (eff * cr) / min(eff * cr, c)     # structural lower bound
        if dedup < self.cm_threshold:
            # measure on the first chunk, PADDED to the static plan
            # shape: route_fn then compiles once per (batch, cr) — a
            # serving flush of any fill level reuses it instead of
            # retracing the encoder inside the latency-critical flush
            route = self.route_fn(cr=cr)
            top_c = self._run_encoding(
                lambda t, m, l: route(snap.rel_params, snap.index_params,
                                      snap.norm, t, m, l),
                [q_tokens[:eff], q_mask[:eff], q_loc[:eff]], batch=batch)
            dedup = (eff * cr) / max(len(np.unique(top_c)), 1)
        self.last_dedup_factor = float(dedup)
        return cluster_major_variant(base, dedup,
                                     threshold=self.cm_threshold)

    def prefix_fn(self, *, cr: int):
        """The jitted sharded-path prefix (:func:`make_prefix_fn`) for
        ``cr`` — one per engine regardless of shard count, so encode/
        route results are bit-identical across placements."""
        if cr not in self._prefix_plans:
            self._prefix_plans[cr] = make_prefix_fn(
                self.cfg, cr=cr, weight_mode=self.weight_mode)
        return self._prefix_plans[cr]

    def shard_topk_fn(self, *, k: int, backend: Optional[str] = None,
                      batch: Optional[int] = None,
                      precision: Optional[str] = None,
                      filtered: bool = False):
        """The traced per-shard plan (:func:`make_shard_topk_fn`),
        cached in the same bounded LRU as the query plans under the key
        ``("shard", batch, k, backend, precision, filtered)``. ONE
        program serves every shard — the local buffer shapes agree
        across shards by construction (sentinel + remainder padding),
        and jax compiles one executable per committed device."""
        backend = self.backend if backend is None else backend
        if precision is None:
            precision = self._snapshot.meta.precision
        key = ("shard", batch, k, backend, precision, filtered)
        if key not in self._plans:
            while len(self._plans) >= self.max_plans:
                self._plans.popitem(last=False)
            self._plans[key] = make_shard_topk_fn(
                k=k, backend=backend, interpret=self.interpret,
                dist_max=self.dist_max, precision=precision,
                filtered=filtered)
        self._plans.move_to_end(key)
        return self._plans[key]

    def _shard_state(self, n_shards: int):
        """Lazy per-mesh health state: a :class:`ShardHealth` +
        :class:`StragglerMonitor` pair sized to the current shard count
        (re-created when a publish changes the mesh width)."""
        from repro.distributed import resilience as resilience_lib

        if (self._shard_health is None
                or self._shard_health.n_shards != n_shards):
            self._shard_health = resilience_lib.ShardHealth(
                n_shards, down_after=self.shard_down_after)
            self._shard_monitor = resilience_lib.StragglerMonitor()
            self._hedged = {}
        return self._shard_health

    def _host_shard_part(self, snap, shards, s: int):
        """Host-side replica of shard ``s``'s local buffers, rebuilt
        from the snapshot's retained GLOBAL arrays (``with_mesh`` keeps
        them host-side for save — DESIGN.md §12) with the exact
        layout/fill convention of ``sharding.shard_cluster_buffers``:
        rows ``[0, len(group))`` hold the shard's clusters in ascending
        global order, everything above (including the sentinel row) is
        empty padding. The SAME jitted shard plan runs on it with
        all-host operands (default device), so a hedged or recovered
        scan is bit-identical to the device scan. Cached per placement
        object (a publish or recovery invalidates by identity)."""
        cache = self._host_parts
        if cache.get("key") != id(shards):
            self._host_parts = cache = {"key": id(shards)}
        part = cache.get(s)
        if part is None:
            g = np.flatnonzero(np.asarray(shards.shard_of) == s)
            rows = shards.c_local + 1        # + sentinel empty cluster
            fills = {"emb": 0, "loc": index_lib.PAD_LOC, "ids": -1,
                     "scale": 1, "attrs": 0, "counts": 0}
            part = {}
            for key, fill in fills.items():
                if key not in snap.buffers:
                    continue
                arr = np.asarray(snap.buffers[key])
                if key == "counts":
                    arr = arr.astype(np.int32)
                out = np.full((rows,) + arr.shape[1:], fill,
                              dtype=arr.dtype)
                out[:len(g)] = arr[g]
                part[key] = out
            cache[s] = part
        return part

    def down_signature(self) -> Tuple[int, ...]:
        """The currently-DOWN shard set — the cache-key component that
        keeps degraded results from ever serving as full-coverage ones
        (DESIGN.md §15)."""
        health = self._shard_health
        return () if health is None else health.down_shards()

    def recover_shard(self, s: int):
        """Online shard recovery (DESIGN.md §15): re-materialize shard
        ``s``'s device part from the snapshot's global host buffers
        (same placement/fill convention as ``shard_cluster_buffers``),
        atomically publish the patched placement, and flip the shard
        back UP. Placement-only — no version bump, no content change,
        and no ``SubscriptionRegistry`` dispatch (notifications flow
        only from insert publishes, so exactly-once delivery is
        untouched). Returns the snapshot now being served."""
        snap = self._snapshot
        shards = getattr(snap, "shards", None)
        if shards is None:
            raise ValueError("recover_shard: snapshot is not mesh-sharded")
        if not 0 <= s < shards.n_shards:
            raise ValueError(f"recover_shard: shard {s} out of range "
                             f"0..{shards.n_shards - 1}")
        host = self._host_shard_part(snap, shards, s)
        device = shards.devices[s]
        new_part = {key: jax.device_put(arr, device)
                    for key, arr in host.items()}
        parts = list(shards.parts)
        parts[s] = new_part
        new_shards = dataclasses.replace(shards, parts=tuple(parts))
        # single reference assignment, like publish(): a concurrent
        # query sees the old placement or the new one, never a mix
        self._snapshot = dataclasses.replace(snap, shards=new_shards)
        self._host_parts = {}           # placement identity changed
        if self._shard_health is not None:
            self._shard_health.mark_up(s)
        self._hedged.pop(s, None)
        self.shard_stats["recoveries"] += 1
        return self._snapshot

    def _query_sharded(self, snap, q_tokens, q_mask, q_loc, *, k: int,
                       cr: int, batch: int, backend: Optional[str],
                       fvals=None, filtered: bool = False):
        """The mesh-sharded scan (DESIGN.md §12): shared prefix on the
        default device, localized per-shard scans pinned to each
        shard's device by their committed buffers, host tree merge.
        The filtered variant threads each shard's local ``attrs`` part
        plus the per-query ``fvals`` rows through the same plan.

        Fault tolerance (DESIGN.md §15): every shard scan is timed into
        :class:`ShardHealth`; failures retry against a host-side replica
        of the shard's clusters with doubling-capped backoff; a shard
        flagged slow by the :class:`StragglerMonitor` is hedged — its
        scans pre-emptively run on the replica (with periodic device
        probes to detect recovery); a DOWN shard is skipped and the
        surviving partials merge into a degraded result whose coverage
        fraction (routed clusters scanned / routed) lands in
        ``last_coverage``. Raises :class:`ShardUnavailable` only when
        NO shard can serve."""
        from repro.core import faults as faults_lib
        from repro.core import serving as serving_lib
        from repro.distributed import resilience as resilience_lib

        shards = snap.shards
        backend = self.backend if backend is None else backend
        prefix = self.prefix_fn(cr=cr)
        sfn = self.shard_topk_fn(k=k, backend=backend, batch=batch,
                                 precision=snap.meta.precision,
                                 filtered=filtered)
        # host (uncommitted) copies of everything the per-shard calls
        # consume: a committed default-device operand would clash with
        # buffers committed on shard s (jax refuses mixed commitments)
        w_hat = np.asarray(snap.w_hat)
        health = self._shard_state(shards.n_shards)
        monitor = self._shard_monitor
        shard_of = np.asarray(shards.shard_of)
        coverage = [0, 0]               # routed clusters scanned / routed
        down_seen = set()

        def run_scan(s, part, q_emb, loc, w, local_c, qf, *, on_device):
            # scan_error fires on BOTH device and host-replica attempts
            # (the shard's DATA is unscannable, not just its device);
            # scan_slow only models a slow device — the replica is fine
            if on_device:
                faults_lib.fire("shard.scan_slow", shard=s)
            faults_lib.fire("shard.scan_error", shard=s)
            if filtered:
                out = sfn(w_hat, part["emb"], part["loc"], part["ids"],
                          part["scale"], part["attrs"],
                          q_emb, loc, w, local_c, qf)
            else:
                out = sfn(w_hat, part["emb"], part["loc"], part["ids"],
                          part["scale"], q_emb, loc, w, local_c)
            # sync here so the wall time fed to ShardHealth measures
            # THIS shard's scan, not whatever dispatch queued behind it
            return np.asarray(out[0]), np.asarray(out[1])

        def scan_shard(s, part, q_emb, loc, w, local_c, qf):
            """One shard's partial ``(ids, scores)``, or ``None`` when
            the shard could not be scanned this chunk."""
            try:
                faults_lib.fire("shard.device_lost", shard=s)
            except Exception:
                health.mark_down(s)
                return None
            hedge = s in self._hedged
            probe = False
            if hedge:
                # hedged shard: serve from the replica, but probe the
                # device every Nth scan so a recovered device is noticed
                self._hedged[s] += 1
                probe = self._hedged[s] % self.hedge_probe_every == 0
            delay_ms = self.shard_backoff_ms
            for attempt in range(1 + self.shard_retries):
                if attempt > 0:
                    self.shard_stats["scan_retries"] += 1
                    if delay_ms > 0:
                        time.sleep(min(delay_ms,
                                       self.shard_backoff_max_ms) / 1e3)
                    delay_ms = min(delay_ms * 2, self.shard_backoff_max_ms)
                # retries go straight to the host replica: the device
                # already failed once this chunk
                on_host = (hedge and not probe) or attempt > 0
                try:
                    t0 = time.perf_counter()
                    if on_host:
                        out = run_scan(
                            s, self._host_shard_part(snap, shards, s),
                            q_emb, loc, w, local_c, qf, on_device=False)
                        self.shard_stats["host_scans"] += 1
                        if hedge and not probe:
                            self.shard_stats["hedged_scans"] += 1
                    else:
                        out = run_scan(s, part, q_emb, loc, w, local_c,
                                       qf, on_device=True)
                    dt = time.perf_counter() - t0
                    health.record_success(s, dt)
                    if not on_host:
                        # only device timings feed the straggler stream:
                        # a hedged replica scan must not mask the slow
                        # device we are hedging against
                        monitor.record(f"shard{s}", dt)
                        if monitor.slow(f"shard{s}"):
                            self._hedged.setdefault(s, 0)
                        elif hedge:
                            self._hedged.pop(s, None)    # probe came
                            # back fast — device recovered, stop hedging
                    return out
                except Exception:
                    health.record_failure(s)
                    if health.is_down(s):
                        return None
            return None                  # retries exhausted, not DOWN yet

        def chunk_fn(t, m, l, *rest):
            q_emb, w, top_c = prefix(snap.rel_params, snap.index_params,
                                     snap.norm, t, m, l)
            q_emb = np.asarray(q_emb)
            w = np.asarray(w)
            top_c = np.asarray(top_c)
            loc = np.asarray(l)
            qf = np.asarray(rest[0]) if filtered else None
            routes_per = np.bincount(shard_of[top_c].ravel(),
                                     minlength=shards.n_shards)
            coverage[1] += int(top_c.size)
            partials = []
            for s, part in enumerate(shards.parts):
                if health.is_down(s):
                    self.shard_stats["down_skips"] += 1
                    down_seen.add(s)
                    continue
                local_c = serving_lib.localize_routes(
                    top_c, shards.shard_of, shards.local_of, s,
                    sentinel=shards.sentinel)
                out = scan_shard(s, part, q_emb, loc, w, local_c, qf)
                if out is None:
                    if health.is_down(s):
                        down_seen.add(s)
                    continue
                coverage[0] += int(routes_per[s])
                partials.append(out)
            if not partials:
                raise resilience_lib.ShardUnavailable(
                    f"all {shards.n_shards} shards down/unscannable — "
                    f"no partial top-k lists to merge")
            return merge_shard_topk(partials, k=k)

        arrays = [q_tokens, q_mask, q_loc]
        if filtered:
            arrays.append(fvals)
        out = self._run_encoding(chunk_fn, arrays, batch=batch)
        self.last_coverage = (coverage[0] / coverage[1]
                              if coverage[1] else 1.0)
        self.last_down_shards = tuple(sorted(down_seen))
        return out

    def delta_scan_fn(self, *, k: int, precision: str,
                      filtered: bool = False):
        """The jitted delta scan plan for ``(k, precision, filtered)``.
        Retraces lazily per padded row-count bucket
        (:data:`DELTA_PAD_BUCKET`)."""
        key = (k, precision, filtered)
        if key not in self._delta_plans:
            self._delta_plans[key] = make_delta_scan_fn(
                self.cfg, k=k, dist_max=self.dist_max,
                weight_mode=self.weight_mode, precision=precision,
                filtered=filtered)
        return self._delta_plans[key]

    def _scan_delta(self, snap, q_tokens, q_mask, q_loc, *, k: int,
                    batch: int, fvals=None, filtered: bool = False):
        """Brute-force scan the pinned snapshot's delta rows: every
        query × every delta row, padded to the bucketed static shape."""
        from repro.core.filters import N_ATTRS

        arrs = snap.delta.arrays()
        m = arrs["ids"].shape[0]
        m_pad = -(-m // DELTA_PAD_BUCKET) * DELTA_PAD_BUCKET
        emb = np.zeros((m_pad,) + arrs["emb"].shape[1:], arrs["emb"].dtype)
        emb[:m] = arrs["emb"]
        scale = np.ones((m_pad,), np.float32)
        scale[:m] = arrs["scale"]
        loc = np.full((m_pad, 2), index_lib.PAD_LOC, np.float32)
        loc[:m] = arrs["loc"]
        ids = np.full((m_pad,), -1, np.int32)
        ids[:m] = arrs["ids"]
        fn = self.delta_scan_fn(k=k, precision=snap.meta.precision,
                                filtered=filtered)
        w_hat = snap.w_hat
        de, ds, dl, di = (jnp.asarray(a) for a in (emb, scale, loc, ids))
        if filtered:
            attrs = np.zeros((m_pad, N_ATTRS), np.int32)
            attrs[:m] = arrs["attrs"]
            da = jnp.asarray(attrs)
            return self._run_encoding(
                lambda t, mk, l, f: fn(snap.rel_params, w_hat, de, ds, dl,
                                       di, da, t, mk, l, f),
                [q_tokens, q_mask, q_loc, fvals], batch=batch)
        return self._run_encoding(
            lambda t, mk, l: fn(snap.rel_params, w_hat, de, ds, dl, di,
                                t, mk, l),
            [q_tokens, q_mask, q_loc], batch=batch)

    @functools.partial(jax.profiler.annotate_function, name="repro.query")
    def query(self, q_tokens, q_mask, q_loc, *, k: int = 20, cr: int = 1,
              batch: int = 256, backend: Optional[str] = None,
              snapshot=None, filters=None):
        """Batched routed query: (ids (n, k), scores (n, k)) numpy.

        Reads the snapshot reference exactly once (or serves an explicit
        ``snapshot`` — the server's flush path pins the one it started
        with), so every chunk of the batch scores one consistent index.
        The plan is selected for the pinned snapshot's precision tier;
        an auto engine additionally picks query- vs cluster-major per
        batch (:meth:`pick_backend`) unless ``backend`` overrides it.

        ``filters`` (core/filters.py, DESIGN.md §13) is ``None``, one
        :class:`~repro.core.filters.FilterSpec` broadcast over the whole
        request, or one spec (or None) per query row. Filters compile to
        per-query ``fvals`` rows riding the batch arrays; all-no-op
        filters collapse to the unfiltered plan, so pre-filter callers
        trace and run the byte-identical program. The predicate applies
        uniformly to base, sharded, and delta scans — a row never leaks
        across a filter anywhere in its lifecycle.

        When the pinned snapshot carries a delta segment (DESIGN.md
        §11), the base results are post-processed on the host: the delta
        rows are scanned (:meth:`_scan_delta`, same ``batch``), the base
        lists tombstone-filtered, and both merged by
        :func:`merge_delta`. A compacted (or delta-free) snapshot skips
        all of it — the fast path is byte-identical to before.

        When the pinned snapshot is mesh-sharded (``snap.shards``,
        DESIGN.md §12), the base scan runs per shard and tree-merges
        (:meth:`_query_sharded`) BEFORE the delta merge — the delta
        path is placement-agnostic and composes unchanged.
        """
        snap = self._snapshot if snapshot is None else snapshot
        # coverage annotation (DESIGN.md §15): 1.0 unless the sharded
        # path below loses a shard; read by Searcher/server after the call
        self.last_coverage = 1.0
        self.last_down_shards = ()
        fvals, filtered = filters_lib.compile_filters(
            filters, np.asarray(q_tokens).shape[0])
        # the per-batch cluster-major pick engages whenever the request
        # is "auto": explicitly (e.g. the serving drivers' resolved CLI
        # default, forwarded through ServerConfig.backend) or implicitly
        # (no override on an auto-constructed engine)
        if backend == "auto" or (backend is None and self._auto_cm):
            base = (resolve_backend("auto")[0] if backend == "auto"
                    else self.backend)
            backend = self.pick_backend(q_tokens, q_mask, q_loc, cr=cr,
                                        batch=batch, snapshot=snap,
                                        base=base)
        buf = snap.buffers
        delta = getattr(snap, "delta", None)
        use_delta = delta is not None and not delta.is_empty
        # every tombstone can kill one base entry, so over-fetch the
        # base list by the tombstone count (bucketed — bounded
        # recompiles; capped by the routed candidate pool) and trim back
        # to k after the merge: the post-filter top-k is then exactly
        # what a compacted snapshot would return
        k_fetch = k
        if use_delta and delta.n_tombstones:
            extra = (-(-delta.n_tombstones // TOMBSTONE_K_BUCKET)
                     * TOMBSTONE_K_BUCKET)
            pool = cr * int(buf["capacity"])
            k_fetch = max(k, min(k + extra, pool))
        if getattr(snap, "shards", None) is not None:
            # mesh-sharded snapshot (DESIGN.md §12): per-shard plans +
            # host tree merge, then the same delta merge below
            ids, scores = self._query_sharded(
                snap, q_tokens, q_mask, q_loc, k=k_fetch, cr=cr,
                batch=batch, backend=backend, fvals=fvals,
                filtered=filtered)
        else:
            fn = self.query_fn(k=k_fetch, cr=cr, backend=backend,
                               batch=batch, precision=snap.meta.precision,
                               filtered=filtered)
            w_hat = snap.w_hat          # once per call, not per chunk
            if filtered:
                ids, scores, tiles = self._run_encoding(
                    lambda t, m, l, f, nv: fn(
                        snap.rel_params, snap.index_params, w_hat,
                        snap.norm, buf["emb"], buf["loc"], buf["ids"],
                        buf["scale"], buf["attrs"], t, m, l, f, nv),
                    [q_tokens, q_mask, q_loc, fvals], batch=batch,
                    chunk_outputs=1, with_rows=True)
            else:
                ids, scores, tiles = self._run_encoding(
                    lambda t, m, l, nv: fn(snap.rel_params,
                                           snap.index_params, w_hat,
                                           snap.norm, buf["emb"],
                                           buf["loc"], buf["ids"],
                                           buf["scale"], t, m, l, nv),
                    [q_tokens, q_mask, q_loc], batch=batch,
                    chunk_outputs=1, with_rows=True)
            live, grid = tiles.sum(axis=0)
            self.stats["scan_tiles_live"] += int(live)
            self.stats["scan_tiles_grid"] += int(grid)
        if not use_delta:
            return ids, scores
        with jax.profiler.TraceAnnotation("repro.delta"):
            d_ids = d_scores = None
            if delta.n_rows:
                d_ids, d_scores = self._scan_delta(
                    snap, q_tokens, q_mask, q_loc, k=k, batch=batch,
                    fvals=fvals, filtered=filtered)
            return merge_delta(ids, scores, d_ids, d_scores,
                               tombstones=delta.tombstone_array(), k=k)
