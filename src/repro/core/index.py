"""LIST-I: the learned cluster-classifier index (paper §4.3).

A single MLP shared between queries and objects maps
x = [L2norm(emb), lat̂, lon̂] (Eq. 9–10) to a softmax over c clusters
(Eq. 11). Training uses the MCL pairwise loss (Eq. 14) on ground-truth
positives + pseudo-negatives mined by the relevance model (Eq. 13,
core/pseudo_labels.py).

TPU-native indexing phase (DESIGN.md §3): instead of pointer-based inverted
lists, objects are packed into fixed-capacity padded **cluster buffers**
(emb (c, cap, d), loc (c, cap, 2), ids (c, cap)) so the query phase is a
static-shape gather + fused score. Overflowing objects spill to their
next-best cluster (at most `spill` hops) — balance is learned (that is the
point of the pseudo-label design), spill is the safety net.

Precision policy (DESIGN.md §9): the query phase is memory-bound on
streaming ``emb (c, cap, d)``, so the resident embeddings can be stored
quantized — ``precision ∈ PRECISIONS``:

* ``"f32"``  — exact float32 (the default and the parity oracle);
* ``"bf16"`` — bfloat16 cast, 2× less HBM traffic, no scale needed;
* ``"int8"`` — symmetric per-row scalar quantization, 4× less traffic:
  ``q = clip(round(emb / scale), -127, 127)`` with
  ``scale = max|emb_row| / 127`` kept in ``buffers["scale"] (c, cap)``
  float32. Dequantization happens in VMEM inside the fused kernels
  (kernels/fused_topk_score.py) so only compressed bytes cross HBM.

``loc``/``ids`` always stay exact: spatial relevance and the padding
mask are bit-identical across precision tiers — only TRel quantizes.
"""
from __future__ import annotations

import heapq
import math
from typing import Optional, Tuple

import ml_dtypes
import numpy as np
import jax
import jax.numpy as jnp

from repro.models import layers

PRECISIONS = ("f32", "bf16", "int8")

# Padding sentinel for ``loc`` rows: far enough outside any normalized
# corpus extent that a padded slot can never look spatially relevant.
# Both the build path and the mutation path MUST use the same value, or
# a mutated index diverges bit-wise from a rebuilt one.
PAD_LOC = 1e6

# buffer rows gathered per packing step of build_cluster_buffers
_PACK_ROWS = 1 << 18


# ---------------------------------------------------------------------------
# Feature construction (Eq. 9–10)
# ---------------------------------------------------------------------------


def loc_normalizer(locs):
    """Fit min/max normalization bounds from the object corpus. locs: (N,2)."""
    lo = locs.min(axis=0)
    hi = locs.max(axis=0)
    return {"lo": lo, "span": jnp.maximum(hi - lo, 1e-9)}


def build_features(emb, loc, norm):
    """x = [L2norm(emb), lat̂, lon̂]: (..., d+2)."""
    e = emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)
    l_hat = (loc - norm["lo"]) / norm["span"]
    return jnp.concatenate([e, l_hat], axis=-1)


# ---------------------------------------------------------------------------
# Cluster classifier (Eq. 11)
# ---------------------------------------------------------------------------


def index_init(key, d_emb: int, n_clusters: int, hidden=(512, 512)):
    dims = (d_emb + 2,) + tuple(hidden) + (n_clusters,)
    return {"mlp": layers.mlp_init(key, dims)}


def cluster_logits(params, x):
    return layers.mlp_apply(params["mlp"], x, act=jax.nn.relu)


def cluster_probs(params, x):
    return jax.nn.softmax(cluster_logits(params, x).astype(jnp.float32), -1)


# ---------------------------------------------------------------------------
# MCL training loss (Eq. 14)
# ---------------------------------------------------------------------------


def mcl_loss(params, batch, *, balance_weight: float = 0.5):
    """Meta-classification likelihood over pairwise pseudo-labels.

    batch:
      q_feat   (B, d+2)
      pos_feat (B, d+2)     one positive per query
      neg_feat (B, m, d+2)  m pseudo-negatives per query
    ŝ(q,o) = Prob_q · Prob_o; maximize log ŝ(pos) + Σ log(1 − ŝ(neg)).

    ``balance_weight`` adds KL(mean-assignment ‖ uniform) — a beyond-paper
    stabilizer (DESIGN.md §6): the paper relies on pseudo-negative hardness
    alone for balance, which we found collapse-prone at small scale (all
    probability mass drifting to a few clusters early in training kills the
    pairwise gradient). The regularizer only bites while the MEAN assignment
    is skewed; at the paper's balanced optimum it vanishes.
    """
    pq = cluster_probs(params, batch["q_feat"])          # (B, c)
    pp = cluster_probs(params, batch["pos_feat"])        # (B, c)
    pn = cluster_probs(params, batch["neg_feat"])        # (B, m, c)
    s_pos = jnp.sum(pq * pp, axis=-1)
    s_neg = jnp.einsum("bc,bmc->bm", pq, pn)
    eps = 1e-6
    loss = -(jnp.log(s_pos + eps).mean()
             + jnp.log(1.0 - s_neg + eps).sum(-1).mean())
    if balance_weight:
        c = pq.shape[-1]
        mean_p = jnp.concatenate(
            [pq, pp, pn.reshape(-1, c)], axis=0).mean(0)
        kl_unif = jnp.log(c) + jnp.sum(mean_p * jnp.log(mean_p + eps))
        loss = loss + balance_weight * kl_unif
    return loss, {"loss": loss, "s_pos": s_pos.mean(), "s_neg": s_neg.mean()}


# ---------------------------------------------------------------------------
# Precision policy: scalar quantization of resident embeddings
# ---------------------------------------------------------------------------


def quantize_rows(emb, precision: str):
    """Quantize embedding rows ``(..., d)`` f32 → (stored, scale (...,) f32).

    Symmetric per-row scalar quantization: each row's scale is
    ``max|row| / 127`` (1.0 for all-zero rows, e.g. padding slots, so
    dequant is a no-op there). ``"f32"``/``"bf16"`` need no scale and
    return all-ones; the uniform return shape keeps the buffer schema
    identical across tiers.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    emb = np.asarray(emb, np.float32)
    scale = np.ones(emb.shape[:-1], np.float32)
    if precision == "f32":
        return emb, scale
    if precision == "bf16":
        return emb.astype(ml_dtypes.bfloat16), scale
    amax = np.abs(emb).max(axis=-1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(emb / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale


def dequantize_rows(emb, scale, precision: str) -> np.ndarray:
    """Host-side inverse of :func:`quantize_rows` (lossy for int8)."""
    emb = np.asarray(emb).astype(np.float32)
    if precision == "int8":
        emb = emb * np.asarray(scale, np.float32)[..., None]
    return emb


def quantize_buffers(buffers: dict, precision: str) -> dict:
    """Derive a quantized copy of f32 cluster buffers (loc/ids untouched).

    Requantization is only defined FROM the exact tier: quantizing an
    already-quantized buffer would silently compound error, so any other
    source precision raises. Returns a new dict; the input is unchanged.
    """
    src = buffers.get("precision", "f32")
    if src == precision:
        return dict(buffers)
    if src != "f32":
        raise ValueError(
            f"quantize_buffers: can only requantize from 'f32' buffers, "
            f"these are {src!r}; rebuild the index at f32 first")
    q, scale = quantize_rows(np.asarray(buffers["emb"], np.float32),
                             precision)
    out = dict(buffers)
    out["emb"] = jnp.asarray(q)
    out["scale"] = jnp.asarray(scale)
    out["precision"] = precision
    return out


# ---------------------------------------------------------------------------
# Indexing phase: partition objects into padded cluster buffers
# ---------------------------------------------------------------------------


def assign_clusters(params, feats, *, top=1):
    """argmax (or top-`top`) cluster per object. feats: (N, d+2)."""
    logits = cluster_logits(params, feats)
    if top == 1:
        return jnp.argmax(logits, axis=-1)
    return jax.lax.top_k(logits, top)[1]


def default_capacity(n_objects: int, n_clusters: int) -> int:
    """Slots per cluster buffer: twice the mean cluster size, rounded up
    to a multiple of 128 (the scan kernels' lane-aligned tile)."""
    capacity = int(math.ceil(n_objects / n_clusters * 2.0))
    return -(-capacity // 128) * 128


def build_cluster_buffers(assign_top, emb, loc, *, n_clusters: int,
                          capacity: Optional[int] = None, spill: int = 3,
                          precision: str = "f32", attrs=None):
    """Pack objects into (c, cap) padded buffers (host-side, numpy).

    assign_top: (N, spill) preferred clusters per object, best first.
    Returns dict with emb (c,cap,d) in ``precision``'s storage dtype,
    loc (c,cap,2), ids (c,cap) int32 (-1 = padding), counts (c,),
    scale (c,cap) f32 per-row dequant scales (all ones unless int8),
    attrs (c,cap,3) int32 per-object filter attributes (core/filters.py;
    zeros when ``attrs`` is None), plus the host-side scalars
    capacity / n_spilled / precision.
    """
    from repro.core import filters as filters_lib
    assign_top = np.asarray(assign_top)
    emb = np.asarray(emb)
    loc = np.asarray(loc)
    attrs = filters_lib.validate_attrs(attrs, emb.shape[0])
    n, d = emb.shape
    c = n_clusters
    if capacity is None:
        capacity = default_capacity(n, c)
    counts = [0] * c
    members = [[] for _ in range(c)]
    # the least-loaded cluster, as np.argmin(counts) picks it (lowest
    # count, then lowest id): one (count, cluster) entry per cluster,
    # refreshed only when it reaches the top stale (counts only grow, so
    # a stale entry sorts too early, never too late)
    least = [(0, ci) for ci in range(c)]
    n_spilled = 0
    for i, hops in enumerate(assign_top[:, :spill].tolist()):
        for h, ci in enumerate(hops):
            if counts[ci] < capacity:
                break
        else:  # every preferred cluster is full: the least-loaded one
            while least[0][0] != counts[least[0][1]]:
                heapq.heapreplace(least, (counts[least[0][1]], least[0][1]))
            ci = least[0][1]
            if counts[ci] >= capacity:
                raise ValueError("cluster capacity exhausted; raise capacity")
            h = -1
        members[ci].append(i)
        counts[ci] += 1
        n_spilled += h != 0
    ids = np.full((c, capacity), -1, np.int32)
    for ci, m in enumerate(members):
        ids[ci, :len(m)] = m
    counts = np.asarray(counts, np.int64)
    gather = np.where(ids >= 0, ids, 0)
    buf_loc = loc[gather]
    buf_attrs = attrs[gather]
    valid = ids >= 0
    buf_loc[~valid] = PAD_LOC
    buf_attrs[~valid] = 0
    # embeddings are gathered and quantized a few clusters at a time, so
    # only the stored tier's (c, cap, d) array is ever whole on the host
    step = max(1, _PACK_ROWS // capacity)
    buf_emb, buf_scale = None, np.ones((c, capacity), np.float32)
    for c0 in range(0, c, step):
        blk = emb[gather[c0:c0 + step]].astype(np.float32)
        # zero out padding so fused scores on pads are harmless (masked)
        blk[~valid[c0:c0 + step]] = 0.0
        blk, buf_scale[c0:c0 + step] = quantize_rows(blk, precision)
        if buf_emb is None:
            buf_emb = np.empty((c, capacity, d), blk.dtype)
        buf_emb[c0:c0 + step] = blk
    return {
        "emb": jnp.asarray(buf_emb), "loc": jnp.asarray(buf_loc),
        "ids": jnp.asarray(ids), "counts": jnp.asarray(counts),
        "scale": jnp.asarray(buf_scale), "attrs": jnp.asarray(buf_attrs),
        "n_spilled": n_spilled, "capacity": capacity, "precision": precision,
    }


def route_queries(params, q_feats, *, cr: int = 1):
    """Top-cr clusters per query: (B, cr) ids + probs."""
    logits = cluster_logits(params, q_feats)
    p = jax.nn.softmax(logits.astype(jnp.float32), -1)
    top_p, top_i = jax.lax.top_k(p, cr)
    return top_i, top_p


# ---------------------------------------------------------------------------
# Insertion / deletion (paper §4.3 "Insertion and Deletion Policy")
# ---------------------------------------------------------------------------


def insert_objects(buffers, params, norm, new_emb, new_loc, new_ids, *,
                   spill: int = 3, new_attrs=None):
    """Route new objects through the trained index into their buffers.

    Placement mirrors :func:`build_cluster_buffers` (paper §4.3): each
    object walks its top-``spill`` preferred clusters best-first and
    lands in the first with a free slot; only when ALL spill hops are
    full does it fall back to the least-loaded cluster. If even that
    cluster has no free slot (the whole index is at capacity) a
    ValueError is raised. Writes go to the first FREE slot
    (``id == -1``) rather than ``counts[ci]`` — after delete_objects a
    cluster has interior holes, and slot ``counts[ci]`` may hold a live
    object (regression: tests/test_index_mutation.py).

    ``new_emb`` is always float32; quantized buffers (DESIGN.md §9)
    quantize the new rows with their own per-row scales on the way in,
    so an insert never changes the buffer's storage dtype.
    """
    from repro.core import filters as filters_lib
    feats = build_features(new_emb, new_loc, norm)
    n_clusters = int(np.asarray(buffers["counts"]).shape[0])
    hops = max(1, min(int(spill), n_clusters))
    cl = np.asarray(assign_clusters(params, feats, top=hops))
    if cl.ndim == 1:
        cl = cl[:, None]
    new_attrs = filters_lib.validate_attrs(new_attrs,
                                           np.asarray(new_ids).shape[0])
    emb_np = {k: np.asarray(v).copy() for k, v in buffers.items()
              if k in ("emb", "loc", "ids", "scale", "attrs")}
    counts = np.asarray(buffers["counts"]).copy()
    cap = buffers["capacity"]
    q_emb, q_scale = quantize_rows(np.asarray(new_emb, np.float32),
                                   buffers.get("precision", "f32"))
    for j in range(cl.shape[0]):
        ci = -1
        for h in range(cl.shape[1]):          # spill hops, best first
            if counts[int(cl[j, h])] < cap:
                ci = int(cl[j, h])
                break
        if ci < 0:
            ci = int(np.argmin(counts))       # least-loaded fallback
        if counts[ci] >= cap:                 # fallback full too: all full
            raise ValueError(
                f"insert_objects: all clusters at capacity {cap} "
                f"(inserted {j}/{cl.shape[0]}); rebuild with higher capacity")
        free = np.flatnonzero(emb_np["ids"][ci] < 0)
        if free.size == 0:                    # counts out of sync with ids
            raise ValueError(
                f"insert_objects: cluster {ci} reports {counts[ci]} < "
                f"cap={cap} but has no free slot; counts/ids inconsistent")
        slot = int(free[0])
        emb_np["emb"][ci, slot] = q_emb[j]
        emb_np["scale"][ci, slot] = q_scale[j]
        emb_np["loc"][ci, slot] = np.asarray(new_loc[j])
        emb_np["ids"][ci, slot] = int(new_ids[j])
        emb_np["attrs"][ci, slot] = new_attrs[j]
        counts[ci] += 1
    out = dict(buffers)
    out.update({k: jnp.asarray(v) for k, v in emb_np.items()})
    out["counts"] = jnp.asarray(counts)
    return out


def delete_objects(buffers, del_ids):
    """Mark deleted ids as padding (lazy deletion, compaction on rebuild).

    A deleted slot is restored to EXACTLY the padding convention of
    :func:`build_cluster_buffers` — emb 0, scale 1, loc ``PAD_LOC``,
    id -1 — so a mutated index stays bit-identical to a rebuilt one.
    (Regression: ``loc`` used to keep the deleted object's live value.)
    """
    ids = np.asarray(buffers["ids"]).copy()
    emb = np.asarray(buffers["emb"]).copy()
    loc = np.asarray(buffers["loc"]).copy()
    scale = np.asarray(buffers["scale"]).copy()
    attrs = np.asarray(buffers["attrs"]).copy()
    mask = np.isin(ids, np.asarray(del_ids))
    ids[mask] = -1
    emb[mask] = 0.0
    loc[mask] = PAD_LOC
    scale[mask] = 1.0          # padding rows dequantize as exact zeros
    attrs[mask] = 0
    out = dict(buffers)
    out["ids"] = jnp.asarray(ids)
    out["emb"] = jnp.asarray(emb)
    out["loc"] = jnp.asarray(loc)
    out["scale"] = jnp.asarray(scale)
    out["attrs"] = jnp.asarray(attrs)
    out["counts"] = jnp.asarray((ids >= 0).sum(-1))
    return out
