"""Fused spatio-textual score + running top-k — LIST's query-phase hot loop.

This is the op the paper's entire index exists to accelerate (Algorithm 1
line 17): for each routed query, score every object in its cluster buffer
with ST(q,o) = w_t·(q·o) + w_s·ŵ_s[⌊S_in·t⌋] and keep the top-k.

TPU-native design (DESIGN.md §3/§4): the resident ``(c, cap, d)``
buffers stream through VMEM in ``(block_n, d)`` tiles; each tile costs
one MXU matmul for TRel plus the step-table lookup for SRel (Eq. 5), and
folds into a running top-k held in the revisited output block — the
spatial relevance and the candidate scores never round-trip to HBM.

Two kernels share one body (:func:`_scan_kernel`):

* :func:`fused_topk_score_routed` — query-major and gather-free. The
  routed cluster ids are **scalar-prefetched**
  (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps
  block-index the resident buffers directly: grid step ``(b, r, j)`` DMAs
  tile ``j`` of cluster ``top_c[b, r]`` — no candidate copy exists, and
  the ``cr`` routed lists merge into one running top-k in VMEM. Output
  ids are global object ids (read from ``buf_ids`` in-kernel).
* :func:`fused_topk_score_cluster_major` — the batched-IVF inversion
  (DESIGN.md §10). Grid ``(rows, cap/bn)`` scalar-prefetches the plan's
  per-row cluster ids (``serving.cluster_major_plan``), DMAs each row's
  cluster tiles once per batch, and scores them against the row's whole
  query roster in one ``(Qcap, d) × (d, bn)`` matmul. The caller folds
  the per-slot partial lists with ``engine.merge_cluster_major``.

What Mosaic (the TPU kernel compiler) accepts shapes the body:

* every block's last two dims are multiples of (8, 128) or equal the
  array's, so per-query and per-candidate vectors ride a leading
  singleton axis (queries ``(B, 1, d)``, ids/scales ``(c, 1, cap)``) and
  locations/attributes are lane-major (``(c, 2, cap)`` / ``(c, 3,
  cap)``) — a minor dim of 2 or 3 would pad every row to 128 lanes;
* the tile ``block_n`` divides ``cap`` and is a multiple of 128 (or all
  of ``cap``);
* there is no vector gather from a long table and no in-kernel
  ``lax.top_k``: the step lookup is a lane gather from 128-entry chunks
  of ŵ (:func:`_step_lookup`), and the merge is ``k`` rounds of max,
  first index of the max, and mask (:func:`_merge_topk`), which keeps
  ``lax.top_k``'s order: ties go to the running list, then to the lower
  tile position.

Precision policy (DESIGN.md §9): int8 tiles are upcast in VMEM and their
per-row scales applied to the matmul's output columns; bf16 tiles need
no scale. The matmul runs at ``Precision.HIGHEST`` so the f32 upcast is
exact on the chip too. Locations, ids, and the padding mask stay exact,
so SRel and the pad semantics (id -1, ``NEG_INF``) are identical across
precision tiers.

Filtered search (DESIGN.md §13): with a ``(c, cap, 3)`` int32 attribute
buffer and per-query compiled filter rows, each tile's attribute strip
streams beside the embeddings and failing rows take the padding
semantics in VMEM. The unfiltered call streams no attribute bytes.

Live extent (DESIGN.md §3): rows past a cluster's last live slot are
never read. A cluster's extent is one past its last slot with an id
``>= 0`` (:func:`live_extent`; holes left by deletes lie inside it, so
``counts`` may be smaller), and both kernels scalar-prefetch, beside the
cluster ids, the live tile count ``ceil(extent / block_n)`` of every
grid row (:func:`routed_tiles`, :func:`cluster_major_tiles`). Past it
the index maps repeat the last live tile, so no DMA is issued, and the
body does not run: those rows are padding (id -1, ``NEG_INF``) and, as
ties go to the running list, could never enter the top-k. A grid row
that serves no query (a batch's zero-padding rows, a plan row whose
roster holds only those or nothing) streams no tile and returns the
padding pair.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_LANES = 128          # TPU vector lane width: tiles and table chunks
_SUBLANES = 8


def _largest_divisor_tile(size: int, requested: int) -> int:
    """The largest tile ≤ ``requested`` that divides ``size`` exactly."""
    tile = min(requested, size)
    if size % tile:
        tile = next(t for t in range(tile, 0, -1) if size % t == 0)
    return tile


def _scan_tile(cap: int, block_n: int, *, who: str) -> int:
    """The streaming tile over a capacity-``cap`` cluster: all of ``cap``
    when it fits ``block_n``, else the largest multiple of 128 that
    divides ``cap`` (a lane-aligned block the chip accepts). A capacity
    with no such divisor falls back to its largest divisor ≤ ``block_n``
    (interpret mode only) and warns if the tiles collapse."""
    if cap <= block_n:
        return cap
    aligned = [t for t in range(_LANES, block_n + 1, _LANES) if cap % t == 0]
    if aligned:
        return max(aligned)
    tile = _largest_divisor_tile(cap, block_n)
    if tile < max(1, block_n // 4):
        warnings.warn(
            f"{who}: capacity {cap} has no divisor near the requested tile "
            f"size ({block_n}); tiles collapsed to {tile} — pathological "
            f"grid. Prefer a capacity with a large power-of-two factor "
            f"(build_cluster_buffers rounds to multiples of 128)",
            stacklevel=3)
    return tile


def live_extent(buf_ids):
    """``(c,)`` int32: one past each cluster's last slot with an id
    ``>= 0``, 0 for an empty cluster."""
    slot = jnp.arange(1, buf_ids.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(buf_ids >= 0, slot, 0), axis=1)


def _grid_tiles(buf_ids, clusters, live, *, block_n: int):
    """``ceil(extent / tile)`` of the cluster each entry of ``clusters``
    names, 0 where ``live`` is False. → (int32 array shaped like
    ``clusters``, the grid's tiles per row ``cap // tile``)."""
    cap = buf_ids.shape[1]
    bn = _scan_tile(cap, block_n, who="scan tiles")
    n = -(-live_extent(buf_ids)[clusters] // bn)
    return jnp.where(live, n, 0).astype(jnp.int32), cap // bn


def routed_tiles(buf_ids, top_c, *, block_n: int, n_valid=None):
    """The tiles :func:`fused_topk_score_routed` streams for each routed
    pair ``(b, r)``: all the live ones of cluster ``top_c[b, r]``, none
    for a batch row ``b >= n_valid`` (padding; default: every row is a
    query). → ((B, cr) int32, the grid's tiles per pair)."""
    b = top_c.shape[0]
    live = (jnp.ones((b, 1), bool) if n_valid is None else
            (jnp.arange(b) < n_valid)[:, None])
    return _grid_tiles(buf_ids, top_c, live, block_n=block_n)


def cluster_major_tiles(buf_ids, u, roster, *, n_live, block_n: int):
    """The tiles :func:`fused_topk_score_cluster_major` streams for each
    plan row ``i``: all the live ones of cluster ``u[i]``, none for a row
    whose roster holds no entry below ``n_live`` (empty slots hold
    ``n_total``; entries in ``[n_live, n_total)`` are the batch's
    padding pairs). → ((rows,) int32, the grid's tiles per row)."""
    return _grid_tiles(buf_ids, u, jnp.any(roster < n_live, axis=1),
                       block_n=block_n)


def _step_table(w_hat):
    """ŵ ``(t,)`` → ``(ceil(t/128), 128)`` f32 chunks (edge-padded), the
    form :func:`_step_lookup` gathers from."""
    t = w_hat.shape[0]
    n = -(-t // _LANES)
    return jnp.pad(w_hat.astype(jnp.float32), (0, n * _LANES - t),
                   mode="edge").reshape(n, _LANES)


def _step_lookup(tab, idx):
    """``ŵ[idx]`` for int32 ``idx (m, n)`` in ``[0, t)``. Mosaic gathers
    only within one 128-lane vreg, so each 128-entry chunk of the table
    is gathered per 128-lane slice of ``idx`` and selected by the chunk
    number. A one-row ``idx`` is broadcast to a full 8-sublane tile."""
    rows, n = idx.shape
    if rows == 1:
        idx = jnp.broadcast_to(idx, (_SUBLANES, n))
    lo = idx % _LANES
    hi = idx // _LANES
    out = jnp.zeros(idx.shape, jnp.float32)
    for c in range(tab.shape[0]):
        chunk = jnp.broadcast_to(tab[c:c + 1, :], (idx.shape[0], _LANES))
        got = jnp.concatenate(
            [jnp.take_along_axis(chunk, lo[:, s:s + _LANES], axis=1)
             for s in range(0, n, _LANES)], axis=1)
        out = jnp.where(hi == c, got, out)
    return out[:rows]


def _predicate(attrs, fvals):
    """In-VMEM filter predicate (the kernel twin of
    ``filters.predicate_mask``): ``attrs`` int32 ``(3, n)`` lane-major
    candidate rows [tenant; category bitmask; timestamp]; ``fvals`` int32
    ``(m, 4)`` compiled per-query filters [tenant, mask, t_min, t_max]
    with sentinel no-ops (tenant<0, mask==0, int32 extremes). Returns
    bool ``(m, n)`` — True = candidate passes that query's filter."""
    tenant, cat, ts = attrs[0:1], attrs[1:2], attrs[2:3]        # (1, n)
    f_tenant, f_mask = fvals[:, 0:1], fvals[:, 1:2]             # (m, 1)
    t_lo, t_hi = fvals[:, 2:3], fvals[:, 3:4]
    ok_tenant = (f_tenant < 0) | (tenant == f_tenant)
    ok_cat = (f_mask == 0) | ((cat & f_mask) != 0)
    ok_time = (ts >= t_lo) & (ts <= t_hi)
    return ok_tenant & ok_cat & ok_time


def _merge_topk(run_s, run_i, st, ids, k: int):
    """Fold a scored tile ``st``/``ids (m, n)`` into the running top-k
    ``run_s``/``run_i (m, k)``: ``k`` rounds of (max, first index of the
    max, mask it). Equals ``lax.top_k`` over ``concat([run, tile])``:
    ties resolve to the lower concatenated position."""
    iota_k = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    big = jnp.int32(2 ** 30)
    low = jnp.int32(-2 ** 31)

    def one(r, carry):
        run_s, st, out_s, out_i = carry
        m_run = jnp.max(run_s, axis=1, keepdims=True)
        m_tile = jnp.max(st, axis=1, keepdims=True)
        from_run = m_run >= m_tile
        p_run = jnp.min(jnp.where(run_s == m_run, iota_k, big), axis=1,
                        keepdims=True)
        p_tile = jnp.min(jnp.where(st == m_tile, iota_n, big), axis=1,
                         keepdims=True)
        hit_run = from_run & (iota_k == p_run)
        hit_tile = jnp.logical_not(from_run) & (iota_n == p_tile)
        i_run = jnp.max(jnp.where(hit_run, run_i, low), axis=1,
                        keepdims=True)
        i_tile = jnp.max(jnp.where(hit_tile, ids, low), axis=1,
                         keepdims=True)
        here = iota_k == r
        out_s = jnp.where(here, jnp.maximum(m_run, m_tile), out_s)
        out_i = jnp.where(here, jnp.where(from_run, i_run, i_tile), out_i)
        run_s = jnp.where(hit_run, -jnp.inf, run_s)
        st = jnp.where(hit_tile, -jnp.inf, st)
        return run_s, st, out_s, out_i

    _, _, out_s, out_i = jax.lax.fori_loop(
        0, k, one, (run_s, st, jnp.zeros_like(run_s), jnp.zeros_like(run_i)))
    return out_s, out_i


def _scan_kernel(*refs, row_of, n_axes: int, dequant: bool,
                 filtered: bool, k: int, t: int, dist_max: float):
    """Score one ``(block_n, d)`` resident tile against ``m`` query rows
    (1 for the routed kernel, the roster's ``Qcap`` for cluster-major)
    and fold it into each row's running top-k.

    The grid's last axis walks the tiles; the axes before it name a grid
    row, ``row_of(*those)`` its entry in the scalar-prefetched cluster
    ids and live tile counts, the first two refs. The output block is
    the first axis's, initialised where every later axis is 0. Refs
    after those two: q ``(1, m, d)``, q_loc ``(1, m, 2)``, w_st
    ``(1, m, 2)``, step table ``(t/128, 128)``, emb ``(1, bn, d)``,
    [scale ``(1, 1, bn)``], loc ``(1, 2, bn)``, ids ``(1, 1, bn)``,
    [attrs ``(1, 3, bn)``, q_filt ``(1, m, 4)``], then the outputs
    scores / ids ``(1, m, k)``."""
    tiles_ref = refs[1]
    refs = list(refs[2:])
    qe_ref, ql_ref, qw_ref, tab_ref, emb_ref = refs[:5]
    del refs[:5]
    scale_ref = refs.pop(0) if dequant else None
    loc_ref, ids_ref = refs.pop(0), refs.pop(0)
    attrs_ref = qf_ref = None
    if filtered:
        attrs_ref, qf_ref = refs.pop(0), refs.pop(0)
    os_ref, oi_ref = refs
    axes = [pl.program_id(a) for a in range(n_axes)]

    @pl.when(functools.reduce(jnp.logical_and,
                              [a == 0 for a in axes[1:]]))
    def _init():
        os_ref[...] = jnp.full(os_ref.shape, NEG_INF, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    @pl.when(axes[-1] < tiles_ref[row_of(*axes[:-1])])
    def _scan():
        q = qe_ref[0].astype(jnp.float32)                     # (m, d)
        trel = jax.lax.dot_general(
            q, emb_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)              # (m, bn)
        if dequant:
            trel = trel * scale_ref[0]                        # per-row scale

        ql = ql_ref[0].astype(jnp.float32)                    # (m, 2)
        ol = loc_ref[0]                                       # (2, bn)
        dx = ql[:, 0:1] - ol[0:1, :]
        dy = ql[:, 1:2] - ol[1:2, :]
        dist = jnp.sqrt(dx * dx + dy * dy)                    # (m, bn)
        s_in = 1.0 - jnp.clip(dist / dist_max, 0.0, 1.0)
        idx = jnp.clip((s_in * t).astype(jnp.int32), 0, t - 1)
        srel = _step_lookup(tab_ref[...], idx)

        w = qw_ref[0].astype(jnp.float32)                     # (m, 2)
        st = w[:, 0:1] * trel + w[:, 1:2] * srel
        ids = ids_ref[0]                                      # (1, bn)
        valid = ids >= 0                                      # buffer pad
        if filtered:
            valid = valid & _predicate(attrs_ref[0], qf_ref[0])   # (m, bn)
        st = jnp.where(valid, st, NEG_INF)
        ids = jnp.where(valid, ids, -1)
        ids = jnp.broadcast_to(ids, st.shape)

        s, i = _merge_topk(os_ref[0], oi_ref[0], st, ids, k)
        os_ref[0] = s
        oi_ref[0] = i


def _buffer_operands(buf_emb, buf_loc, buf_ids, buf_scale, buf_attrs, *,
                     block_n: int, row_of, who: str):
    """BlockSpecs + operands for the resident buffers. Grid step
    ``(*row, j)`` reads tile ``j`` of the cluster that prefetched entry
    ``row_of(*row)`` names, clamped to that entry's last live tile: past
    it the block index repeats, so nothing more is copied in."""
    c, cap, d = buf_emb.shape
    bn = _scan_tile(cap, block_n, who=who)

    def cluster_tile(*a):
        *row, j, clusters, tiles = a
        r = row_of(*row)
        return clusters[r], jnp.minimum(j, jnp.maximum(tiles[r] - 1, 0))

    def lane(rows):
        def index(*a):
            cl, j = cluster_tile(*a)
            return cl, 0, j
        return pl.BlockSpec((1, rows, bn), index)

    def rows_first(*a):
        cl, j = cluster_tile(*a)
        return cl, j, 0

    specs = [pl.BlockSpec((1, bn, d), rows_first)]
    args = [buf_emb]
    # the lane-major copies of the side buffers, made on every call:
    # named so a profile shows what they cost
    with jax.named_scope("relayout"):
        if buf_scale is not None:
            specs.append(lane(1))
            args.append(buf_scale.astype(jnp.float32).reshape(c, 1, cap))
        specs += [lane(2), lane(1)]
        args += [jnp.swapaxes(buf_loc.astype(jnp.float32), 1, 2),
                 buf_ids.astype(jnp.int32).reshape(c, 1, cap)]
        if buf_attrs is not None:
            specs.append(lane(3))
            args.append(jnp.swapaxes(buf_attrs.astype(jnp.int32), 1, 2))
    return specs, args, bn


def fused_topk_score_routed(q_emb, q_loc, w_st, top_c, buf_emb, buf_loc,
                            buf_ids, w_hat, *, k: int, dist_max: float,
                            interpret: bool, block_n: int = 512,
                            buf_scale=None, buf_attrs=None, q_filt=None,
                            n_valid=None):
    """Gather-free fused score + top-k over routed cluster buffers.

    q_emb (B, d); q_loc (B, 2); w_st (B, 2); top_c (B, cr) int32 routed
    cluster ids (scalar-prefetched); buf_emb (c, cap, d) in f32, bf16,
    or int8; buf_loc (c, cap, 2); buf_ids (c, cap) int32 (-1 pad);
    w_hat (t,) f32; buf_scale (c, cap) f32 per-row dequant scales
    (required for int8 buffers, omitted otherwise).

    Filtered search: pass BOTH ``buf_attrs (c, cap, 3)`` int32 object
    attributes and ``q_filt (B, 4)`` int32 compiled filter rows
    (core/filters.py) to mask failing candidates to the padding
    semantics (NEG_INF score, id -1) in VMEM.

    Returns (scores (B, k) f32, ids (B, k) i32 **global object ids**,
    -1 where fewer than k valid candidates exist). Grid ``(B, cr,
    cap/block_n)``: step ``(b, r, j)`` streams tile ``j`` of resident
    cluster ``top_c[b, r]``, and the cr routed lists fold into one
    running top-k in VMEM; only the cluster's live tiles are read and
    scored (:func:`routed_tiles`). ``n_valid`` (an int32 scalar, may be
    traced) marks the rows from ``n_valid`` on as the batch's padding:
    they stream nothing and return padding pairs. ``interpret`` runs the Pallas interpreter (off the chip) instead of
    compiling with Mosaic.
    """
    b, d = q_emb.shape
    cr = top_c.shape[1]
    if (buf_attrs is None) != (q_filt is None):
        raise ValueError("fused_topk_score_routed: pass buf_attrs and "
                         "q_filt together or not at all")
    n_tiles, _ = routed_tiles(buf_ids, top_c, block_n=block_n,
                              n_valid=n_valid)

    def row_of(b_, r):
        return b_ * cr + r

    buf_specs, buf_args, bn = _buffer_operands(
        buf_emb, buf_loc, buf_ids, buf_scale, buf_attrs, block_n=block_n,
        row_of=row_of, who="fused_topk_score_routed")
    tab = _step_table(w_hat)

    def per_query(width):
        return pl.BlockSpec((1, 1, width), lambda b_, r, j, *_: (b_, 0, 0))

    in_specs = [per_query(d), per_query(2), per_query(2),
                pl.BlockSpec(tab.shape, lambda *_: (0, 0)), *buf_specs]
    args = [q_emb.reshape(b, 1, d), q_loc.reshape(b, 1, 2),
            w_st.reshape(b, 1, 2), tab, *buf_args]
    if q_filt is not None:
        in_specs.append(per_query(4))
        args.append(q_filt.astype(jnp.int32).reshape(b, 1, 4))
    kern = functools.partial(
        _scan_kernel, row_of=row_of, n_axes=3,
        dequant=buf_scale is not None, filtered=q_filt is not None, k=k,
        t=w_hat.shape[0], dist_max=float(dist_max))
    scores, ids = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, cr, buf_emb.shape[1] // bn),
            in_specs=in_specs, out_specs=[per_query(k), per_query(k)]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, k), jnp.int32)],
        interpret=interpret,
    )(top_c.reshape(-1).astype(jnp.int32), n_tiles.reshape(-1), *args)
    return scores.reshape(b, k), ids.reshape(b, k)


def fused_topk_score_cluster_major(q_emb_r, q_loc_r, w_st_r, u, roster,
                                   buf_emb, buf_loc, buf_ids, w_hat, *,
                                   k: int, dist_max: float, n_total: int,
                                   interpret: bool, block_n: int = 512,
                                   buf_scale=None, buf_attrs=None,
                                   q_filt_r=None, n_live=None):
    """Cluster-major fused score + top-k over the batch plan.

    Inputs are the plan of ``serving.cluster_major_plan`` plus the
    roster-gathered query payloads: q_emb_r (rows, Qcap, d) / q_loc_r
    (rows, Qcap, 2) / w_st_r (rows, Qcap, 2) the queries of each plan
    row's roster; u (rows,) int32 the cluster each row scans
    (scalar-prefetched); roster (rows, Qcap) int32 flattened (query,
    route) indices with ``n_total = B·cr`` marking empty slots;
    buf_emb (c, cap, d) in f32, bf16, or int8; buf_loc (c, cap, 2);
    buf_ids (c, cap) int32 (-1 pad); w_hat (t,) f32; buf_scale (c, cap)
    f32 per-row dequant scales (required for int8 buffers, omitted
    otherwise).

    Filtered search: pass BOTH ``buf_attrs (c, cap, 3)`` int32 object
    attributes and ``q_filt_r (rows, Qcap, 4)`` int32 roster-gathered
    compiled filter rows to mask failing candidates in VMEM.

    Returns partial per-roster-slot top-k lists (scores (rows, Qcap, k)
    f32, ids (rows, Qcap, k) i32 global object ids, (-1, NEG_INF) on
    empty roster slots and past-the-end). Fold them per query with
    ``engine.merge_cluster_major(roster)``.

    Grid ``(rows, cap/block_n)``: step ``(i, j)`` DMAs tile ``j`` of
    cluster ``u[i]`` and scores it against the row's whole roster in one
    ``(Qcap, d) × (d, block_n)`` MXU matmul — each distinct cluster's
    bytes cross HBM once per plan row instead of once per routed query.
    Only the cluster's live tiles are read and scored, none on a row
    that serves no query (:func:`cluster_major_tiles`): ``n_live`` (an
    int32 scalar, may be traced; default ``n_total``) marks the roster
    entries from it on as the batch's padding pairs. ``Qcap`` bounds the query block held in VMEM.
    """
    rows, qcap, d = q_emb_r.shape
    if (buf_attrs is None) != (q_filt_r is None):
        raise ValueError("fused_topk_score_cluster_major: pass buf_attrs "
                         "and q_filt_r together or not at all")
    n_tiles, _ = cluster_major_tiles(
        buf_ids, u, roster, block_n=block_n,
        n_live=n_total if n_live is None else n_live)
    buf_specs, buf_args, bn = _buffer_operands(
        buf_emb, buf_loc, buf_ids, buf_scale, buf_attrs, block_n=block_n,
        row_of=lambda i: i, who="fused_topk_score_cluster_major")
    tab = _step_table(w_hat)

    def per_row(width):
        return pl.BlockSpec((1, qcap, width), lambda i, j, *_: (i, 0, 0))

    in_specs = [per_row(d), per_row(2), per_row(2),
                pl.BlockSpec(tab.shape, lambda *_: (0, 0)), *buf_specs]
    args = [q_emb_r, q_loc_r, w_st_r, tab, *buf_args]
    if q_filt_r is not None:
        in_specs.append(per_row(4))
        args.append(q_filt_r.astype(jnp.int32))
    kern = functools.partial(
        _scan_kernel, row_of=lambda i: i, n_axes=2,
        dequant=buf_scale is not None, filtered=q_filt_r is not None, k=k,
        t=w_hat.shape[0], dist_max=float(dist_max))
    scores, ids = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, buf_emb.shape[1] // bn),
            in_specs=in_specs, out_specs=[per_row(k), per_row(k)]),
        out_shape=[jax.ShapeDtypeStruct((rows, qcap, k), jnp.float32),
                   jax.ShapeDtypeStruct((rows, qcap, k), jnp.int32)],
        interpret=interpret,
    )(u.astype(jnp.int32), n_tiles, *args)
    # empty roster slots scored whatever query row 0 is: null them to
    # the padding pair so every slot honours the contract
    live = (roster < n_total)[..., None]
    return (jnp.where(live, scores, NEG_INF),
            jnp.where(live, ids, -1))
