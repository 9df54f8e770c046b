"""serving.dispatch_queries round-trip invariants (DESIGN.md §5).

The sort-based scatter must (a) place every non-dropped (query, route)
pair in its routed cluster's row, (b) be invertible through ``origin``,
and (c) count capacity overflow in ``n_dropped`` instead of silently
truncating.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import serving


def _dispatch(top_c, feat, c, cap):
    q_buf, origin, n_dropped = serving.dispatch_queries(
        jnp.asarray(top_c), jnp.asarray(feat), n_clusters=c, capacity=cap)
    return np.asarray(q_buf), np.asarray(origin), int(n_dropped)


def _unique_payload(b, cr):
    """Payload row j encodes the query id so origin inversion is checkable."""
    return np.arange(b, dtype=np.float32)[:, None] + 1000.0


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("b,cr,c,cap", [
    (16, 2, 4, 16),      # ample capacity
    (32, 1, 8, 8),       # tight
    (8, 4, 2, 32),       # few clusters, heavy multi-route
])
def test_roundtrip_invariants(b, cr, c, cap, seed):
    rng = np.random.default_rng(seed)
    top_c = rng.integers(0, c, size=(b, cr)).astype(np.int32)
    feat = _unique_payload(b, cr)
    q_buf, origin, n_dropped = _dispatch(top_c, feat, c, cap)

    n = b * cr
    placed = origin[origin < n]
    # (a) + drop accounting: every pair is either placed once or counted
    assert len(set(placed.tolist())) == len(placed)
    assert len(placed) + n_dropped == n
    # per-cluster demand vs what landed
    flat = top_c.reshape(-1)
    for ci in range(c):
        demand = int((flat == ci).sum())
        landed = int((origin[ci] < n).sum())
        assert landed == min(demand, cap)
    # (a) every placed pair sits in the cluster it was routed to,
    # (b) origin inverts the scatter: the payload row matches the query
    for ci in range(c):
        for s in range(cap):
            o = origin[ci, s]
            if o < n:
                assert flat[o] == ci
                assert q_buf[ci, s, 0] == feat[o // cr, 0]
    # pad slots carry the zero payload
    pad_rows = q_buf[origin >= n]
    assert (pad_rows == 0).all()


def test_overflow_is_counted_not_silent():
    """All queries route to one cluster; capacity only fits half."""
    b, c, cap = 16, 4, 8
    top_c = np.zeros((b, 1), np.int32)
    q_buf, origin, n_dropped = _dispatch(top_c, _unique_payload(b, 1), c, cap)
    assert n_dropped == b - cap
    assert int((origin < b).sum()) == cap
    # the kept pairs are the first `cap` in stable sort order
    assert sorted(origin[0][origin[0] < b].tolist()) == list(range(cap))


def test_no_drops_when_capacity_suffices():
    b, cr, c, cap = 12, 2, 3, 24      # cap == b*cr: can never overflow
    rng = np.random.default_rng(3)
    top_c = rng.integers(0, c, size=(b, cr)).astype(np.int32)
    _, origin, n_dropped = _dispatch(top_c, _unique_payload(b, cr), c, cap)
    assert n_dropped == 0
    assert int((origin < b * cr).sum()) == b * cr


def test_dispatch_degenerate_all_distinct():
    """U = B·cr: every route its own cluster — one slot per row, no
    drops even at capacity 1."""
    b, cr, c = 4, 2, 8
    top_c = np.arange(8, dtype=np.int32).reshape(b, cr)
    _, origin, n_dropped = _dispatch(top_c, _unique_payload(b, cr), c, 1)
    assert n_dropped == 0
    assert ((origin < b * cr).sum(axis=1) == 1).all()


# ---------------------------------------------------------------------------
# cluster_major_plan: the DISTINCT-cluster roster (DESIGN.md §10)
# ---------------------------------------------------------------------------


def _plan(top_c, c, **kw):
    u, roster, n_distinct, n_dropped = serving.cluster_major_plan(
        jnp.asarray(top_c), n_clusters=c, **kw)
    return (np.asarray(u), np.asarray(roster), int(n_distinct),
            int(n_dropped))


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("b,cr,c", [(16, 2, 4), (8, 4, 2), (6, 1, 8)])
def test_cluster_major_plan_roundtrip_invariants(b, cr, c, seed):
    """(a) every (query, route) pair is placed exactly once or counted
    dropped, (b) each roster row holds exactly the pairs routed to its
    ``u`` cluster, (c) ``n_distinct`` is the realized U and u's live
    slots are the distinct clusters in ascending order."""
    rng = np.random.default_rng(seed)
    top_c = rng.integers(0, c, size=(b, cr)).astype(np.int32)
    u, roster, n_distinct, n_dropped = _plan(top_c, c)
    n = b * cr
    flat = top_c.reshape(-1)
    distinct = np.unique(flat)
    assert n_distinct == len(distinct)
    assert (u[:n_distinct] == distinct).all()      # ascending, deduped
    placed = roster[roster < n]
    assert len(set(placed.tolist())) == len(placed)
    assert len(placed) + n_dropped == n
    assert n_dropped == 0                          # default qcap = B·cr
    for slot in range(len(u)):
        entries = roster[slot][roster[slot] < n]
        if slot < n_distinct:
            # exactly the pairs routed to this distinct cluster
            assert sorted(entries.tolist()) == sorted(
                np.flatnonzero(flat == u[slot]).tolist())
        else:
            assert entries.size == 0               # padding slots empty


def test_cluster_major_plan_single_cluster_saturation():
    """All B·cr routes land on ONE cluster: U=1, roster row 0 saturated.
    One slot below B·cr, the LAST pair in stable sort order spills onto
    a second row of the same cluster; only a caller-forced ``u_max`` of
    one row drops it, and then it is counted."""
    b, cr, c = 8, 2, 4
    n = b * cr
    top_c = np.full((b, cr), 2, np.int32)
    u, roster, n_distinct, n_dropped = _plan(top_c, c)
    assert n_distinct == 1 and n_dropped == 0 and u[0] == 2
    assert sorted(roster[0].tolist()) == list(range(n))    # saturated
    assert (roster[1:] == n).all()
    # exact saturation boundary: qcap = n-1 spills exactly one pair
    u, roster, n_distinct, n_dropped = _plan(top_c, c, qcap=n - 1)
    assert n_distinct == 1 and n_dropped == 0
    assert sorted(roster[0].tolist()) == list(range(n - 1))
    assert u[1] == 2 and roster[1].tolist() == [n - 1] + [n] * (n - 2)
    u, roster, n_distinct, n_dropped = _plan(top_c, c, qcap=n - 1,
                                             u_max=1)
    assert n_distinct == 1 and n_dropped == 1
    assert sorted(roster[0].tolist()) == list(range(n - 1))


def test_cluster_major_plan_all_distinct():
    """U = B·cr (every route a different cluster): one entry per roster
    row, u enumerates them all, qcap=1 suffices with zero drops."""
    b, cr, c = 4, 2, 8
    top_c = np.arange(8, dtype=np.int32).reshape(b, cr)
    u, roster, n_distinct, n_dropped = _plan(top_c, c, qcap=1)
    assert n_distinct == b * cr and n_dropped == 0
    assert (u == np.arange(8)).all()
    assert ((roster < b * cr).sum(axis=1) == 1).all()


def test_cluster_major_plan_u_max_truncation_counted():
    """A caller-forced u_max below the realized U drops whole clusters —
    counted, never silent."""
    b, cr, c = 4, 1, 8
    top_c = np.array([[0], [2], [5], [7]], np.int32)
    u, roster, n_distinct, n_dropped = _plan(top_c, c, u_max=2)
    assert n_distinct == 4            # realized U is still reported
    assert n_dropped == 2             # clusters 5 and 7 fell off the plan
    assert (u == np.array([0, 2])).all()


def test_cluster_dispatch_query_surfaces_drops(rng):
    """End-to-end: return_dropped=True reports the overflow count and the
    dropped queries degrade to empty lists rather than wrong results."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.core import index as il
    from repro.core import relevance

    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=1, d_model=16, n_heads=2, d_ff=32, vocab_size=256,
        max_len=8, spatial_t=20, n_clusters=2, index_mlp_hidden=(8,))
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c, cap, b, k = 64, 2, 32, 8, 4
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, c,
                            hidden=(8,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=1))[:, None]
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap)
    tok = jnp.asarray(rng.integers(2, 256, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)

    # qcap=1: at most one query per cluster survives dispatch — through
    # the snapshot-based entry point (the raw-kernel form is what
    # launch/steps.py shards; both share this body)
    from repro.core.snapshot import IndexSnapshot
    snap = IndexSnapshot.from_parts(cfg, params, iparams, norm, buf,
                                    dist_max=1.414)
    ids, sc, nd = serving.cluster_dispatch_query(
        snap, tok, msk, ql, k=k, cr=1, capacity=1,
        return_dropped=True)
    assert int(nd) == b - len(np.unique(
        np.asarray(il.route_queries(
            iparams, il.build_features(
                relevance.encode_queries(params, tok, msk, cfg), ql, norm),
            cr=1)[0])))
    dropped_rows = np.asarray(ids[(np.asarray(sc) == -np.inf).all(1)])
    assert (dropped_rows == -1).all()


def test_dispatch_quantized_snapshot_and_int8_guard(rng):
    """The dispatch path serves quantized snapshots through the shared
    score_candidates dequant, and the raw-kernel form refuses int8
    buffers passed WITHOUT their precision/scales (which would rank rows
    on raw code magnitude)."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.core import index as il
    from repro.core import relevance
    from repro.core.snapshot import IndexSnapshot

    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=16, n_heads=2, d_ff=32, vocab_size=256,
        max_len=8, spatial_t=20, n_clusters=2, index_mlp_hidden=(8,))
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c, cap, b, k = 64, 2, 32, 8, 4
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, c,
                            hidden=(8,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=1))[:, None]
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap)
    tok = jnp.asarray(rng.integers(2, 256, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    snap = IndexSnapshot.from_parts(cfg, params, iparams, norm, buf,
                                    dist_max=1.414)

    ids_f, sc_f = serving.cluster_dispatch_query(snap, tok, msk, ql, k=k)
    ids_q, sc_q = serving.cluster_dispatch_query(
        snap.with_precision("int8"), tok, msk, ql, k=k)
    # same candidate sets; scores within scalar-quantization error
    np.testing.assert_allclose(np.asarray(sc_q), np.asarray(sc_f),
                               rtol=0.05, atol=0.05)

    qbuf = snap.with_precision("int8").buffers
    with pytest.raises(ValueError, match="int8"):
        serving.dispatch_query_kernel(
            params, iparams, snap.w_hat, norm, qbuf["emb"], qbuf["loc"],
            qbuf["ids"], tok, msk, ql, cfg, k=k, dist_max=1.414)
