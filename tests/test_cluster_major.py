"""Cluster-major batched execution (DESIGN.md §10): parity + properties.

The cluster-major kernel (stream each DISTINCT routed cluster once per
batch against its whole query roster, merge the cr partial lists per
query) must be indistinguishable from the query-major pallas kernel and
the dense oracle across duplicate routings, saturated rosters, buffer
padding, and every precision tier — and the auto heuristic / plan-cache
bound around it must behave.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import engine
from repro.core import index as il
from repro.core import relevance
from repro.core import serving
from repro.kernels import ops

DIST_MAX = 1.414


# ---------------------------------------------------------------------------
# Kernel-level parity: pallas-cm == query-major pallas == dense oracle
# ---------------------------------------------------------------------------


def _mk_instance(rng, *, b, cr, c, cap, d, t=50, precision="f32",
                 valid_per_cluster=None, top_c=None):
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    emb = rng.normal(size=(c, cap, d)).astype(np.float32)
    bi = np.arange(c * cap, dtype=np.int32).reshape(c, cap)
    if valid_per_cluster is not None:
        bi[:, valid_per_cluster:] = -1
    emb[bi < 0] = 0.0
    bl = rng.uniform(size=(c, cap, 2)).astype(np.float32)
    bl[bi < 0] = 1e6
    be, bs = il.quantize_rows(emb, precision)
    if top_c is None:
        top_c = rng.integers(0, c, size=(b, cr)).astype(np.int32)
    wh = np.cumsum(rng.uniform(0, 0.05, size=t)).astype(np.float32)
    return (q, ql, w, jnp.asarray(top_c), jnp.asarray(be),
            jnp.asarray(bl), jnp.asarray(bi), jnp.asarray(wh),
            jnp.asarray(bs) if precision == "int8" else None)


def _run_cluster_major_kernel(args, *, k, block_n=512, qcap=None,
                              u_max=None):
    q, ql, w, top_c, be, bl, bi, wh, bs = args
    b, cr = top_c.shape
    c = be.shape[0]
    n = b * cr
    u, roster, _, _ = serving.cluster_major_plan(top_c, n_clusters=c,
                                                 qcap=qcap, u_max=u_max)
    qidx = serving.roster_query_rows(roster, cr=cr, n_total=n)
    ps, pi = ops.fused_topk_score_cluster_major(
        q[qidx], ql[qidx], w[qidx], u, roster, be, bl, bi, wh,
        k=k, dist_max=DIST_MAX, n_total=n, block_n=block_n, buf_scale=bs,
        interpret=True)
    return engine.merge_cluster_major(ps, pi, roster, b=b, cr=cr, k=k)


def _all_three(args, *, k, block_n=512):
    q, ql, w, top_c, be, bl, bi, wh, bs = args
    s_cm, i_cm = _run_cluster_major_kernel(args, k=k, block_n=block_n)
    s_qm, i_qm = ops.fused_topk_score_routed(
        q, ql, w, top_c, be, bl, bi, wh, k=k, dist_max=DIST_MAX,
        block_n=block_n, buf_scale=bs, interpret=True)
    s_d, i_d = engine.dense_cluster_major(
        q, ql, w, top_c, be, bl, bi, wh, k=k, dist_max=DIST_MAX,
        buf_scale=bs)
    return [(np.asarray(s), np.asarray(i))
            for s, i in ((s_cm, i_cm), (s_qm, i_qm), (s_d, i_d))]


def _assert_equivalent(results):
    (s0, i0), *rest = results
    order0 = np.sort(i0, axis=1)
    for s, i in rest:
        np.testing.assert_allclose(s, s0, rtol=1e-5, atol=1e-5)
        assert (np.sort(i, axis=1) == order0).all()


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("b,cr,c,cap,d,k,block_n", [
    (8, 2, 6, 64, 32, 5, 512),      # cap < block_n: single-tile clusters
    (16, 4, 4, 128, 16, 10, 32),    # multi-tile streaming per cluster
    (3, 2, 5, 96, 8, 7, 64),        # odd b
    (1, 1, 2, 32, 64, 32, 512),     # single query, k == cap
])
def test_cluster_major_matches_query_major_and_dense(b, cr, c, cap, d, k,
                                                     block_n, precision,
                                                     rng):
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d,
                        precision=precision)
    _assert_equivalent(_all_three(args, k=k, block_n=block_n))


def test_cluster_major_duplicate_routes(rng):
    """A query routed TWICE to the same cluster keeps query-major
    semantics: its duplicate roster slots both score that cluster, so
    duplicated ids survive the merge exactly as the query-major paths
    duplicate them."""
    b, c, cap, d, k = 5, 4, 32, 16, 8
    top_c = np.array([[1, 1], [0, 2], [3, 3], [2, 2], [1, 1]], np.int32)
    args = _mk_instance(rng, b=b, cr=2, c=c, cap=cap, d=d, top_c=top_c)
    results = _all_three(args, k=k)
    _assert_equivalent(results)
    # duplicates ARE present (top-2·k of a twice-scanned cluster)
    i_cm = results[0][1]
    assert any(len(set(row.tolist())) < k for row in i_cm)


def test_cluster_major_saturated_single_cluster(rng):
    """Degenerate skew: every route lands on ONE cluster (U=1, the
    roster fully saturated at qcap = B·cr) — the kernel streams that
    cluster once and still matches query-major, which streams it
    B·cr times."""
    b, cr, c, cap, d, k = 8, 2, 6, 64, 32, 5
    top_c = np.full((b, cr), 3, np.int32)
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d, top_c=top_c)
    u, roster, n_distinct, n_dropped = serving.cluster_major_plan(
        jnp.asarray(top_c), n_clusters=c)
    assert int(n_distinct) == 1 and int(n_dropped) == 0
    assert (np.asarray(roster)[0] < b * cr).all()      # row 0 saturated
    assert (np.asarray(roster)[1:] == b * cr).all()    # rest empty
    _assert_equivalent(_all_three(args, k=k))


def test_cluster_major_all_distinct(rng):
    """Degenerate anti-skew: every route hits a different cluster
    (U = B·cr, dedup factor 1) — one roster entry per row."""
    b, cr, c, cap, d, k = 4, 2, 8, 32, 16, 5
    top_c = np.arange(8, dtype=np.int32).reshape(b, cr)
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d, top_c=top_c)
    u, roster, n_distinct, n_dropped = serving.cluster_major_plan(
        jnp.asarray(top_c), n_clusters=c)
    assert int(n_distinct) == b * cr and int(n_dropped) == 0
    assert ((np.asarray(roster) < b * cr).sum(axis=1) == 1).all()
    _assert_equivalent(_all_three(args, k=k))


def test_cluster_major_partial_and_empty_clusters(rng):
    """-1 buffer padding: partially-filled clusters return only valid
    ids, and k > valid candidates pads with (-1, NEG_INF) like the
    query-major contract."""
    b, cr, c, cap, d, k = 6, 2, 4, 32, 16, 20
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d,
                        valid_per_cluster=3)
    results = _all_three(args, k=k)
    _assert_equivalent(results)
    s_cm, i_cm = results[0]
    assert ((i_cm >= 0).sum(axis=1) <= 3 * cr).all()
    assert ((s_cm < -1e29) == (i_cm < 0)).all()


def test_cluster_major_qcap_saturation_degrades_gracefully(rng):
    """qcap below the realized demand spills pairs onto a second row of
    the cluster, and the answers match the unbounded plan; with the rows
    capped at one (``u_max=1``) the spilled pairs drop — the count is
    surfaced and they contribute empty partial lists (never wrong
    results): queries keep whatever their surviving routes found."""
    b, cr, c, cap, d, k = 8, 1, 4, 32, 16, 4
    top_c = np.zeros((b, 1), np.int32)          # all 8 routes → cluster 0
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d, top_c=top_c)
    _, _, _, n_dropped = serving.cluster_major_plan(
        jnp.asarray(top_c), n_clusters=c, qcap=5)
    assert int(n_dropped) == 0
    _assert_equivalent([_run_cluster_major_kernel(args, k=k, qcap=5),
                        _run_cluster_major_kernel(args, k=k)])
    _, _, _, n_dropped = serving.cluster_major_plan(
        jnp.asarray(top_c), n_clusters=c, qcap=5, u_max=1)
    assert int(n_dropped) == 3
    s, i = _run_cluster_major_kernel(args, k=k, qcap=5, u_max=1)
    s, i = np.asarray(s), np.asarray(i)
    # stable sort keeps the FIRST 5 (query, route) pairs; the rest answer
    # with empty lists
    assert (i[:5] >= 0).all()
    assert (i[5:] == -1).all() and (s[5:] < -1e29).all()


# ---------------------------------------------------------------------------
# Hypothesis property test: random routings × precision tiers
# ---------------------------------------------------------------------------


def _check_property_instance(seed, b, cr, c, cap_tiles, valid, precision):
    """For ANY routing (duplicates, saturated single-cluster rosters)
    and any buffer padding, cluster-major == query-major pallas == the
    dense oracle on every precision tier: identical score multisets and
    identical id sets per query (tie order inside equal scores is
    free)."""
    rng = np.random.default_rng(seed)
    cap = 16 * cap_tiles
    k = int(rng.integers(1, cap + 1))
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=8,
                        precision=precision, valid_per_cluster=valid)
    _assert_equivalent(_all_three(args, k=k, block_n=16))


def test_cluster_major_property_parity():
    # hypothesis imported HERE so its absence skips only this test, not
    # the whole module (the rest of the file must always run)
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.given(
        seed=st.integers(0, 2**16),
        b=st.integers(1, 9),
        cr=st.integers(1, 3),
        c=st.integers(1, 6),
        cap_tiles=st.integers(1, 4),
        valid=st.sampled_from([None, 0, 3]),
        precision=st.sampled_from(["f32", "bf16", "int8"]),
    )
    @hypothesis.settings(max_examples=25, deadline=None)
    def check(seed, b, cr, c, cap_tiles, valid, precision):
        _check_property_instance(seed, b, cr, c, cap_tiles, valid, precision)

    check()


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_cluster_major_property_seed_sweep(seed, precision):
    """Hypothesis-free slice of the same property (always runs, even
    where hypothesis isn't installed): random shapes, routings, and
    padding per seed."""
    rng = np.random.default_rng(100 + seed)
    _check_property_instance(
        seed=int(rng.integers(0, 2**16)), b=int(rng.integers(1, 10)),
        cr=int(rng.integers(1, 4)), c=int(rng.integers(1, 7)),
        cap_tiles=int(rng.integers(1, 5)),
        valid=[None, 0, 3][int(rng.integers(0, 3))], precision=precision)


# ---------------------------------------------------------------------------
# Engine integration: plan-cache LRU bound + the auto heuristic
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_snapshot():
    from repro.core.snapshot import IndexSnapshot
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=32, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=4, index_mlp_hidden=(16,))
    rng = np.random.default_rng(3)
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c = 96, cfg.n_clusters
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, c,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=32)
    return IndexSnapshot.from_parts(cfg, params, iparams, norm, buf,
                                    dist_max=DIST_MAX)


def _queries(rng, b, L=8, vocab=512):
    tok = rng.integers(2, vocab, (b, L)).astype(np.int32)
    tok[:, 0] = 1
    return tok, np.ones((b, L), bool), rng.uniform(size=(b, 2)).astype(
        np.float32)


def test_plan_cache_lru_bound(tiny_snapshot):
    """The compiled-plan cache is LRU-bounded: distinct (batch, k, cr,
    backend, precision, filtered) keys beyond ``max_plans`` evict the
    least recently used plan, and a re-request retraces it."""
    e = engine.QueryEngine(tiny_snapshot, backend="dense", max_plans=2)
    f1 = e.query_fn(k=3, cr=1, batch=4)
    f2 = e.query_fn(k=4, cr=1, batch=4)
    assert e.query_fn(k=3, cr=1, batch=4) is f1      # hit refreshes
    e.query_fn(k=5, cr=1, batch=4)                   # evicts k=4 (LRU)
    assert len(e._plans) == 2
    assert (4, 4, 1, "dense", "f32", False) not in e._plans
    assert (4, 3, 1, "dense", "f32", False) in e._plans
    assert e.query_fn(k=4, cr=1, batch=4) is not f2  # retraced, not stale
    assert len(e._plans) == 2


def test_cluster_major_variant_heuristic():
    th = engine.CLUSTER_MAJOR_DEDUP_THRESHOLD
    assert engine.cluster_major_variant("pallas", th) == "pallas-cm"
    assert engine.cluster_major_variant("dense", th + 1) == "dense-cm"
    assert engine.cluster_major_variant("pallas", th - 0.5) == "pallas"
    # already-cluster-major names pass through
    assert engine.cluster_major_variant("pallas-cm", th) == "pallas-cm"


def test_cluster_major_feasibility_guard(rng):
    """Auto never picks a cluster-major plan whose roster overhead
    outgrows the stream it saves: u_max = min(B·cr, c) must stay within
    the buffer capacity — the large-c small-cap regime refuses the
    upgrade."""
    from repro.core.snapshot import IndexSnapshot
    assert engine.cluster_major_feasible(256, 2, 4, 32)        # u_max=4
    assert not engine.cluster_major_feasible(256, 2, 512, 128)  # u_max=512
    # end-to-end on an adversarial shape: c=16 clusters of capacity 8 —
    # a batch with B·cr > 8 would need u_max up to 16 > cap, so the
    # guard keeps query-major even though the dedup bound is maximal
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=1, d_model=16, n_heads=2, d_ff=32, vocab_size=512,
        max_len=8, spatial_t=20, n_clusters=16, index_mlp_hidden=(8,))
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c, cap = 64, 16, 8
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, c,
                            hidden=(8,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap, spill=16)
    snap = IndexSnapshot.from_parts(cfg, params, iparams, norm, buf,
                                    dist_max=DIST_MAX)
    tok = rng.integers(2, 512, (8, 8)).astype(np.int32)
    tok[:, 0] = 1
    msk = np.ones((8, 8), bool)
    loc = rng.uniform(size=(8, 2)).astype(np.float32)
    auto = engine.QueryEngine(snap, backend="auto")
    picked = auto.pick_backend(tok, msk, loc, cr=2, batch=8)   # u_max=16>8
    assert picked == auto.backend                  # refused the upgrade
    assert auto.last_dedup_factor is None


def test_auto_server_warmup_pretraces_both_twins(tiny_snapshot):
    """An auto server's warm-up must not let the degenerate all-padding
    batch (identical rows → maximal measured dedup) pick the plan: it
    pre-traces BOTH twins so whichever the live traffic selects is
    already compiled, and the artificial dedup factor never leaks into
    metrics()."""
    from repro.core import server as server_lib
    eng = engine.QueryEngine(tiny_snapshot, backend="auto")
    server = server_lib.StreamingServer(
        eng, server_lib.ServerConfig(batch_size=4, k=5, cr=2, backend=None))
    compiles = server.warmup()
    base = eng.backend
    twin = engine.cluster_major_variant(base, float("inf"))
    assert {f"{base}@4", f"{twin}@4"} <= set(compiles)
    backends_traced = {key[3] for key in eng._plans}
    assert {base, twin} <= backends_traced
    assert eng.last_dedup_factor is None
    assert server.metrics()["dedup_factor"] is None


def test_engine_auto_upgrades_to_cluster_major(tiny_snapshot, rng):
    """backend="auto" with a cluster-saturating batch (B·cr ≥ 2·c)
    upgrades to the cluster-major twin per batch; results match the
    explicit query-major backend modulo tie order."""
    tok, msk, loc = _queries(rng, 8)
    auto = engine.QueryEngine(tiny_snapshot, backend="auto")
    ids_a, sc_a = auto.query(tok, msk, loc, k=5, cr=2, batch=8)
    assert auto.last_dedup_factor >= engine.CLUSTER_MAJOR_DEDUP_THRESHOLD
    used = {key[3] for key in auto._plans}
    expect = "pallas-cm" if jax.default_backend() == "tpu" else "dense-cm"
    assert used == {expect}
    explicit = engine.QueryEngine(tiny_snapshot, backend="dense")
    ids_e, sc_e = explicit.query(tok, msk, loc, k=5, cr=2, batch=8)
    np.testing.assert_allclose(sc_a, sc_e, rtol=1e-5, atol=1e-5)
    assert (np.sort(ids_a) == np.sort(ids_e)).all()
    # an EXPLICIT backend never auto-upgrades
    assert {key[3] for key in explicit._plans} == {"dense"}
    # ... but an explicit "auto" REQUEST engages the pick even on a
    # non-auto engine (the serving drivers forward their resolved CLI
    # default "auto" through ServerConfig.backend)
    explicit.query(tok, msk, loc, k=5, cr=2, batch=8, backend="auto")
    assert expect in {key[3] for key in explicit._plans}
    assert explicit.last_dedup_factor is not None


def test_engine_auto_measures_when_structurally_inconclusive(tiny_snapshot,
                                                            rng):
    """When B·cr < threshold·c the pick must MEASURE: route the first
    chunk and use the realized distinct-cluster count."""
    tok, msk, loc = _queries(rng, 2)
    auto = engine.QueryEngine(tiny_snapshot, backend="auto")
    picked = auto.pick_backend(tok, msk, loc, cr=1, batch=2)
    # 2 routes over 4 clusters: structural bound 1.0 < threshold, so the
    # pick reflects the measured routing (dedup ∈ {1.0, 2.0})
    assert auto.last_dedup_factor in (1.0, 2.0)
    base = "pallas" if jax.default_backend() == "tpu" else "dense"
    expect = engine.cluster_major_variant(base, auto.last_dedup_factor)
    assert picked == expect


def test_server_flush_parity_on_cluster_major_backend(tiny_snapshot, rng):
    """A streaming server configured with backend="pallas-cm" serves
    micro-batches bit-identical to a direct engine call on the same
    backend (the padding rules compose with the cluster-major plan)."""
    from repro.core import server as server_lib
    tok, msk, loc = _queries(rng, 6)
    e = engine.QueryEngine(tiny_snapshot, backend="dense", interpret=True)
    server = server_lib.StreamingServer(
        e, server_lib.ServerConfig(batch_size=4, max_delay_ms=1.0, k=5,
                                   cr=2, backend="pallas-cm"))
    ids_s, sc_s = server.serve_all(tok, msk, loc)
    ids_d, sc_d = e.query(tok, msk, loc, k=5, cr=2, batch=4,
                          backend="pallas-cm")
    np.testing.assert_array_equal(ids_s, ids_d)
    np.testing.assert_array_equal(sc_s, sc_d)
