"""Filtered-search tier (core/filters.py, DESIGN.md §13).

Covers the tentpole acceptance criteria of attribute-filtered queries:

* the in-VMEM predicate (tenant equality ∧ category-bitmask intersection
  ∧ inclusive time window) returns filtered top-k ids identical to a
  PURE-NUMPY brute-force oracle over the routed clusters, across all 4
  backends × 3 precision tiers, unsharded and mesh-sharded, and over
  delta-resident rows;
* tenant isolation is absolute: a tenant-filtered query NEVER returns a
  foreign tenant's id, even when fewer than k candidates pass (failing
  rows take full padding semantics — id -1, score NEG_INF — so nothing
  can leak out of a NEG_INF slot); a hypothesis property test explores
  random attribute tables and filter mixes;
* all-no-op filters collapse to the unfiltered plan (same plan-cache
  entry, bit-identical results), and the server's cache keys carry the
  filter signature so two tenants never share a cached result.
"""
import asyncio
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.configs import get_config
from repro.core import engine as engine_lib
from repro.core import filters as filters_lib
from repro.core import index as il
from repro.core import relevance
from repro.core import server as server_lib
from repro.core.delta import DeltaSegment
from repro.core.filters import FilterSpec
from repro.core.snapshot import IndexSnapshot

DIST_MAX = 1.414
D = 32
BACKENDS = ["dense", "dense-cm", "pallas", "pallas-cm"]

N_DEV = jax.device_count()


# ---------------------------------------------------------------------------
# FilterSpec / compile unit contracts
# ---------------------------------------------------------------------------


def test_filterspec_noop_and_signature():
    assert filters_lib.NOOP_FILTER.is_noop
    assert FilterSpec().is_noop
    assert not FilterSpec(tenant=0).is_noop            # tenant 0 is real
    assert not FilterSpec(category_mask=1).is_noop
    assert not FilterSpec(t_min=5).is_noop
    # signature: all-no-op collapses to None; real specs are per-row tuples
    assert filters_lib.filter_signature(None) is None
    assert filters_lib.filter_signature(filters_lib.NOOP_FILTER) is None
    assert filters_lib.filter_signature([None, FilterSpec()]) is None
    sig = filters_lib.filter_signature(FilterSpec(tenant=2))
    assert sig is not None
    assert sig == filters_lib.filter_signature(FilterSpec(tenant=2))
    assert sig != filters_lib.filter_signature(FilterSpec(tenant=3))
    # per-row mixes keep row order in the signature
    a = filters_lib.filter_signature([FilterSpec(tenant=1), None])
    b = filters_lib.filter_signature([None, FilterSpec(tenant=1)])
    assert a != b


def test_compile_filters_shapes_and_sentinels():
    fv, filtered = filters_lib.compile_filters(None, 3)
    assert not filtered                     # static flag: unfiltered plan
    assert fv.shape == (3, filters_lib.N_FVALS)
    attrs_any = filters_lib.make_attrs([0, 5], [0, 7], [-9, 9])
    assert filters_lib.predicate_mask_np(attrs_any, fv[0][None]).all()
    fv, filtered = filters_lib.compile_filters(FilterSpec(tenant=1), 3)
    assert filtered and fv.shape == (3, filters_lib.N_FVALS)
    assert (fv == fv[0]).all()                         # broadcast spec
    # mixed rows: None rows become sentinel no-ops that pass everything
    fv, filtered = filters_lib.compile_filters(
        [FilterSpec(tenant=1), None], 2)
    assert filtered
    attrs = filters_lib.make_attrs([0, 1, 2], [0, 0, 0], [0, 0, 0])
    m = filters_lib.predicate_mask_np(attrs, fv[1][None])
    assert m.all()                                     # no-op row passes all
    m = filters_lib.predicate_mask_np(attrs, fv[0][None])
    assert m.tolist() == [False, True, False]
    with pytest.raises(ValueError):
        filters_lib.compile_filters([None], 2)         # row-count mismatch


def test_predicate_semantics():
    attrs = filters_lib.make_attrs(
        tenant=[0, 1, 1, 2],
        category_mask=[0b001, 0b010, 0b110, 0b000],
        timestamp=[10, 20, 30, 40])

    def passes(spec):
        return filters_lib.predicate_mask_np(
            attrs, spec.to_fvals()[None]).tolist()

    assert passes(FilterSpec()) == [True] * 4
    assert passes(FilterSpec(tenant=1)) == [False, True, True, False]
    # category: bitwise intersection; an object with mask 0 matches no
    # category-constrained query; a query mask of 0 means "any"
    assert passes(FilterSpec(category_mask=0b010)) == [
        False, True, True, False]
    assert passes(FilterSpec(category_mask=0b101)) == [
        True, False, True, False]
    # time window: inclusive on both bounds
    assert passes(FilterSpec(t_min=20, t_max=30)) == [
        False, True, True, False]
    assert passes(FilterSpec(t_min=41)) == [False] * 4
    # conjunction of all three legs
    assert passes(FilterSpec(tenant=1, category_mask=0b100,
                             t_min=25)) == [False, False, True, False]


def test_validate_attrs():
    z = filters_lib.validate_attrs(None, 5)
    assert z.shape == (5, 3) and z.dtype == np.int32 and not z.any()
    a = filters_lib.make_attrs([1, 2], [4, 8], [100, 200])
    assert np.array_equal(filters_lib.validate_attrs(a, 2), a)
    with pytest.raises(ValueError):
        filters_lib.validate_attrs(a, 3)               # row-count mismatch


# ---------------------------------------------------------------------------
# Fixture: a tiny snapshot carrying an attribute table
# ---------------------------------------------------------------------------

N_OBJ = 160


def _mk_attrs(n, seed=3):
    rng = np.random.default_rng(seed)
    return filters_lib.make_attrs(
        tenant=rng.integers(0, 3, n),
        category_mask=rng.integers(0, 16, n),          # 4 category bits
        timestamp=rng.integers(0, 1000, n))


@pytest.fixture(scope="module")
def fsnap():
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=D, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=4, index_mlp_hidden=(16,))
    rng = np.random.default_rng(17)
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c, cap = N_OBJ, cfg.n_clusters, 64
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(5), cfg.d_model, c,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    attrs = _mk_attrs(n)
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap, attrs=attrs)
    return IndexSnapshot.from_parts(cfg, params, iparams, norm, buf,
                                    dist_max=DIST_MAX)


_TIERS, _ENGINES = {}, {}


def snap_at(snap, precision):
    if precision not in _TIERS:
        _TIERS[precision] = (snap if precision == "f32"
                             else snap.with_precision(precision))
    return _TIERS[precision]


def engine_at(snap, precision, backend):
    key = (precision, backend)
    if key not in _ENGINES:
        _ENGINES[key] = engine_lib.QueryEngine.from_snapshot(
            snap_at(snap, precision), backend=backend,
            interpret=backend.startswith("pallas"))
    return _ENGINES[key]


def make_requests(rng, n, cfg):
    tok = rng.integers(2, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
    tok[:, 0] = 1
    msk = np.ones((n, cfg.max_len), bool)
    loc = rng.uniform(size=(n, 2)).astype(np.float32)
    return tok, msk, loc


# a representative mixed filter roster: no-op rows ride beside real specs
def _mixed_specs(b):
    roster = [None,
              FilterSpec(tenant=1),
              FilterSpec(category_mask=0b0101),
              FilterSpec(t_min=200, t_max=700),
              FilterSpec(tenant=0, category_mask=0b0011, t_min=100)]
    return [roster[i % len(roster)] for i in range(b)]


# ---------------------------------------------------------------------------
# The pure-numpy brute-force filtered oracle
# ---------------------------------------------------------------------------


def filtered_oracle(eng, snap, tok, msk, loc, specs, *, k, cr, batch):
    """Route with the engine's own (deterministic) prefix, then score the
    routed clusters' candidates entirely in numpy: dequant, Eq. 5 serve
    form, predicate, top-k. Independent of every jit'd scan path.

    The prefix runs in the engine's own ``batch``-row chunks: the towers
    compute in bfloat16, and XLA picks its dot strategy per batch shape,
    so encoding all rows at once can round a query embedding differently
    from the engine's chunked encode."""
    prefix = eng.prefix_fn(cr=cr)
    q_emb, w, top_c = engine_lib.run_batched(
        lambda t, m, l: prefix(snap.rel_params, snap.index_params,
                               snap.norm, t, m, l),
        [tok, msk, loc], batch=batch)
    buf = snap.buffers
    be = np.asarray(buf["emb"]).astype(np.float32)
    if snap.meta.precision == "int8":
        be = be * np.asarray(buf["scale"])[..., None]
    bl, bi = np.asarray(buf["loc"]), np.asarray(buf["ids"])
    ba = np.asarray(buf["attrs"])
    w_hat = np.asarray(snap.w_hat)
    t = w_hat.shape[0]
    out_i, out_s = [], []
    for q in range(tok.shape[0]):
        ce = be[top_c[q]].reshape(-1, D)
        cl = bl[top_c[q]].reshape(-1, 2)
        ci = bi[top_c[q]].reshape(-1).copy()
        ca = ba[top_c[q]].reshape(-1, 3)
        spec = specs[q] if specs is not None else None
        fv = (spec or filters_lib.NOOP_FILTER).to_fvals()
        ci[~filters_lib.predicate_mask_np(ca, fv[None])] = -1
        trel = ce @ q_emb[q]
        d = np.linalg.norm(loc[q] - cl, axis=-1)
        s_in = 1.0 - np.clip(d / snap.meta.dist_max, 0.0, 1.0)
        srel = w_hat[np.clip(np.floor(s_in * t).astype(np.int32), 0, t - 1)]
        st = w[q, 0] * trel + w[q, 1] * srel
        st = np.where(ci >= 0, st, engine_lib.NEG_INF)
        order = np.argsort(-st, kind="stable")[:k]
        ids_q = np.where(st[order] > engine_lib.NEG_INF / 2, ci[order], -1)
        out_i.append(ids_q)
        out_s.append(st[order])
    return np.stack(out_i), np.stack(out_s)


def _assert_matches_oracle(ids, scores, want_i, want_s, specs, attrs_by_id):
    np.testing.assert_allclose(scores, want_s, rtol=2e-4, atol=2e-4)
    assert (np.sort(ids, axis=1) == np.sort(want_i, axis=1)).all()
    # every live id satisfies its row's predicate — checked against the
    # GROUND-TRUTH attribute table, not anything the engine returned
    for q in range(ids.shape[0]):
        spec = specs[q] if specs is not None else None
        if spec is None:
            continue
        fv = spec.to_fvals()
        for i in ids[q][ids[q] >= 0]:
            assert filters_lib.predicate_mask_np(
                attrs_by_id[int(i)][None], fv[None])[0]


# ---------------------------------------------------------------------------
# Backend × precision filtered parity (unsharded)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("precision", il.PRECISIONS)
def test_filtered_parity_vs_oracle(fsnap, precision, backend, rng):
    snap = snap_at(fsnap, precision)
    eng = engine_at(fsnap, precision, backend)
    b, k, cr = 10, 7, 2
    tok, msk, loc = make_requests(rng, b, fsnap.cfg)
    specs = _mixed_specs(b)
    ids, sc = eng.query(tok, msk, loc, k=k, cr=cr, batch=4,
                        snapshot=snap, filters=specs)
    want_i, want_s = filtered_oracle(eng, snap, tok, msk, loc, specs,
                                     k=k, cr=cr, batch=4)
    attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    attrs_by_id = {int(i): attrs[base_ids == i][0]
                   for i in base_ids[base_ids >= 0]}
    _assert_matches_oracle(ids, sc, want_i, want_s, specs, attrs_by_id)


def test_single_spec_broadcasts(fsnap, rng):
    """One FilterSpec (not a list) applies to every row of the request."""
    eng = engine_at(fsnap, "f32", "dense")
    tok, msk, loc = make_requests(rng, 6, fsnap.cfg)
    spec = FilterSpec(tenant=2)
    ids_b, sc_b = eng.query(tok, msk, loc, k=5, cr=2, batch=4, filters=spec)
    ids_l, sc_l = eng.query(tok, msk, loc, k=5, cr=2, batch=4,
                            filters=[spec] * 6)
    assert np.array_equal(ids_b, ids_l) and np.array_equal(sc_b, sc_l)


def test_noop_filters_use_unfiltered_plan(fsnap, rng):
    """All-no-op filter lists collapse: same results AND the same
    plan-cache entry as a plain unfiltered query (the pre-filter fast
    path stays byte-identical)."""
    eng = engine_lib.QueryEngine.from_snapshot(snap_at(fsnap, "f32"),
                                               backend="dense")
    tok, msk, loc = make_requests(rng, 4, fsnap.cfg)
    i0, s0 = eng.query(tok, msk, loc, k=5, cr=2, batch=4)
    n_plans = len(eng._plans)
    i1, s1 = eng.query(tok, msk, loc, k=5, cr=2, batch=4,
                       filters=[None] * 4)
    i2, s2 = eng.query(tok, msk, loc, k=5, cr=2, batch=4,
                       filters=filters_lib.NOOP_FILTER)
    assert len(eng._plans) == n_plans          # no new compile
    assert np.array_equal(i0, i1) and np.array_equal(i0, i2)
    assert np.array_equal(s0, s1) and np.array_equal(s0, s2)


def test_filtered_underfull_returns_padding(fsnap, rng):
    """A filter passing almost nothing yields (-1, NEG_INF) padding, not
    foreign rows — the isolation guarantee under candidate starvation."""
    eng = engine_at(fsnap, "f32", "dense")
    tok, msk, loc = make_requests(rng, 4, fsnap.cfg)
    # timestamps are < 1000 in the fixture, so this passes nothing
    ids, sc = eng.query(tok, msk, loc, k=6, cr=2, batch=4,
                        filters=FilterSpec(t_min=10_000))
    assert (ids == -1).all() and (sc < engine_lib.NEG_INF / 2).all()


# ---------------------------------------------------------------------------
# Mesh-sharded filtered parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("precision", il.PRECISIONS)
def test_filtered_sharded_parity(fsnap, precision, n_shards, rng):
    """A mesh-sharded snapshot serves the same filtered answers as the
    unsharded engine — the predicate rides the per-shard scans and the
    attrs buffers shard with their clusters."""
    if n_shards > N_DEV:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")
    snap = snap_at(fsnap, precision)
    eng = engine_at(fsnap, precision, "dense")
    b, k, cr = 8, 5, 2
    tok, msk, loc = make_requests(rng, b, fsnap.cfg)
    specs = _mixed_specs(b)
    want_i, want_s = eng.query(tok, msk, loc, k=k, cr=cr, batch=4,
                               snapshot=snap, filters=specs)
    snap_m = snap.with_mesh(n_shards)
    ids, sc = eng.query(tok, msk, loc, k=k, cr=cr, batch=4,
                        snapshot=snap_m, filters=specs)
    assert np.array_equal(ids, want_i)
    np.testing.assert_allclose(sc, want_s, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Delta-path filtered parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", il.PRECISIONS)
def test_filtered_delta_rows(fsnap, precision, rng):
    """Delta-resident rows obey the same predicate: inserted rows that
    match surface, inserted rows that fail never do, and the whole
    filtered answer equals the compacted snapshot's (ids bit-equal)."""
    snap = snap_at(fsnap, precision)
    d = DeltaSegment.empty(D, precision)
    m = 12
    emb = rng.normal(size=(m, D)).astype(np.float32)
    loc_o = rng.uniform(size=(m, 2)).astype(np.float32)
    ids_new = np.arange(9000, 9000 + m)
    # half tenant 7 (a tenant no base row has), half tenant 8
    attrs = filters_lib.make_attrs(np.where(np.arange(m) < 6, 7, 8),
                                   np.full(m, 0b1), np.arange(m))
    d = d.insert(emb, loc_o, ids_new, attrs)
    snap_d = snap.with_delta(d)
    eng = engine_at(fsnap, precision, "dense")
    b, k = 6, 8
    tok, msk, loc = make_requests(rng, b, fsnap.cfg)
    spec = FilterSpec(tenant=7)
    ids, sc = eng.query(tok, msk, loc, k=k, cr=fsnap.cfg.n_clusters,
                        batch=4, snapshot=snap_d, filters=spec)
    live = ids[ids >= 0]
    assert live.size                                # tenant-7 rows surface
    assert set(live.tolist()) <= set(ids_new[:6].tolist())
    # parity with the compacted snapshot (delta folded into the base)
    snap_c = snap_d.compact()
    want_i, want_s = eng.query(tok, msk, loc, k=k, cr=fsnap.cfg.n_clusters,
                               batch=4, snapshot=snap_c, filters=spec)
    assert np.array_equal(ids, want_i)
    np.testing.assert_allclose(sc, want_s, atol=1e-5, rtol=1e-6)


def test_filtered_delta_mixed_base_and_delta(fsnap, rng):
    """A time-window filter straddling base and delta rows returns the
    union — the predicate is one contract across both scans."""
    snap = snap_at(fsnap, "f32")
    eng = engine_at(fsnap, "f32", "dense")
    m = 8
    emb = rng.normal(size=(m, D)).astype(np.float32)
    loc_o = rng.uniform(size=(m, 2)).astype(np.float32)
    ids_new = np.arange(9500, 9500 + m)
    attrs = filters_lib.make_attrs(np.zeros(m), np.full(m, 0b1),
                                   np.full(m, 500))          # in-window
    snap_d = snap.with_delta(
        DeltaSegment.empty(D, "f32").insert(emb, loc_o, ids_new, attrs))
    tok, msk, loc = make_requests(rng, 4, fsnap.cfg)
    spec = FilterSpec(t_min=400, t_max=600)
    ids, _ = eng.query(tok, msk, loc, k=20, cr=fsnap.cfg.n_clusters,
                       batch=4, snapshot=snap_d, filters=spec)
    base_attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    in_window = set(base_ids[(base_ids >= 0) & (base_attrs[..., 2] >= 400)
                             & (base_attrs[..., 2] <= 600)].tolist())
    live = set(int(i) for i in ids[ids >= 0])
    assert live & set(ids_new.tolist())             # delta rows present
    assert live <= in_window | set(ids_new.tolist())


# ---------------------------------------------------------------------------
# Tenant isolation: the property test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("tenant", [0, 1, 2, 3])        # 3 = nobody
def test_tenant_isolation_fixed(fsnap, backend, tenant):
    """Deterministic isolation sweep — always runs, so the guarantee has
    coverage even where hypothesis is unavailable."""
    attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    tenant_of = {int(i): int(attrs[base_ids == i][0][0])
                 for i in base_ids[base_ids >= 0]}
    qrng = np.random.default_rng(29)
    tok, msk, loc = make_requests(qrng, 4, fsnap.cfg)
    eng = engine_at(fsnap, "f32", backend)
    ids, sc = eng.query(tok, msk, loc, k=9, cr=2, batch=4,
                        filters=FilterSpec(tenant=tenant))
    for i in ids[ids >= 0]:
        assert tenant_of[int(i)] == tenant
    assert ((ids >= 0) == (sc > engine_lib.NEG_INF / 2)).all()
    if tenant == 3:
        assert (ids == -1).all()            # no such tenant anywhere


def test_tenant_isolation_property(fsnap):
    """ANY tenant filter over ANY backend returns only that tenant's
    rows — hypothesis explores tenants, k, cr, and backends."""
    hypothesis = pytest.importorskip("hypothesis")
    st_ = hypothesis.strategies
    attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    tenant_of = {int(i): int(attrs[base_ids == i][0][0])
                 for i in base_ids[base_ids >= 0]}
    qrng = np.random.default_rng(23)
    tok, msk, loc = make_requests(qrng, 4, fsnap.cfg)

    @hypothesis.settings(max_examples=12, deadline=None)
    @hypothesis.given(tenant=st_.integers(0, 3),       # 3 = nobody
                      k=st_.integers(1, 12),
                      cr=st_.sampled_from([1, 2, 4]),
                      backend=st_.sampled_from(BACKENDS))
    def run(tenant, k, cr, backend):
        eng = engine_at(fsnap, "f32", backend)
        ids, sc = eng.query(tok, msk, loc, k=k, cr=cr, batch=4,
                            filters=FilterSpec(tenant=tenant))
        for i in ids[ids >= 0]:
            assert tenant_of[int(i)] == tenant
        assert ((ids >= 0) == (sc > engine_lib.NEG_INF / 2)).all()

    run()


# ---------------------------------------------------------------------------
# Server integration: filter-aware cache keys
# ---------------------------------------------------------------------------


def _mk_server(fsnap, **over):
    eng = engine_lib.QueryEngine.from_snapshot(snap_at(fsnap, "f32"),
                                               backend="dense")
    kw = dict(batch_size=2, max_delay_ms=30.0, k=5, cr=2, backend="dense")
    kw.update(over)
    return server_lib.StreamingServer(eng, server_lib.ServerConfig(**kw))


def test_server_cache_isolated_by_filter(fsnap, rng):
    """The same query text under two tenant filters — and under no
    filter — must produce three distinct cached entries; repeats hit."""
    server = _mk_server(fsnap)
    tok, msk, loc = make_requests(rng, 1, fsnap.cfg)
    f0, f1 = FilterSpec(tenant=0), FilterSpec(tenant=1)

    async def go():
        outs = {}
        for tag, f in [("t0", f0), ("t1", f1), ("nf", None)]:
            a, b = await asyncio.gather(
                server.submit(tok[0], msk[0], loc[0], filters=f),
                server.submit(tok[0], msk[0], loc[0], filters=f))
            outs[tag] = (a, b)
        return outs

    outs = asyncio.run(go())
    for tag, (a, b) in outs.items():                 # coalesced pairs agree
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    tenant_of = {int(i): int(attrs[base_ids == i][0][0])
                 for i in base_ids[base_ids >= 0]}
    for tag, tenant in [("t0", 0), ("t1", 1)]:
        ids = outs[tag][0][0]
        for i in ids[ids >= 0]:
            assert tenant_of[int(i)] == tenant, (
                f"{tag} leaked a foreign-tenant row — cache keys must "
                f"include the filter signature")
    # the three filter signatures never collide in the result sets
    assert not np.array_equal(outs["t0"][0][0], outs["t1"][0][0])

    async def again():
        return await server.submit(tok[0], msk[0], loc[0], filters=f0)

    n_queries = server.stats.engine_queries
    rep = asyncio.run(again())
    assert server.stats.engine_queries == n_queries   # exact-cache hit
    assert np.array_equal(rep[0], outs["t0"][0][0])


def test_server_filtered_matches_direct_engine(fsnap, rng):
    """A filtered flush returns exactly what a direct engine.query with
    the same per-row filter roster returns."""
    server = _mk_server(fsnap, batch_size=3)
    tok, msk, loc = make_requests(rng, 3, fsnap.cfg)
    specs = [FilterSpec(tenant=1), None, FilterSpec(category_mask=0b10)]

    async def go():
        return await asyncio.gather(*[
            server.submit(tok[i], msk[i], loc[i], filters=specs[i])
            for i in range(3)])

    out = asyncio.run(go())
    eng = engine_lib.QueryEngine.from_snapshot(snap_at(fsnap, "f32"),
                                               backend="dense")
    want_i, want_s = eng.query(tok, msk, loc, k=5, cr=2, batch=3,
                               filters=specs)
    for i, (ids, sc) in enumerate(out):
        assert np.array_equal(ids, want_i[i])
        assert np.array_equal(sc, want_s[i])


# ---------------------------------------------------------------------------
# api surface: Searcher.query(filters=) and attrs through api.build
# ---------------------------------------------------------------------------


def test_searcher_query_filters(fsnap, rng):
    s = api.Searcher(snap_at(fsnap, "f32"), backend="dense")
    tok, msk, loc = make_requests(rng, 4, fsnap.cfg)
    ids, sc = s.query(tok, msk, loc, k=5, cr=2, batch=4,
                      filters=FilterSpec(tenant=1))
    attrs = np.asarray(fsnap.buffers["attrs"])
    base_ids = np.asarray(fsnap.buffers["ids"])
    for i in ids[ids >= 0]:
        assert int(attrs[base_ids == int(i)][0][0]) == 1
