"""Mesh-sharded serving parity tier (DESIGN.md §12).

The headline contract: partitioning the resident cluster buffers across
a device mesh is PLACEMENT, not content — for every shard count, backend
and precision tier the sharded engine returns

* bit-identical top-k ids vs the single-device engine,
* scores equal to the single-device engine up to fusion ulps (the
  decomposed prefix+scan programs are distinct XLA programs from the
  fused single-device plan, so the last bit of a float reduction may
  differ — ids never do),
* bit-identical ids AND scores across shard counts (the sharded path is
  one program family: S=1 vs S=8 agree on every bit).

Runs multi-device on CPU: conftest force-sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` ahead of any jax
import, and the CI ``mesh`` job exports the same flag.

Also covers the satellites: a hypothesis property over random
cluster→shard assignments, non-divisible ``c % n_shards`` remainder
handling, elastic persistence (save sharded → load under 8→4→1 devices,
bit-identical to the never-sharded build, including a delta-nonempty
LSM case), and server hot-swap of a re-sharded snapshot under open-loop
load with zero failed/torn requests.
"""
import asyncio
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.configs import get_config
from repro.core import delta as delta_lib
from repro.core import engine as engine_lib
from repro.core import index as il
from repro.core import relevance
from repro.core import server as server_lib
from repro.core.snapshot import IndexSnapshot

DIST_MAX = 1.4142
BACKENDS = ("dense", "pallas", "dense-cm", "pallas-cm")
SHARD_COUNTS = (1, 2, 4, 8)
N_DEV = jax.device_count()


def _need(n_shards):
    if n_shards > N_DEV:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV} "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")


def _build_snap(n_clusters, seed=0, n=96, cap=32):
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=32, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=n_clusters,
        index_mlp_hidden=(16,))
    rng = np.random.default_rng(seed)
    rel = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, n_clusters,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc,
                                   n_clusters=n_clusters, capacity=cap)
    return IndexSnapshot.from_parts(cfg, rel, iparams, norm, buf,
                                    dist_max=DIST_MAX)


def _make_queries(cfg, n=12, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(2, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
    tok[:, 0] = 1
    msk = np.ones_like(tok, bool)
    loc = rng.uniform(size=(n, 2)).astype(np.float32)
    return tok, msk, loc


@pytest.fixture(scope="module")
def snap8():
    return _build_snap(8)            # c divisible by every shard count


@pytest.fixture(scope="module")
def queries(snap8):
    return _make_queries(snap8.cfg)


# one query run per (precision, backend, S) for the whole module — the
# matrix below compares cached results, not 48 fresh compiles
_cache = {}


def _run(snap, backend, queries, *, tag):
    if tag not in _cache:
        tok, msk, loc = queries
        _cache[tag] = api.Searcher(snap, backend=backend).query(
            tok, msk, loc, k=5, cr=2, batch=4)
    return _cache[tag]


def _ref(snap8, precision, backend, queries):
    return _run(snap8.with_precision(precision), backend, queries,
                tag=("ref", precision, backend))


def _sharded(snap8, precision, backend, n_shards, queries):
    key = ("mesh", precision, n_shards)
    if key not in _cache:
        _cache[key] = snap8.with_precision(precision).with_mesh(n_shards)
    return _run(_cache[key], backend, queries,
                tag=("out", precision, backend, n_shards))


# ---------------------------------------------------------------------------
# The parity matrix: {1,2,4,8} shards × backends × precision tiers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", il.PRECISIONS)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_parity_matrix(snap8, queries, precision, backend, n_shards):
    _need(n_shards)
    ref_ids, ref_sc = _ref(snap8, precision, backend, queries)
    ids, sc = _sharded(snap8, precision, backend, n_shards, queries)
    assert np.array_equal(ref_ids, ids)             # ids: every bit
    assert np.allclose(ref_sc, sc, rtol=2e-5, atol=1e-6)
    # placement invariance: EVERY bit agrees across shard counts
    a_ids, a_sc = _sharded(snap8, precision, backend, 1, queries)
    assert np.array_equal(a_ids, ids)
    assert np.array_equal(a_sc, sc)


def test_with_mesh_is_placement_not_content(snap8):
    _need(2)
    s = snap8.with_mesh(2)
    assert s.meta.version == snap8.meta.version     # no version bump
    assert s.meta.n_shards == 2
    assert s.shards is not None and s.shards.n_shards == 2
    # buffers stay global host arrays, bit-identical to the base
    for k in ("emb", "loc", "ids", "scale", "counts"):
        assert np.array_equal(np.asarray(s.buffers[k]),
                              np.asarray(snap8.buffers[k]))
    u = s.unshard()
    assert u.shards is None and u.meta.n_shards == 1
    assert np.array_equal(np.asarray(u.buffers["ids"]),
                          np.asarray(snap8.buffers["ids"]))


def test_content_derivations_reshard(snap8, rng):
    """with_buffers / with_precision / compact on a sharded snapshot
    hand back a snapshot sharded the same way (stale placements would
    silently serve the OLD buffers)."""
    _need(2)
    s = snap8.with_mesh(2)
    p = s.with_precision("int8")
    assert p.shards is not None and p.shards.n_shards == 2
    assert p.meta.n_shards == 2
    new_emb = jnp.asarray(rng.normal(size=(3, snap8.cfg.d_model)),
                          jnp.float32)
    new_loc = jnp.asarray(rng.uniform(size=(3, 2)), jnp.float32)
    buf = il.insert_objects(s.buffers, s.index_params, s.norm,
                            new_emb, new_loc, np.arange(8000, 8003))
    g = s.with_buffers(buf)
    assert g.shards is not None and g.shards.n_shards == 2
    assert (np.asarray(g.buffers["ids"]) >= 8000).any()
    # and the new rows are actually resident on the shards
    got = np.concatenate([np.asarray(part["ids"]).ravel()
                          for part in g.shards.parts])
    assert np.isin(np.arange(8000, 8003), got).all()


# ---------------------------------------------------------------------------
# Remainder policy: c % n_shards != 0 pads short shards, never mis-shards
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", (4, 8))
def test_nondivisible_remainder_parity(n_shards):
    _need(n_shards)
    snap6 = _build_snap(6, seed=2)          # 6 % 4 == 2, 6 < 8
    tok, msk, loc = _make_queries(snap6.cfg, seed=2)
    ref = api.Searcher(snap6, backend="dense").query(tok, msk, loc,
                                                     k=5, cr=2, batch=4)
    s = snap6.with_mesh(n_shards)
    # with 8 shards and 6 clusters some shards hold ONLY padding
    out = api.Searcher(s, backend="dense").query(tok, msk, loc,
                                                 k=5, cr=2, batch=4)
    assert np.array_equal(ref[0], out[0])
    assert np.allclose(ref[1], out[1], rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Property: parity holds for EVERY cluster→shard assignment
# ---------------------------------------------------------------------------


try:                       # optional: richer shrinking when available
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # seeded-random fallback, same property
    HAVE_HYPOTHESIS = False

_PROP = {}


def _assignment_parity(n_shards, assignment):
    """The property: ANY cluster→shard map — balanced, skewed, or
    starving some shard entirely — yields bit-identical top-k ids."""
    if not _PROP:
        _PROP["snap"] = _build_snap(8, seed=4)
        _PROP["q"] = _make_queries(_PROP["snap"].cfg, seed=4)
        tok, msk, loc = _PROP["q"]
        _PROP["ref"] = api.Searcher(_PROP["snap"], backend="dense").query(
            tok, msk, loc, k=5, cr=2, batch=12)
    snap, (tok, msk, loc), ref = _PROP["snap"], _PROP["q"], _PROP["ref"]
    s = snap.with_mesh(n_shards, assignment=np.asarray(assignment,
                                                       np.int32))
    out = api.Searcher(s, backend="dense").query(tok, msk, loc,
                                                 k=5, cr=2, batch=12)
    assert np.array_equal(ref[0], out[0]), (n_shards, list(assignment))
    assert np.allclose(ref[1], out[1], rtol=2e-5, atol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(data=st.data())
    def test_random_assignment_parity(data):
        if N_DEV < 2:
            pytest.skip("needs 2+ devices")
        n_shards = data.draw(st.integers(2, min(8, N_DEV)))
        assignment = data.draw(st.lists(st.integers(0, n_shards - 1),
                                        min_size=8, max_size=8))
        _assignment_parity(n_shards, assignment)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_assignment_parity(seed):
        if N_DEV < 2:
            pytest.skip("needs 2+ devices")
        rng = np.random.default_rng(100 + seed)
        n_shards = int(rng.integers(2, min(8, N_DEV) + 1))
        # seed 0 pins the adversarial corner: everything on one shard
        if seed == 0:
            assignment = np.zeros(8, np.int32)
        else:
            assignment = rng.integers(0, n_shards, size=8)
        _assignment_parity(n_shards, assignment)


# ---------------------------------------------------------------------------
# Elastic persistence: save sharded, load under 8→4→1 devices
# ---------------------------------------------------------------------------


def test_sharded_persistence_elastic(snap8, queries, tmp_path):
    """Arrays persist GLOBAL (gather-on-save): a snapshot sharded 8 ways
    re-shards at load time to whatever this host can hold — 4, 1, or
    unsharded — with bit-identical ids vs the never-sharded build."""
    _need(2)
    tok, msk, loc = queries
    ref = _ref(snap8, "f32", "dense", queries)
    s = snap8.with_mesh(min(8, N_DEV))
    assert s.meta.n_shards == min(8, N_DEV)
    api.save(s, str(tmp_path))
    for n_shards in (4, 2, 1):
        if n_shards > N_DEV:
            continue
        loaded = api.load(str(tmp_path), mesh=n_shards)
        assert loaded.meta.n_shards == n_shards
        out = api.Searcher(loaded, backend="dense").query(
            tok, msk, loc, k=5, cr=2, batch=4)
        assert np.array_equal(ref[0], out[0])
        assert np.allclose(ref[1], out[1], rtol=2e-5, atol=1e-6)
        # and bitwise vs the in-memory sharded run at the same count
        mem = _sharded(snap8, "f32", "dense", n_shards, queries)
        assert np.array_equal(mem[0], out[0])
        assert np.array_equal(mem[1], out[1])
    # a plain load is UNSHARDED and fully bit-identical to the base
    plain = api.load(str(tmp_path))
    assert plain.shards is None and plain.meta.n_shards == 1
    out = api.Searcher(plain, backend="dense").query(tok, msk, loc,
                                                     k=5, cr=2, batch=4)
    assert np.array_equal(ref[0], out[0])
    assert np.array_equal(ref[1], out[1])


def test_sharded_persistence_with_delta(snap8, queries, tmp_path, rng):
    """The LSM path under sharding: a snapshot with a NON-EMPTY delta
    segment (pending inserts + tombstones, DESIGN.md §11) round-trips
    sharded and serves identically — the delta merge is
    placement-agnostic and composes after the sharded base scan."""
    _need(2)
    tok, msk, loc = queries
    d = snap8.cfg.d_model
    # the delta rows are scaled copies of the first queries' own
    # embeddings at their locations, so they rank first by construction
    # (random rows rank wherever the random draw puts them)
    prefix = engine_lib.QueryEngine(snap8, backend="dense").prefix_fn(cr=1)
    q_emb = np.asarray(prefix(snap8.rel_params, snap8.index_params,
                              snap8.norm, tok[:4], msk[:4], loc[:4])[0])
    seg = delta_lib.DeltaSegment.empty(d, "f32")
    seg = seg.insert(4.0 * q_emb, loc[:4], np.arange(9000, 9004))
    live_id = int(np.asarray(snap8.buffers["ids"]).ravel()[0])
    seg = seg.delete([live_id])
    snap_d = snap8.with_delta(seg)
    assert snap_d.meta.delta_rows == 4 and snap_d.meta.n_tombstones == 1

    ref = api.Searcher(snap_d, backend="dense").query(
        tok, msk, loc, k=5, cr=8, batch=4)
    assert (ref[0] >= 9000).any()               # delta rows retrievable
    assert not (ref[0] == live_id).any()        # tombstone filtered

    s = snap_d.with_mesh(min(4, N_DEV))
    api.save(s, str(tmp_path))
    loaded = api.load(str(tmp_path), mesh=2)
    assert loaded.meta.delta_rows == 4
    out = api.Searcher(loaded, backend="dense").query(
        tok, msk, loc, k=5, cr=8, batch=4)
    assert np.array_equal(ref[0], out[0])
    assert np.allclose(ref[1], out[1], rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Engine internals: the shard-topk tree merge
# ---------------------------------------------------------------------------


def test_merge_shard_topk_equals_global_topk(rng):
    k, n, parts = 6, 9, 5
    ids = rng.integers(0, 100_000, size=(parts, n, k)).astype(np.int32)
    sc = rng.normal(size=(parts, n, k)).astype(np.float32)
    sc = -np.sort(-sc, axis=-1)                 # each part sorted desc
    got_ids, got_sc = engine_lib.merge_shard_topk(
        [(ids[p], sc[p]) for p in range(parts)], k=k)
    all_sc = sc.transpose(1, 0, 2).reshape(n, parts * k)
    all_ids = ids.transpose(1, 0, 2).reshape(n, parts * k)
    order = np.argsort(-all_sc, axis=-1, kind="stable")[:, :k]
    assert np.array_equal(got_sc, np.take_along_axis(all_sc, order, -1))
    assert np.array_equal(got_ids, np.take_along_axis(all_ids, order, -1))
    assert got_ids.dtype == np.int32 and got_sc.dtype == np.float32


# ---------------------------------------------------------------------------
# Server hot-swap of a re-sharded snapshot under open-loop load
# ---------------------------------------------------------------------------


def test_open_loop_swap_resharded_zero_failed_or_torn(snap8, rng):
    """Mid-run publish of a GROWN, re-sharded successor: zero failed
    requests, every answer matches exactly one generation's sharded
    oracle bit-for-bit (none torn across two)."""
    _need(2)
    s1 = snap8.with_mesh(2)
    server = server_lib.StreamingServer(
        engine_lib.QueryEngine.from_snapshot(s1, backend="dense"),
        server_lib.ServerConfig(batch_size=4, max_delay_ms=1.0,
                                k=5, cr=2, backend="dense"))
    n = 32
    tok, msk, loc = _make_queries(snap8.cfg, n=n, seed=9)
    requests = [(tok[i], msk[i], loc[i]) for i in range(n)]
    # the successor: new objects inserted, re-sharded 4 ways — a shard
    # TOPOLOGY change riding the same publish
    new_emb = jnp.asarray(rng.normal(size=(5, snap8.cfg.d_model)),
                          jnp.float32)
    new_loc = jnp.asarray(rng.uniform(size=(5, 2)), jnp.float32)
    buf = il.insert_objects(s1.buffers, s1.index_params, s1.norm,
                            new_emb, new_loc, np.arange(5000, 5005))
    s2 = s1.with_buffers(buf).with_mesh(min(4, N_DEV))
    assert s2.meta.version == s1.meta.version + 1

    versions = []
    orig = server.engine.query

    def spy_then_swap(*a, **kw):
        versions.append(kw["snapshot"].meta.version)
        res = orig(*a, **kw)
        if len(versions) == 2:
            server.publish(s2)
        return res

    server.engine.query = spy_then_swap
    results = asyncio.run(server_lib.open_loop(server, requests,
                                               qps=4000.0))
    assert len(results) == n                    # zero failed requests
    assert server.engine.snapshot is s2
    assert set(versions) <= {s1.meta.version, s2.meta.version}
    o1 = engine_lib.QueryEngine.from_snapshot(s1, backend="dense")
    o2 = engine_lib.QueryEngine.from_snapshot(s2, backend="dense")
    ids1, sc1 = o1.query(tok, msk, loc, k=5, cr=2, batch=4)
    ids2, sc2 = o2.query(tok, msk, loc, k=5, cr=2, batch=4)
    for i, (ids, sc) in enumerate(results):
        old = np.array_equal(ids, ids1[i]) and np.array_equal(sc, sc1[i])
        new = np.array_equal(ids, ids2[i]) and np.array_equal(sc, sc2[i])
        assert old or new, f"request {i} matches NEITHER snapshot (torn)"
    assert s1.meta.version in versions          # both generations served
    assert s2.meta.version in versions
    m = server.metrics()
    assert m["n_shards"] == s2.meta.n_shards
    assert len(m["shard_bytes_per_device"]) == s2.meta.n_shards


# ---------------------------------------------------------------------------
# Route localization: shards that hold ONLY padding clusters
# ---------------------------------------------------------------------------


def test_localize_routes_all_off_shard():
    """A shard owning none of the routed clusters localizes EVERY route
    to its sentinel row — never clamps into a real local cluster."""
    from repro.core import serving
    # 6 global clusters on 3 shards: shard_of [0,0,1,1,2,2]
    shard_of = np.array([0, 0, 1, 1, 2, 2], np.int32)
    local_of = np.array([0, 1, 0, 1, 0, 1], np.int32)
    top_c = np.array([[0, 1], [0, 5], [4, 5]], np.int32)
    sentinel = 2
    # shard 1 owns clusters {2, 3}; no query routes there
    out = serving.localize_routes(top_c, shard_of, local_of, 1,
                                  sentinel=sentinel)
    assert out.shape == top_c.shape and out.dtype == np.int32
    assert (out == sentinel).all()
    # shards 0 and 2 see their own rows, sentinel elsewhere
    out0 = serving.localize_routes(top_c, shard_of, local_of, 0,
                                   sentinel=sentinel)
    assert out0.tolist() == [[0, 1], [0, sentinel],
                             [sentinel, sentinel]]
    out2 = serving.localize_routes(top_c, shard_of, local_of, 2,
                                   sentinel=sentinel)
    assert out2.tolist() == [[sentinel, sentinel], [sentinel, 1], [0, 1]]


def test_shard_holding_only_padding_clusters(rng):
    """A shard whose assigned clusters are ALL empty (every id -1)
    contributes only sentinel rows: the sharded answer still equals the
    unsharded oracle, and localization on that shard is all-sentinel."""
    _need(2)
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=32, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=4, index_mlp_hidden=(16,))
    rng_o = np.random.default_rng(41)
    rel = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n = 64
    obj_emb = rng_o.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng_o.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(1), cfg.d_model, 4,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    top = np.clip(top, 0, 1)           # clusters 2 and 3 stay EMPTY
    # capacity n: the first hop (cluster 0 or 1) always has room, so no
    # object ever falls back to the least-loaded (empty) clusters
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=4,
                                   capacity=n)
    snap = IndexSnapshot.from_parts(cfg, rel, iparams, norm, buf,
                                    dist_max=DIST_MAX)
    bi = np.asarray(snap.buffers["ids"])
    assert (bi[2:] == -1).all()        # the premise: all-padding clusters
    # assignment pins the two empty clusters alone on shard 1
    s = snap.with_mesh(2, assignment=np.array([0, 0, 1, 1], np.int32))
    from repro.core import serving
    sh = s.shards
    tok, msk, loc = _make_queries(cfg, n=8, seed=3)
    eng = engine_lib.QueryEngine.from_snapshot(snap, backend="dense")
    want = eng.query(tok, msk, loc, k=5, cr=2, batch=4, snapshot=snap)
    got = eng.query(tok, msk, loc, k=5, cr=2, batch=4, snapshot=s)
    assert np.array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=1e-6)
    # any routing (even straight into the empty clusters) localizes on
    # shard 1 to the sentinel, and its answers are pure padding
    top_c = np.array([[2, 3], [0, 1]], np.int32)
    local = serving.localize_routes(top_c, sh.shard_of, sh.local_of, 1,
                                    sentinel=sh.sentinel)
    # shard 1's REAL rows are its two empty clusters; routing into them
    # is indistinguishable from the sentinel: ids are -1 either way
    part_ids = np.asarray(sh.parts[1]["ids"])
    assert (part_ids[local] == -1).all()
    local0 = serving.localize_routes(top_c, sh.shard_of, sh.local_of, 0,
                                     sentinel=sh.sentinel)
    assert local0.tolist() == [[sh.sentinel, sh.sentinel], [0, 1]]
