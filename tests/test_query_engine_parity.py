"""Parity/property tier for the unified query engine (DESIGN.md §4–§5).

The gather-free Pallas kernel (scalar-prefetched routing into resident
(c, cap, d) buffers, in-kernel cr-merge) must be indistinguishable from
the dense reference (gather + one top-k) across shapes, buffer padding,
tie scores, and cr ∈ {1, 2, 4} — and its jaxpr must contain NO
(B, cr·cap, d) candidate-sized intermediate (the point of the kernel).
"""
import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import jax.extend.core as jex_core
import pytest

from repro.configs import get_config
from repro.core import engine
from repro.core import index as il
from repro.core import relevance
from repro.core import spatial as sp
from repro.kernels import ops

DIST_MAX = 1.414


# ---------------------------------------------------------------------------
# Synthetic routed-query instances (no encoder: kernel-level parity)
# ---------------------------------------------------------------------------


def _mk_instance(rng, *, b, cr, c, cap, d, t=50, empty_clusters=(),
                 valid_per_cluster=None, tie_embeddings=False):
    """Random buffers + routed queries. -1 ids mark buffer padding."""
    q = rng.normal(size=(b, d)).astype(np.float32)
    ql = rng.uniform(size=(b, 2)).astype(np.float32)
    w = rng.uniform(0.5, 1.5, size=(b, 2)).astype(np.float32)
    be = rng.normal(size=(c, cap, d)).astype(np.float32)
    bl = rng.uniform(size=(c, cap, 2)).astype(np.float32)
    bi = np.arange(c * cap, dtype=np.int32).reshape(c, cap)
    if valid_per_cluster is not None:        # partially-filled clusters
        bi[:, valid_per_cluster:] = -1
    for ci in empty_clusters:                # fully-empty clusters
        bi[ci] = -1
    be[bi < 0] = 0.0
    bl[bi < 0] = 1e6
    if tie_embeddings:                       # every candidate scores equal
        be[:] = be[0, 0]
        bl[:] = 0.25
        ql[:] = 0.25
    top_c = rng.integers(0, c, size=(b, cr)).astype(np.int32)
    w_hat = np.cumsum(rng.uniform(0, 0.05, size=t)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, ql, w, top_c, be, bl, bi, w_hat))


def _both_backends(args, *, k, block_n=512):
    s_p, i_p = ops.fused_topk_score_routed(*args, k=k, dist_max=DIST_MAX,
                                           block_n=block_n, interpret=True)
    s_d, i_d = engine.dense_routed_topk(*args, k=k, dist_max=DIST_MAX)
    return (np.asarray(s_p), np.asarray(i_p),
            np.asarray(s_d), np.asarray(i_d))


# ---------------------------------------------------------------------------
# Shape sweep: n < block_n, cap not a multiple of block_n, cr ∈ {1,2,4}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cr", [1, 2, 4])
@pytest.mark.parametrize("b,c,cap,d,k,block_n", [
    (8, 6, 64, 32, 5, 512),      # cap < block_n: single-tile clusters
    (16, 4, 128, 16, 10, 32),    # multi-tile streaming per cluster
    (3, 5, 96, 8, 7, 64),        # odd b; block_n forced down to gcd=32
    (1, 2, 32, 64, 32, 512),     # single query, k == cap
])
def test_routed_kernel_matches_dense_reference(b, c, cap, d, k, cr, block_n,
                                               rng):
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d)
    s_p, i_p, s_d, i_d = _both_backends(args, k=k, block_n=block_n)
    np.testing.assert_allclose(s_p, s_d, rtol=1e-4, atol=1e-4)
    # identical id SETS per query (tie order inside equal scores is free)
    assert (np.sort(i_p, axis=1) == np.sort(i_d, axis=1)).all()


@pytest.mark.parametrize("cr", [1, 2, 4])
def test_k_exceeds_valid_candidates(cr, rng):
    """k > valid candidates: both backends pad with (-1, NEG_INF)."""
    b, c, cap, d, k = 6, 4, 32, 16, 20
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d,
                        valid_per_cluster=3)        # ≤ 3·cr valid per query
    s_p, i_p, s_d, i_d = _both_backends(args, k=k)
    np.testing.assert_allclose(s_p, s_d, rtol=1e-4, atol=1e-4)
    assert (np.sort(i_p, axis=1) == np.sort(i_d, axis=1)).all()
    n_valid = (i_p >= 0).sum(1)
    assert (n_valid <= 3 * cr).all()
    assert ((s_p < -1e29) == (i_p < 0)).all()       # pads are NEG_INF/-1


def test_fully_empty_routed_clusters(rng):
    """Queries routed into all-padding clusters return only pads."""
    b, c, cap, d, k, cr = 4, 4, 32, 16, 5, 2
    args = list(_mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d,
                             empty_clusters=(1, 3)))
    args[3] = jnp.asarray(np.array([[1, 3]] * b, np.int32))  # route to empties
    s_p, i_p, s_d, i_d = _both_backends(tuple(args), k=k)
    assert (i_p == -1).all() and (i_d == -1).all()
    np.testing.assert_allclose(s_p, s_d)
    # mixed routing: one empty + one live cluster still merges correctly
    args[3] = jnp.asarray(np.array([[1, 0]] * b, np.int32))
    s_p, i_p, s_d, i_d = _both_backends(tuple(args), k=k)
    np.testing.assert_allclose(s_p, s_d, rtol=1e-4, atol=1e-4)
    assert (np.sort(i_p, axis=1) == np.sort(i_d, axis=1)).all()
    assert (i_p < cap).all()                        # only cluster-0 objects


@pytest.mark.parametrize("cr", [1, 2, 4])
def test_tie_scores(cr, rng):
    """All candidates score identically: backends may order ties freely,
    but scores must match exactly and every returned id must be a real,
    distinct candidate from the routed clusters."""
    b, c, cap, d, k = 5, 4, 32, 16, 8
    args = _mk_instance(rng, b=b, cr=cr, c=c, cap=cap, d=d,
                        tie_embeddings=True)
    s_p, i_p, s_d, i_d = _both_backends(args, k=k)
    np.testing.assert_allclose(s_p, s_d, rtol=1e-4, atol=1e-4)
    top_c, bi = np.asarray(args[3]), np.asarray(args[6])
    for row in range(b):
        routed = set(bi[top_c[row]].reshape(-1).tolist()) - {-1}
        picked = i_p[row].tolist()
        assert len(set(picked)) == k                # no duplicates
        assert set(picked) <= routed                # all from routed clusters


# ---------------------------------------------------------------------------
# Engine-level parity (encoder + router + kernel) and batch padding
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_setup():
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=32, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=4, index_mlp_hidden=(16,))
    rng = np.random.default_rng(7)
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    n, c, cap = 160, cfg.n_clusters, 64
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(5), cfg.d_model, c,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap)
    w_hat = sp.extract_lookup(params["spatial"])
    return cfg, params, iparams, norm, buf, w_hat


@pytest.mark.parametrize("backend", ["pallas", "pallas-cm", "dense-cm"])
@pytest.mark.parametrize("cr", [1, 2, 4])
def test_engine_backend_parity_end_to_end(engine_setup, cr, backend, rng):
    """Every non-reference backend — query-major pallas AND the two
    cluster-major flavors (DESIGN.md §10) — matches the dense oracle
    through the full encode→route→scan pipeline."""
    cfg, params, iparams, norm, buf, w_hat = engine_setup
    b, k = 8, 5
    tok = jnp.asarray(rng.integers(2, 512, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    a = (params, iparams, w_hat, norm, buf["emb"], buf["loc"], buf["ids"],
         buf["scale"], tok, msk, ql)
    fd = engine.make_query_fn(cfg, cr=cr, k=k, backend="dense",
                              dist_max=DIST_MAX)
    fp = engine.make_query_fn(cfg, cr=cr, k=k, backend=backend,
                              interpret=True, dist_max=DIST_MAX)
    i_d, s_d, _ = fd(*a)
    i_p, s_p, _ = fp(*a)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_d),
                               rtol=1e-4, atol=1e-4)
    assert (np.sort(np.asarray(i_p)) == np.sort(np.asarray(i_d))).all()


# ---------------------------------------------------------------------------
# Precision tiers (DESIGN.md §9): dense↔pallas parity WITHIN each tier,
# and quantization fidelity against the exact-f32 ranking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "pallas-cm", "dense-cm"])
@pytest.mark.parametrize("precision", ["bf16", "int8"])
@pytest.mark.parametrize("cr", [1, 2])
def test_engine_precision_tier_backend_parity(engine_setup, precision, cr,
                                              backend, rng):
    """Within a precision tier every backend must agree with the dense
    reference: the kernels (query- AND cluster-major) dequantize in VMEM
    with the same per-row scales the dense paths apply after their
    gathers."""
    from repro.core import index as il2
    cfg, params, iparams, norm, buf, w_hat = engine_setup
    qbuf = il2.quantize_buffers(buf, precision)
    assert str(np.asarray(qbuf["emb"]).dtype) == (
        "bfloat16" if precision == "bf16" else "int8")
    b, k = 8, 5
    tok = jnp.asarray(rng.integers(2, 512, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    a = (params, iparams, w_hat, norm, qbuf["emb"], qbuf["loc"],
         qbuf["ids"], qbuf["scale"], tok, msk, ql)
    fd = engine.make_query_fn(cfg, cr=cr, k=k, backend="dense",
                              dist_max=DIST_MAX, precision=precision)
    fp = engine.make_query_fn(cfg, cr=cr, k=k, backend=backend,
                              interpret=True, dist_max=DIST_MAX,
                              precision=precision)
    i_d, s_d, _ = fd(*a)
    i_p, s_p, _ = fp(*a)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_d),
                               rtol=1e-4, atol=1e-4)
    assert (np.sort(np.asarray(i_p)) == np.sort(np.asarray(i_d))).all()


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_quantized_scores_track_f32(engine_setup, precision, rng):
    """Quantization changes TRel by at most the scalar-quantization error
    — SRel, routing, and padding are bit-identical, so the tier's scores
    must stay close to f32 and the top-k sets mostly overlap."""
    from repro.core import index as il2
    cfg, params, iparams, norm, buf, w_hat = engine_setup
    qbuf = il2.quantize_buffers(buf, precision)
    b, k, cr = 16, 10, 2
    tok = jnp.asarray(rng.integers(2, 512, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    f_exact = engine.make_query_fn(cfg, cr=cr, k=k, backend="dense",
                                   dist_max=DIST_MAX)
    f_quant = engine.make_query_fn(cfg, cr=cr, k=k, backend="dense",
                                   dist_max=DIST_MAX, precision=precision)
    i_e, s_e, _ = f_exact(params, iparams, w_hat, norm, buf["emb"],
                          buf["loc"], buf["ids"], buf["scale"], tok, msk, ql)
    i_q, s_q, _ = f_quant(params, iparams, w_hat, norm, qbuf["emb"],
                          qbuf["loc"], qbuf["ids"], qbuf["scale"], tok, msk,
                          ql)
    # int8 per-row scalar quantization bounds the per-element embedding
    # error by scale/2; bf16 by ~2^-8 relative — both stay well under 2%
    # of the score magnitude at this scale
    np.testing.assert_allclose(np.asarray(s_q), np.asarray(s_e),
                               rtol=0.05, atol=0.05)
    overlap = np.mean([
        len(set(np.asarray(i_q)[r].tolist())
            & set(np.asarray(i_e)[r].tolist())) / k
        for r in range(b)])
    assert overlap >= 0.9, f"{precision} top-{k} overlap {overlap}"


def test_run_batched_pads_partial_batches(rng):
    """b % batch != 0: the static-shape padding trims outputs exactly."""
    calls = []

    def fn(x, y):
        calls.append(x.shape[0])
        return x * 2, y + 1

    x = rng.normal(size=(23, 4)).astype(np.float32)
    y = rng.normal(size=(23, 2)).astype(np.float32)
    ox, oy = engine.run_batched(fn, [x, y], batch=8)
    assert ox.shape == (23, 4) and oy.shape == (23, 2)
    assert calls == [8, 8, 8]                  # every chunk static-shaped
    np.testing.assert_allclose(ox, x * 2, rtol=1e-6)
    np.testing.assert_allclose(oy, y + 1, rtol=1e-6)


def test_run_batched_stacks_chunk_outputs(rng):
    """Outputs that describe a whole chunk come back one per chunk,
    untrimmed, beside the trimmed row outputs."""
    x = rng.normal(size=(19, 3)).astype(np.float32)
    out, count = engine.run_batched(
        lambda c: (c * 2, np.array([c.shape[0], 1])), [x], batch=8,
        chunk_outputs=1)
    np.testing.assert_allclose(out, x * 2, rtol=1e-6)
    assert count.tolist() == [[8, 1]] * 3


def test_run_batched_overlaps_transfer_with_dispatch(rng):
    """Chunk i's outputs are materialized (host sync) only AFTER chunk
    i+1 has been dispatched — the transfer/compute overlap of the
    serving path. Observed via __array__ hooks on the returned values."""
    events = []

    class Lazy:
        def __init__(self, arr, tag):
            self.arr, self.tag = arr, tag

        def __array__(self, dtype=None, copy=None):
            events.append(("sync", self.tag))
            return self.arr

    def fn(x):
        tag = sum(1 for e in events if e[0] == "dispatch")
        events.append(("dispatch", tag))
        return Lazy(np.asarray(x) * 2, tag)

    x = rng.normal(size=(24, 3)).astype(np.float32)
    out = engine.run_batched(fn, [x], batch=8)
    np.testing.assert_allclose(out, x * 2, rtol=1e-6)
    assert events == [("dispatch", 0), ("dispatch", 1), ("sync", 0),
                      ("dispatch", 2), ("sync", 1), ("sync", 2)]


def test_resolve_backend_rules():
    assert engine.resolve_backend("dense") == ("dense",
                                               engine.default_interpret())
    assert engine.resolve_backend("pallas", interpret=True) == ("pallas",
                                                                True)
    # auto keys on hardware, NOT the interpret flag: pallas iff on TPU
    expect = "pallas" if jax.default_backend() == "tpu" else "dense"
    assert engine.resolve_backend("auto", interpret=True)[0] == expect
    assert engine.resolve_backend("auto", interpret=False)[0] == expect
    with pytest.raises(ValueError):
        engine.resolve_backend("tpu")
    # the legacy entry points are collapsed: use_pallas survives ONLY as
    # the CLI alias in resolve_cli_backend (tested in test_server), and
    # pipeline no longer wraps the engine's query-fn builder
    from repro.core import pipeline as pl
    assert not hasattr(engine, "legacy_backend")
    assert not hasattr(pl, "make_query_fn")


# ---------------------------------------------------------------------------
# The acceptance criterion: the pallas path's jaxpr has NO candidate copy
# ---------------------------------------------------------------------------


def _subjaxprs_of(params):
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _all_eqn_out_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield aval
        for sub in _subjaxprs_of(eqn.params):
            yield from _all_eqn_out_avals(sub)


def test_pallas_jaxpr_has_no_candidate_gather(engine_setup, rng):
    """The gather path materializes a (B, cr·cap, d) copy; the routed
    kernel must not — assert no candidate-sized intermediate exists."""
    cfg, params, iparams, norm, buf, w_hat = engine_setup
    b, k, cr = 8, 5, 2
    cap, d = buf["emb"].shape[1], buf["emb"].shape[2]
    cand_size = b * cr * cap * d
    tok = jnp.asarray(rng.integers(2, 512, (b, 8)), jnp.int32)
    msk = jnp.ones((b, 8), bool)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    a = (params, iparams, w_hat, norm, buf["emb"], buf["loc"], buf["ids"],
         buf["scale"], tok, msk, ql)

    def sizes(backend):
        fn = engine.make_query_fn(cfg, cr=cr, k=k, backend=backend,
                                  interpret=True, dist_max=DIST_MAX)
        jaxpr = jax.make_jaxpr(fn)(*a)
        return [int(np.prod(av.shape))
                for av in _all_eqn_out_avals(jaxpr.jaxpr)]

    dense_sizes = sizes("dense")
    assert cand_size in dense_sizes, (
        "detector broken: dense path should materialize the candidate copy")
    pallas_sizes = sizes("pallas")
    assert cand_size not in pallas_sizes, (
        "gather-free path materialized a (B, cr·cap, d)-sized intermediate")
    assert max(pallas_sizes) < cand_size, (
        f"pallas path has an intermediate ≥ candidate copy: "
        f"{max(pallas_sizes)} vs {cand_size}")
    # cluster-major goes FURTHER: its largest intermediate is bounded by
    # the distinct-cluster working set min(B·cr, c)·cap·d — smaller than
    # the query-major candidate copy whenever the batch saturates the
    # cluster set (here 4 < 16 routed scans). This bound assumes the
    # roster payload fits it, i.e. B·cr ≤ cap (here 16 ≤ 64) — exactly
    # the regime engine.cluster_major_feasible admits for auto
    cm_sizes = sizes("pallas-cm")
    c = buf["emb"].shape[0]
    cm_bound = min(b * cr, c) * cap * d
    assert cand_size not in cm_sizes
    assert max(cm_sizes) <= cm_bound < cand_size, (
        f"cluster-major intermediate {max(cm_sizes)} exceeds the "
        f"distinct-cluster working set {cm_bound}")
