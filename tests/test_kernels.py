"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("b,n,d,t,k", [
    (8, 1024, 32, 50, 5),
    (16, 2048, 64, 100, 10),
    (4, 512, 128, 1000, 20),
])
def test_fused_topk_score(b, n, d, t, k, rng):
    """Every query routed to one n-object cluster: the routed kernel's
    scores equal the per-candidate reference's, with padding ids and
    duplicate ids in the buffer and a step table of up to 1000 entries
    (several 128-entry lookup chunks)."""
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    be = jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32)
    bl = jnp.asarray(rng.uniform(size=(1, n, 2)), jnp.float32)
    bi = jnp.asarray(rng.integers(-1, 10_000, size=(1, n)), jnp.int32)
    tc = jnp.zeros((b, 1), jnp.int32)
    wh = jnp.asarray(np.cumsum(rng.uniform(0, 0.01, size=t)), jnp.float32)
    s1, _ = ops.fused_topk_score_routed(q, ql, w, tc, be, bl, bi, wh, k=k,
                                        dist_max=1.414, interpret=True)
    s2, _ = ref.fused_topk_score_ref(
        q, ql, w, jnp.broadcast_to(be, (b, n, d)),
        jnp.broadcast_to(bl, (b, n, 2)), jnp.broadcast_to(bi, (b, n)), wh,
        k=k, dist_max=1.414)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,c,cap,d,t,k,cr", [
    (8, 8, 256, 32, 50, 5, 1),
    (4, 6, 128, 64, 100, 10, 2),
    (5, 4, 64, 16, 20, 8, 4),
])
def test_fused_topk_score_routed(b, c, cap, d, t, k, cr, rng):
    """Gather-free kernel == the dense oracle (engine.dense_routed_topk —
    the single routed reference, shared with the engine parity tier)."""
    from repro.core.engine import dense_routed_topk
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    be = jnp.asarray(rng.normal(size=(c, cap, d)), jnp.float32)
    bl = jnp.asarray(rng.uniform(size=(c, cap, 2)), jnp.float32)
    bi = jnp.asarray(np.arange(c * cap).reshape(c, cap), jnp.int32)
    tc = jnp.asarray(rng.integers(0, c, size=(b, cr)), jnp.int32)
    wh = jnp.asarray(np.cumsum(rng.uniform(0, 0.01, size=t)), jnp.float32)
    s1, i1 = ops.fused_topk_score_routed(q, ql, w, tc, be, bl, bi, wh,
                                         k=k, dist_max=1.414, block_n=64,
                                         interpret=True)
    s2, i2 = dense_routed_topk(q, ql, w, tc, be, bl, bi, wh,
                               k=k, dist_max=1.414)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    assert (np.sort(np.asarray(i1)) == np.sort(np.asarray(i2))).all()


def test_fused_topk_score_routed_tile_collapse_warns_but_correct(rng):
    """The cap-has-no-large-divisor fallback (prime cap ⇒ tiles collapse
    to 1): the warning must fire AND results must still match the dense
    oracle — a pathological grid is slow, never wrong."""
    import warnings
    from repro.core.engine import dense_routed_topk
    b, c, cap, d, t, k, cr = 3, 4, 127, 8, 20, 5, 2     # 127 is prime
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    be = jnp.asarray(rng.normal(size=(c, cap, d)), jnp.float32)
    bl = jnp.asarray(rng.uniform(size=(c, cap, 2)), jnp.float32)
    bi = jnp.asarray(np.arange(c * cap).reshape(c, cap), jnp.int32)
    tc = jnp.asarray(rng.integers(0, c, size=(b, cr)), jnp.int32)
    wh = jnp.asarray(np.cumsum(rng.uniform(0, 0.01, size=t)), jnp.float32)
    from repro.kernels import fused_topk_score as fts
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        s1, i1 = fts.fused_topk_score_routed(q, ql, w, tc, be, bl, bi, wh,
                                             k=k, dist_max=1.414,
                                             block_n=64, interpret=True)
    assert any("tiles collapsed" in str(w_.message) for w_ in caught)
    s2, i2 = dense_routed_topk(q, ql, w, tc, be, bl, bi, wh,
                               k=k, dist_max=1.414)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    assert (np.sort(np.asarray(i1)) == np.sort(np.asarray(i2))).all()


@pytest.mark.parametrize("b,c,cap,d,t,k,cr", [
    (8, 8, 256, 32, 50, 5, 1),
    (4, 6, 128, 64, 100, 10, 2),
])
def test_fused_topk_score_routed_int8_dequant(b, c, cap, d, t, k, cr, rng):
    """Dequant-in-kernel path (DESIGN.md §9): int8 resident buffers +
    per-row scales must match the dense oracle applying the SAME scales
    after its gather."""
    from repro.core import index as il
    from repro.core.engine import dense_routed_topk
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    emb = rng.normal(size=(c, cap, d)).astype(np.float32)
    q_emb8, scale = il.quantize_rows(emb, "int8")
    be = jnp.asarray(q_emb8)
    bs = jnp.asarray(scale)
    assert be.dtype == jnp.int8 and bs.shape == (c, cap)
    bl = jnp.asarray(rng.uniform(size=(c, cap, 2)), jnp.float32)
    bi = jnp.asarray(np.arange(c * cap).reshape(c, cap), jnp.int32)
    tc = jnp.asarray(rng.integers(0, c, size=(b, cr)), jnp.int32)
    wh = jnp.asarray(np.cumsum(rng.uniform(0, 0.01, size=t)), jnp.float32)
    s1, i1 = ops.fused_topk_score_routed(q, ql, w, tc, be, bl, bi, wh,
                                         k=k, dist_max=1.414, block_n=64,
                                         buf_scale=bs, interpret=True)
    s2, i2 = dense_routed_topk(q, ql, w, tc, be, bl, bi, wh,
                               k=k, dist_max=1.414, buf_scale=bs)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    assert (np.sort(np.asarray(i1)) == np.sort(np.asarray(i2))).all()


def test_quantize_rows_int8_bounds_error(rng):
    """Symmetric per-row scalar quantization: |emb − deq(q)| ≤ scale/2
    elementwise, padding (all-zero) rows get unit scales and stay exact."""
    from repro.core import index as il
    emb = rng.normal(size=(6, 32)).astype(np.float32)
    emb[2] = 0.0                                 # a padding row
    q, scale = il.quantize_rows(emb, "int8")
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert scale[2] == 1.0 and (q[2] == 0).all()
    deq = il.dequantize_rows(q, scale, "int8")
    assert (np.abs(deq - emb) <= scale[:, None] / 2 + 1e-7).all()


def test_fused_topk_masks_padding(rng):
    """A cluster that is all padding but for k slots: only those k
    objects can be selected."""
    b, n, d, t, k = 4, 512, 16, 20, 8
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.zeros((b, 2), jnp.float32)
    w = jnp.ones((b, 2), jnp.float32)
    be = jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32)
    bl = jnp.zeros((1, n, 2), jnp.float32)
    bi = jnp.full((1, n), -1, jnp.int32)          # everything is padding
    bi = bi.at[:, :k].set(jnp.arange(k))
    wh = jnp.asarray(np.linspace(0, 1, t), jnp.float32)
    s, i = ops.fused_topk_score_routed(q, ql, w, jnp.zeros((b, 1), jnp.int32),
                                       be, bl, bi, wh, k=k, dist_max=1.414,
                                       interpret=True)
    # only the k valid slots can be selected
    assert (np.asarray(i) < k).all() and (np.asarray(i) >= 0).all()


def _extent_buffers(rng, *, cap, d, precision, attrs):
    """Seven clusters of capacity ``cap`` whose live extents span every
    edge of a 128-row tile grid: full, empty, one row, mid-tile, on a
    tile boundary, interior holes (``delete_objects``), and live rows
    after a hole near the end (``counts`` 12, extent ``cap``)."""
    from repro.core import index as il
    fill = [cap, 0, 1, 200, 256, 400, cap]
    c = len(fill)
    ids = np.full((c, cap), -1, np.int32)
    for ci, n in enumerate(fill):
        ids[ci, :n] = ci * cap + np.arange(n)
    emb = rng.normal(size=(c, cap, d)).astype(np.float32)
    emb[ids < 0] = 0.0
    be, bs = il.quantize_rows(emb, precision)
    loc = rng.uniform(size=(c, cap, 2)).astype(np.float32)
    loc[ids < 0] = il.PAD_LOC
    att = np.stack([rng.integers(0, 2, (c, cap)), rng.integers(1, 4, (c, cap)),
                    rng.integers(0, 100, (c, cap))], -1).astype(np.int32)
    att[ids < 0] = 0
    buf = {"emb": jnp.asarray(be), "scale": jnp.asarray(bs),
           "loc": jnp.asarray(loc), "ids": jnp.asarray(ids),
           "attrs": jnp.asarray(att), "counts": jnp.asarray(fill)}
    holes = np.concatenate([5 * cap + np.arange(100, 300, 3),
                            6 * cap + np.arange(10, cap - 2)])
    buf = il.delete_objects(buf, holes)
    assert np.asarray(buf["counts"])[6] == 12
    return buf, (buf["attrs"] if attrs else None)


@pytest.mark.parametrize("kernel,precision,filtered,n_valid", [
    pytest.param("routed", "f32", False, None, id="routed-f32-False"),
    pytest.param("routed", "int8", False, None, id="routed-int8-False"),
    pytest.param("cluster_major", "f32", False, None,
                 id="cluster_major-f32-False"),
    pytest.param("cluster_major", "int8", False, None,
                 id="cluster_major-int8-False"),
    pytest.param("cluster_major", "f32", True, None,
                 id="cluster_major-f32-True"),
    pytest.param("routed", "f32", False, 5, id="routed-f32-padded"),
    pytest.param("routed", "int8", True, 3, id="routed-int8-filtered-padded"),
    pytest.param("cluster_major", "f32", False, 3,
                 id="cluster_major-f32-padded"),
    pytest.param("cluster_major", "int8", False, 5,
                 id="cluster_major-int8-padded"),
])
def test_scan_reads_only_live_extent(kernel, precision, filtered, n_valid,
                                     rng):
    """The kernels stream each cluster only up to its last live slot:
    every extent edge — empty, one row, mid-tile, on a tile boundary,
    full, interior holes, live rows after a hole near the end — and a
    cluster-major plan with empty rows equal the dense oracle. A scan
    bounded by ``counts`` would miss cluster 6's last two rows. With
    ``n_valid``, the batch rows from it on are padding: they stream no
    tile (routed: their answers are padding pairs) while the queries'
    answers stay the oracle's."""
    from repro.core import serving
    from repro.core.engine import dense_routed_topk, merge_cluster_major
    from repro.kernels import fused_topk_score as fts
    cap, d, k, bn = 512, 16, 20, 128
    buf, attrs = _extent_buffers(rng, cap=cap, d=d, precision=precision,
                                 attrs=filtered)
    top_c = jnp.asarray([[6, 1], [0, 2], [3, 4], [5, 6], [2, 3], [4, 0],
                         [1, 5], [6, 6]], jnp.int32)
    b, cr = top_c.shape
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ql = jnp.asarray(rng.uniform(size=(b, 2)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, 2)), jnp.float32)
    wh = jnp.asarray(np.cumsum(rng.uniform(0, 0.01, size=50)), jnp.float32)
    q_filt = (jnp.asarray([[1, 0, -2 ** 31, 2 ** 31 - 1],
                           [-1, 2, 10, 90]] * (b // 2), jnp.int32)
              if filtered else None)
    scale = buf["scale"] if precision == "int8" else None
    common = dict(k=k, dist_max=1.414, block_n=bn, buf_scale=scale,
                  buf_attrs=attrs, interpret=True)
    extent_tiles = -(-np.array([cap, 0, 1, 200, 256, 400, cap]) // bn)
    nv = b if n_valid is None else n_valid
    if kernel == "routed":
        s1, i1 = fts.fused_topk_score_routed(
            q, ql, w, top_c, buf["emb"], buf["loc"], buf["ids"], wh,
            q_filt=q_filt, n_valid=n_valid, **common)
        tiles, per_row = fts.routed_tiles(buf["ids"], top_c, block_n=bn,
                                          n_valid=n_valid)
        want = np.where(np.arange(b)[:, None] < nv,
                        extent_tiles[np.asarray(top_c)], 0)
        assert (np.asarray(s1)[nv:] == fts.NEG_INF).all()
        assert (np.asarray(i1)[nv:] == -1).all()
    else:
        n = b * cr
        u, roster, _, _ = serving.cluster_major_plan(top_c, n_clusters=7,
                                                     qcap=4)
        assert (np.asarray(roster) == n).all(axis=1).sum() == 3
        qi = serving.roster_query_rows(roster, cr=cr, n_total=n)
        n_live = None if n_valid is None else n_valid * cr
        ps, pi = fts.fused_topk_score_cluster_major(
            q[qi], ql[qi], w[qi], u, roster, buf["emb"], buf["loc"],
            buf["ids"], wh, n_total=n, n_live=n_live,
            q_filt_r=None if q_filt is None else q_filt[qi], **common)
        s1, i1 = merge_cluster_major(ps, pi, roster, b=b, cr=cr, k=k)
        tiles, per_row = fts.cluster_major_tiles(
            buf["ids"], u, roster, n_live=nv * cr, block_n=bn)
        serves = (np.asarray(roster) < nv * cr).any(axis=1)
        want = np.where(serves, extent_tiles[np.asarray(u)], 0)
        if n_valid == 3:               # cluster 5 serves padding rows only
            assert not serves[np.asarray(u) == 5].any()
    assert per_row == cap // bn
    assert (np.asarray(tiles) == want).all()
    s2, i2 = dense_routed_topk(q, ql, w, top_c, buf["emb"], buf["loc"],
                               buf["ids"], wh, k=k, dist_max=1.414,
                               buf_scale=scale, buf_attrs=attrs,
                               q_filt=q_filt)
    np.testing.assert_allclose(np.asarray(s1)[:nv], np.asarray(s2)[:nv],
                               rtol=1e-5, atol=1e-5)
    assert (np.sort(np.asarray(i1)[:nv]) == np.sort(np.asarray(i2)[:nv])).all()
    if not filtered:                   # query 0 sees all of cluster 6
        assert {6 * cap + cap - 2, 6 * cap + cap - 1} <= set(
            np.asarray(i1)[0].tolist())


@pytest.mark.parametrize("b,s,h,kv,d,causal,window", [
    (2, 256, 4, 2, 32, True, 0),
    (1, 128, 4, 4, 64, True, 64),
    (2, 200, 2, 1, 16, True, 0),          # non-multiple seq (padding path)
    (1, 256, 8, 2, 32, True, 100),        # window not multiple of block
    (1, 64, 2, 2, 32, False, 0),
])
def test_flash_attention(b, s, h, kv, d, causal, window, rng):
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=64, block_k=64, interpret=True)
    o2 = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.bfloat16)
    o1 = ops.flash_attention(q, k, v, interpret=True)
    o2 = ref.flash_attention_ref(q, k, v)
    err = np.abs(np.asarray(o1, np.float32) - np.asarray(o2, np.float32))
    assert err.max() < 2e-2


def test_flash_matches_layers_oracle(rng):
    """The kernel also matches the model's chunked-attention path."""
    from repro.models import layers
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=True, interpret=True)
    o2 = layers.attention_full(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,f,d", [(128, 27, 16), (256, 27, 128), (64, 8, 8)])
def test_dot_interaction(b, f, d, rng):
    x = jnp.asarray(rng.normal(size=(b, f, d)), jnp.float32)
    o1 = ops.dot_interaction(x, block_m=64, interpret=True)
    o2 = ref.dot_interaction_ref(x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)
    # matches the model's implementation too
    from repro.models.recsys import dlrm_dot_interaction
    o3 = dlrm_dot_interaction(x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("v,d,b,p,block_v", [
    (1000, 32, 128, 8, 256),
    (500, 16, 64, 4, 512),     # block_v > v (single tile)
    (4096, 64, 256, 16, 512),
])
def test_embedding_bag(v, d, b, p, block_v, rng):
    tab = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(-1, v, size=(b, p)), jnp.int32)
    o1 = ops.embedding_bag(tab, idx, block_v=block_v, interpret=True)
    o2 = ref.embedding_bag_ref(tab, idx)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


def test_embedding_bag_duplicate_indices(rng):
    tab = jnp.asarray(rng.normal(size=(100, 8)), jnp.float32)
    idx = jnp.asarray([[3, 3, 3, -1]], jnp.int32)
    idx = jnp.tile(idx, (8, 1))
    o = ops.embedding_bag(tab, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(o)[0], 3 * np.asarray(tab)[3],
                               rtol=1e-5)
