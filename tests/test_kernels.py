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
