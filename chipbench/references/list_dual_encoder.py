"""Plain float32 reference of the LIST query phase (arXiv:2403.07331).

What the configuration describes, written out in ``jax.numpy`` with no
kernel, cache, batching or program code:

* query tower: token + position embeddings, ``n_layers`` pre-LN
  encoder blocks (multi-head self-attention over the real tokens, then a
  tanh-GELU feed-forward), a final LayerNorm and a tanh dense head on the
  CLS position (the paper's BERT tower, pre-LN as the configuration
  states);
* mixing weights (Eq. 6): ``softplus(MLP(q))`` with one ReLU hidden layer;
* router (Eq. 9-11): an MLP with ReLU hidden layers over
  ``[q / |q|, lat, lon]`` whose logits rank the clusters;
* score (Eq. 5): ``w_t (q . o) + w_s ŵ[floor(S_in t)]`` with
  ``S_in = 1 - clip(dist / dist_max, 0, 1)`` and ``ŵ`` the cumulative sum
  of ``softplus`` of the step increments.

Every matmul runs at ``Precision.HIGHEST``. ``precision="fp8"`` (the
control) rounds both operands of every tower matmul to float8 e4m3 with a
per-tensor scale first.

The weights are made here, on the device, from a key: every dense layer
``N(0, 1/fan_in)``, biases ``N(0, 0.02)``, LayerNorm gains
``1 + N(0, 0.1)`` and offsets ``N(0, 0.02)``, token embeddings
``N(0, 1/d)``, position embeddings ``N(0, 0.02)``, step increments
``-2 + N(0, 0.01)``. A random tower maps every query to nearly the same
direction, so a random router sends almost every query to a handful of
clusters; a trained LIST router spreads them over its balanced clusters.
``calibrate_router`` stands in for that training: over a seeded sample
of queries it standardizes the router's input features (the first
layer sees each feature at zero mean and unit variance) and centres
each cluster's logit, so routes spread over the clusters as the
queries' own features differ.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _dense(key, n_in, n_out):
    kw, kb = jax.random.split(key)
    return (jax.random.normal(kw, (n_in, n_out), jnp.float32)
            / math.sqrt(n_in),
            0.02 * jax.random.normal(kb, (n_out,), jnp.float32))


def _norm(key, d):
    kg, kb = jax.random.split(key)
    return (1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32),
            0.02 * jax.random.normal(kb, (d,), jnp.float32))


def _layer(key, d, d_ff):
    ks = jax.random.split(key, 8)
    p = {}
    for name, k in zip(("q", "k", "v", "o"), ks[:4]):
        p["w" + name], p["b" + name] = _dense(k, d, d)
    p["w1"], p["b1"] = _dense(ks[4], d, d_ff)
    p["w2"], p["b2"] = _dense(ks[5], d_ff, d)
    p["ln1_g"], p["ln1_b"] = _norm(ks[6], d)
    p["ln2_g"], p["ln2_b"] = _norm(ks[7], d)
    return p


def _mlp(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [_dense(k, a, b) for k, a, b in zip(keys, dims[:-1], dims[1:])]


def init_weights(key, cfg):
    """All weights of the query phase from one key (call under jit)."""
    d = cfg["d_model"]
    k = jax.random.split(key, 8)
    layers = jax.vmap(lambda kk: _layer(kk, d, cfg["d_ff"]))(
        jax.random.split(k[0], cfg["n_layers"]))
    lnf_g, lnf_b = _norm(k[3], d)
    cls_w, cls_b = _dense(k[4], d, d)
    return {
        "tok_emb": jax.random.normal(k[1], (cfg["vocab_size"], d),
                                     jnp.float32) / math.sqrt(d),
        "pos_emb": 0.02 * jax.random.normal(k[2], (cfg["max_len"], d),
                                            jnp.float32),
        "layers": layers, "lnf_g": lnf_g, "lnf_b": lnf_b,
        "cls_w": cls_w, "cls_b": cls_b,
        "weight_mlp": _mlp(k[5], (d, cfg["weight_mlp_hidden"], 2)),
        "router": _mlp(k[6], (d + 2, *cfg["index_mlp_hidden"],
                              cfg["n_clusters"])),
        "w_s": -2.0 + 0.01 * jax.random.normal(k[7], (cfg["spatial_t"],),
                                               jnp.float32),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def round_fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale, as f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = round_fp8(a), round_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def encode(w, tokens, mask, cfg, precision="f32"):
    """Query tower: tokens (B, L) int32, mask (B, L) bool → (B, d) f32."""
    b, length = tokens.shape
    h_n = cfg["n_heads"]
    hd = cfg["d_model"] // h_n
    eps = cfg["norm_eps"]
    x = w["tok_emb"][tokens] + w["pos_emb"][:length][None]

    def block(x, p):
        h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        q, k, v = (( _mm("bld,de->ble", h, p["w" + n], precision)
                    + p["b" + n]).reshape(b, length, h_n, hd)
                   for n in ("q", "k", "v"))
        s = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = _mm("bhqk,bkhd->bqhd", a, v, precision).reshape(b, length, -1)
        x = x + _mm("bld,de->ble", o, p["wo"], precision) + p["bo"]
        h = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        m = _gelu(_mm("bld,df->blf", h, p["w1"], precision) + p["b1"])
        return x + _mm("blf,fd->bld", m, p["w2"], precision) + p["b2"], None

    x, _ = jax.lax.scan(block, x, w["layers"])
    x = _layer_norm(x, w["lnf_g"], w["lnf_b"], eps)
    return jnp.tanh(_mm("bd,de->be", x[:, 0], w["cls_w"], precision)
                    + w["cls_b"])


def _mlp_apply(layers, x):
    for i, (wi, bi) in enumerate(layers):
        x = jnp.matmul(x, wi, precision=HIGHEST) + bi
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def mixing_weights(w, q):
    """Eq. 6: (B, d) → (B, 2) positive (textual, spatial) weights."""
    return jax.nn.softplus(_mlp_apply(w["weight_mlp"], q))


def router_logits(w, q, q_loc):
    """Eq. 9-11: (B, d), (B, 2) in the unit box → (B, c) cluster logits."""
    e = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    return _mlp_apply(w["router"], jnp.concatenate([e, q_loc], -1))


def calibrate_router(w, tokens, mask, q_loc, cfg):
    """``w`` with its router standardized and centred over a sample of
    queries (see the module docstring)."""
    q = encode(w, tokens, mask, cfg)
    e = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    x = jnp.concatenate([e, q_loc], -1)
    mu, sd = x.mean(0), x.std(0) + 1e-6
    (w0, b0), *rest = w["router"]
    router = [(w0 / sd[:, None],
               b0 - jnp.matmul(mu / sd, w0, precision=HIGHEST)), *rest]
    logits = _mlp_apply(router, x)
    wl, bl = router[-1]
    router[-1] = (wl, bl - logits.mean(0))
    return {**w, "router": router}


def step_table(w):
    return jnp.cumsum(jax.nn.softplus(w["w_s"]))


def score(q, q_loc, mix, emb, loc, table, dist_max):
    """Eq. 5 for every (query, row): q (B, d), q_loc (B, 2), mix (B, 2)
    against emb (..., N, d) f32 and loc (..., N, 2) → (B, ..., N)."""
    trel = jnp.einsum("bd,...nd->b...n", q, emb, precision=HIGHEST)
    diff = q_loc.reshape((q.shape[0],) + (1,) * (loc.ndim - 1) + (2,)) - loc
    dist = jnp.sqrt(jnp.sum(diff * diff, -1))
    s_in = 1.0 - jnp.clip(dist / dist_max, 0.0, 1.0)
    t = table.shape[0]
    srel = table[jnp.clip(jnp.floor(s_in * t).astype(jnp.int32), 0, t - 1)]
    shape = (q.shape[0],) + (1,) * (trel.ndim - 1)
    return mix[:, 0].reshape(shape) * trel + mix[:, 1].reshape(shape) * srel
