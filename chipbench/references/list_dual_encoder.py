"""Plain float32 reference of the LIST query phase (arXiv:2403.07331)
with its BERT query tower.

What the configuration describes, written out in ``jax.numpy`` with no
kernel, cache, batching or program code:

* query tower: token + position embeddings, ``n_layers`` pre-LN
  encoder blocks (multi-head self-attention over the real tokens, then a
  tanh-GELU feed-forward), a final LayerNorm and a tanh dense head on the
  CLS position (the paper's BERT tower, pre-LN as the configuration
  states);
* the LIST head (mixing weights, router, score): ``list_head``, which
  this module re-exports.

Every matmul runs at ``Precision.HIGHEST``. ``precision="fp8"`` (the
control) rounds both operands of every tower matmul to float8 e4m3 with a
per-tensor scale first.

The weights are made here, on the device, from a key: every dense layer
``N(0, 1/fan_in)``, biases ``N(0, 0.02)``, LayerNorm gains
``1 + N(0, 0.1)`` and offsets ``N(0, 0.02)``, token embeddings
``N(0, 1/d)``, position embeddings ``N(0, 0.02)``; the head's as
``list_head`` draws them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import list_head
from chipbench.references.list_head import (  # noqa: F401 - the contract
    mixing_weights, router_logits, score, step_table)

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _norm(key, d):
    kg, kb = jax.random.split(key)
    return (1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32),
            0.02 * jax.random.normal(kb, (d,), jnp.float32))


def _layer(key, d, d_ff):
    ks = jax.random.split(key, 8)
    p = {}
    for name, k in zip(("q", "k", "v", "o"), ks[:4]):
        p["w" + name], p["b" + name] = list_head.dense(k, d, d)
    p["w1"], p["b1"] = list_head.dense(ks[4], d, d_ff)
    p["w2"], p["b2"] = list_head.dense(ks[5], d_ff, d)
    p["ln1_g"], p["ln1_b"] = _norm(ks[6], d)
    p["ln2_g"], p["ln2_b"] = _norm(ks[7], d)
    return p


def init_weights(key, cfg):
    """All weights of the query phase from one key (call under jit)."""
    d = cfg["d_model"]
    k = jax.random.split(key, 8)
    layers = jax.vmap(lambda kk: _layer(kk, d, cfg["d_ff"]))(
        jax.random.split(k[0], cfg["n_layers"]))
    lnf_g, lnf_b = _norm(k[3], d)
    cls_w, cls_b = list_head.dense(k[4], d, d)
    return {
        "tok_emb": jax.random.normal(k[1], (cfg["vocab_size"], d),
                                     jnp.float32) / math.sqrt(d),
        "pos_emb": 0.02 * jax.random.normal(k[2], (cfg["max_len"], d),
                                            jnp.float32),
        "layers": layers, "lnf_g": lnf_g, "lnf_b": lnf_b,
        "cls_w": cls_w, "cls_b": cls_b,
        **list_head.init_weights(k[5], k[6], k[7], cfg, d),
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


def calibrate_router(w, tokens, mask, q_loc, cfg):
    """``w`` with its router calibrated over queries this tower encodes
    (``list_head.calibrate_router``)."""
    return list_head.calibrate_router(w, tokens, mask, q_loc, cfg, encode)


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------


def tower_dense_flops_per_token(cfg):
    """Projection and feed-forward FLOPs of one token through the tower:
    per layer ``2 * (4 d^2 + 2 d d_ff)``."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return 2 * (4 * d * d + 2 * d * f) * cfg["n_layers"]


def query_flops(cfg, lengths):
    """FLOPs one query of ``lengths`` real tokens needs before the scan:
    the tower's projections and feed-forward per token, attention scores
    and values (``4 n d`` per token per layer), and the head (the CLS
    dense, the mixing MLP and the router MLP). ``lengths`` may be an
    array (summed)."""
    n = np.asarray(lengths, np.float64)
    d, layers = cfg["d_model"], cfg["n_layers"]
    tower = n * tower_dense_flops_per_token(cfg) + 4.0 * n * n * d * layers
    return float(np.sum(tower + list_head.flops_per_query(cfg, d, d)))
