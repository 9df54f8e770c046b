"""The LIST head (arXiv:2403.07331), the part of the query phase that
follows the query tower, whatever the tower is.

Given the tower's query embedding ``q`` (B, d) in float32:

* mixing weights (Eq. 6): ``softplus(MLP(q))`` with one ReLU hidden layer;
* router (Eq. 9-11): an MLP with ReLU hidden layers over
  ``[q / |q|, lat, lon]`` whose logits rank the clusters;
* score (Eq. 5): ``w_t (q . o) + w_s ŵ[floor(S_in t)]`` with
  ``S_in = 1 - clip(dist / dist_max, 0, 1)`` and ``ŵ`` the cumulative sum
  of ``softplus`` of the step increments.

Every matmul runs at ``Precision.HIGHEST``. The head reads
``weight_mlp_hidden``, ``index_mlp_hidden``, ``n_clusters`` and
``spatial_t`` from the configuration's ``model`` block, and takes the
query width ``d`` from the tower's reference.

Weights: every dense layer ``N(0, 1/fan_in)``, biases ``N(0, 0.02)``,
step increments ``-2 + N(0, 0.01)``. A random tower maps every query to
nearly the same direction, so a random router sends almost every query
to a handful of clusters; a trained LIST router spreads them over its
balanced clusters. ``calibrate_router`` stands in for that training:
over a seeded sample of queries it standardizes the router's input
features (the first layer sees each feature at zero mean and unit
variance) and centres each cluster's logit, so routes spread over the
clusters as the queries' own features differ.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dense(key, n_in, n_out):
    """One dense layer's (weight, bias) drawn from ``key``."""
    kw, kb = jax.random.split(key)
    return (jax.random.normal(kw, (n_in, n_out), jnp.float32)
            / math.sqrt(n_in),
            0.02 * jax.random.normal(kb, (n_out,), jnp.float32))


def _mlp(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [dense(k, a, b) for k, a, b in zip(keys, dims[:-1], dims[1:])]


def init_weights(k_mix, k_router, k_step, cfg, d):
    """The head's weights for queries of width ``d``, one key each for
    the mixing MLP, the router MLP and the step increments."""
    return {
        "weight_mlp": _mlp(k_mix, (d, cfg["weight_mlp_hidden"], 2)),
        "router": _mlp(k_router, (d + 2, *cfg["index_mlp_hidden"],
                                  cfg["n_clusters"])),
        "w_s": -2.0 + 0.01 * jax.random.normal(k_step, (cfg["spatial_t"],),
                                               jnp.float32),
    }


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


def calibrate_router(w, tokens, mask, q_loc, cfg, encode):
    """``w`` with its router standardized and centred over a sample of
    queries, each embedded by the tower's ``encode`` (see the module
    docstring)."""
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


def flops_per_query(cfg, d, d_pooled):
    """FLOPs of one query's head: the projection of the tower's pooled
    state (width ``d_pooled``) to the query (width ``d``), the mixing MLP
    and the router MLP."""
    projection = 2.0 * d_pooled * d
    mix = 2.0 * (d * cfg["weight_mlp_hidden"] + cfg["weight_mlp_hidden"] * 2)
    dims = (d + 2, *cfg["index_mlp_hidden"], cfg["n_clusters"])
    router = 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return projection + mix + router
