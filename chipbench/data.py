"""The index a configuration describes, drawn on the device from the seed.

The object tower is not run: each object's embedding is drawn
``N(0, 1)`` at width ``d``, and its location uniform in the unit box.
The cluster buffers are drawn directly in the packed layout the engine
serves, ``(c, cap, ...)``, a few clusters per loop step inside one
jitted call, so no float32 copy of the index ever exists:

* cluster ``j`` holds ``counts[j]`` objects in slots ``[0, counts[j])``,
  with global ids ``offsets[j] + slot``; padding slots have id -1,
  embedding 0, scale 1 and location ``PAD_LOC``;
* bf16 rows are the drawn values rounded to bfloat16; int8 rows are the
  drawn values under a symmetric per-row scale ``max|row| / 127``.

The per-cluster fill is a fixed set of counts (the same for every seed,
so every seed streams the same work) put in a seeded order.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

PAD_LOC = 1e6           # location of a padding slot, far outside the box
STORAGE = {"bf16": jnp.bfloat16, "int8": jnp.int8}
_BLOCK_BYTES = 1 << 29  # f32 bytes of one loop step's draw


def fill_counts(index_cfg, rng):
    """Objects per cluster: a fixed template, in a seeded order.

    The template spreads the fill uniformly over ``mean * (1 ± spread)``
    (``index_cfg["fill_spread"]``) and then shifts single objects until
    the counts sum to ``n_objects``."""
    n, c = index_cfg["n_objects"], index_cfg["n_clusters"]
    cap = index_cfg["capacity"]
    mean = n / c
    u = np.random.default_rng(0).uniform(-1.0, 1.0, c)
    counts = np.floor(mean * (1.0 + index_cfg["fill_spread"] * u))
    counts = np.clip(counts, 1, cap).astype(np.int64)
    i = 0
    while counts.sum() != n:
        step = 1 if counts.sum() < n else -1
        j = i % c
        if 1 <= counts[j] + step <= cap:
            counts[j] += step
        i += 1
    return rng.permutation(counts)


def offsets_of(counts):
    return np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)


def cluster_of(ids, offsets):
    """Cluster and slot of each global object id (ids >= 0)."""
    ids = np.asarray(ids, np.int64)
    cl = np.searchsorted(offsets, ids, side="right") - 1
    return cl, ids - offsets[cl]


def _block_clusters(c, cap, d):
    """Clusters drawn per loop step: the largest divisor of ``c`` whose
    float32 draw stays under ``_BLOCK_BYTES``."""
    most = max(1, _BLOCK_BYTES // (cap * d * 4))
    return max(b for b in range(1, min(c, most) + 1) if c % b == 0)


@functools.partial(jax.jit, static_argnames=("cap", "d", "precision"))
def _draw(key, counts, offsets, *, cap, d, precision):
    c = counts.shape[0]
    nb = _block_clusters(c, cap, d)
    slot = jnp.arange(cap, dtype=jnp.int32)
    valid = slot[None, :] < counts[:, None]                       # (c, cap)
    ids = jnp.where(valid, offsets[:, None] + slot[None, :], -1)
    kl, ke = jax.random.split(key)
    loc = jax.random.uniform(kl, (c, cap, 2), jnp.float32)
    loc = jnp.where(valid[..., None], loc, PAD_LOC)

    def body(i, carry):
        emb, scale = carry
        x = jax.random.normal(jax.random.fold_in(ke, i), (nb, cap, d),
                              jnp.float32)
        ok = jax.lax.dynamic_slice_in_dim(valid, i * nb, nb)
        x = jnp.where(ok[..., None], x, 0.0)
        if precision == "int8":
            amax = jnp.max(jnp.abs(x), axis=-1)
            s = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(x / s[..., None]), -127, 127)
            q = q.astype(jnp.int8)
        else:
            s = jnp.ones((nb, cap), jnp.float32)
            q = x.astype(STORAGE[precision])
        emb = jax.lax.dynamic_update_slice_in_dim(emb, q, i * nb, 0)
        scale = jax.lax.dynamic_update_slice_in_dim(scale, s, i * nb, 0)
        return emb, scale

    emb = jnp.zeros((c, cap, d), STORAGE[precision])
    scale = jnp.ones((c, cap), jnp.float32)
    emb, scale = jax.lax.fori_loop(0, c // nb, body, (emb, scale))
    return emb, scale, loc, ids


def draw_index(key, index_cfg, counts):
    """→ the buffer dict ``IndexSnapshot.from_parts`` takes (the keys of
    ``index.build_cluster_buffers``), on the default device."""
    c, cap = index_cfg["n_clusters"], index_cfg["capacity"]
    precision = index_cfg["precision"]
    emb, scale, loc, ids = _draw(
        key, jnp.asarray(counts, jnp.int32),
        jnp.asarray(offsets_of(counts), jnp.int32),
        cap=cap, d=index_cfg["d"], precision=precision)
    return {"emb": emb, "loc": loc, "ids": ids,
            "counts": jnp.asarray(counts, jnp.int32), "scale": scale,
            "attrs": jnp.zeros((c, cap, 3), jnp.int32),
            "n_spilled": 0, "capacity": cap, "precision": precision}


def dequantized(emb, scale, precision):
    """Stored rows as float32 (the values every reader must score)."""
    x = emb.astype(jnp.float32)
    return x * scale[..., None] if precision == "int8" else x
