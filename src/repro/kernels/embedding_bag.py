"""Vocab-tiled EmbeddingBag: multi-hot pooled lookup as MXU one-hot matmuls.

Regime note (DESIGN.md §4): this kernel targets *hash-bucketed / small-vocab*
tables (V up to a few 10k), where streaming the table through VMEM in
(block_v, d) tiles and accumulating ``onehot(idx ∈ tile) @ tile`` on the MXU
beats a host of scalar gathers — the standard TPU trick for pooled sparse
lookups without SparseCore. For the 40M-row DLRM tables the models use the
XLA-native gather (``jnp.take`` + ``segment_sum`` in models/recsys.py),
which GSPMD shards row-parallel; that path is the production default.

Inputs use the fixed multi-hot layout: idx (B, P) int32 per-bag pooled
indices, padded with -1 (weight 0).

Grid: (B/block_m, V/block_v), v innermost; the output block accumulates
partial pools across vocab tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(idx_ref, tab_ref, o_ref, *, block_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = idx_ref[...]                                  # (bm, P)
    v_start = j * block_v
    local = idx - v_start                               # position in tile
    in_tile = (local >= 0) & (local < block_v) & (idx >= 0)
    # multi-hot over the tile: (bm, block_v)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_v,), 0)
    onehot = (local[..., None] == iota[None, None, :]) & in_tile[..., None]
    counts = onehot.sum(axis=1).astype(jnp.float32)     # (bm, block_v)
    tab = tab_ref[...].astype(jnp.float32)              # (block_v, d)
    o_ref[...] += jax.lax.dot_general(
        counts, tab, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def embedding_bag(table, idx, *, block_m: int = 256, block_v: int = 512,
                  interpret: bool):
    """table: (V, d); idx: (B, P) int32, -1 padded → pooled sums (B, d)."""
    v, d = table.shape
    b, p = idx.shape
    block_m = min(block_m, b)
    block_v = min(block_v, v)
    pad_v = (-v) % block_v
    tab = jnp.pad(table, ((0, pad_v), (0, 0)))
    assert b % block_m == 0
    grid = (b // block_m, (v + pad_v) // block_v)
    return pl.pallas_call(
        functools.partial(_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, p), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(idx, tab)
