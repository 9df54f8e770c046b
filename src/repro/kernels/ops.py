"""Jit'd dispatch wrappers for the Pallas kernels.

``interpret=None`` follows the platform: compiled with Mosaic on a TPU,
the Pallas interpreter everywhere else (correctness only). The same rule
backs core/engine.default_interpret so every entry point agrees.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import (
    dot_interaction as _di,
    embedding_bag as _eb,
    flash_attention as _fa,
    fused_topk_score as _fts,
)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("k", "dist_max", "block_n",
                                             "interpret"))
def fused_topk_score_routed(q_emb, q_loc, w_st, top_c, buf_emb, buf_loc,
                            buf_ids, w_hat, *, k, dist_max, block_n=512,
                            buf_scale=None, interpret=None):
    """Gather-free query-phase kernel: scalar-prefetched cluster routing.
    ``buf_scale (c, cap)`` enables the dequant-in-kernel path for int8
    resident buffers (DESIGN.md §9)."""
    interpret = _interpret_default() if interpret is None else interpret
    return _fts.fused_topk_score_routed(
        q_emb, q_loc, w_st, top_c, buf_emb, buf_loc, buf_ids, w_hat, k=k,
        dist_max=dist_max, block_n=block_n, buf_scale=buf_scale,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "dist_max", "n_total",
                                             "block_n", "interpret"))
def fused_topk_score_cluster_major(q_emb_r, q_loc_r, w_st_r, u, roster,
                                   buf_emb, buf_loc, buf_ids, w_hat, *, k,
                                   dist_max, n_total, block_n=512,
                                   buf_scale=None, interpret=None):
    """Cluster-major query-phase kernel: stream each distinct routed
    cluster once per batch against its whole query roster (DESIGN.md
    §10). Inputs/outputs per the kernel docstring — fold the returned
    per-roster-slot partial top-k lists with
    ``engine.merge_cluster_major``."""
    interpret = _interpret_default() if interpret is None else interpret
    return _fts.fused_topk_score_cluster_major(
        q_emb_r, q_loc_r, w_st_r, u, roster, buf_emb, buf_loc, buf_ids,
        w_hat, k=k, dist_max=dist_max, n_total=n_total, block_n=block_n,
        buf_scale=buf_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def dot_interaction(feats, *, block_m=128, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _di.dot_interaction(feats, block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_v",
                                             "interpret"))
def embedding_bag(table, idx, *, block_m=256, block_v=512, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _eb.embedding_bag(table, idx, block_m=block_m, block_v=block_v,
                             interpret=interpret)
