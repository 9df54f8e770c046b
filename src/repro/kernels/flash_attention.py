"""Flash attention (block-wise online softmax) for train/prefill paths.

Contract matches ``layers.attention_full``: causal (+ optional sliding
window), GQA via a group-size fold in the index maps. fp32 accumulation,
inputs any float dtype.

Grid: (B·H, S_q/block_q, S_k/block_k), k innermost. Running (m, l, acc)
live in VMEM scratch; the output block is written on the last k step.
Fully-masked k blocks (causal/window) are skipped with ``pl.when`` — this
is what makes the sliding-window cells sub-quadratic on the dry-run HLO.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            window: int, seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    # block-level mask culling: block is live iff some (q, k) pair in it
    # satisfies k <= q (causal) and q - k < window.
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window > 0:
        # earliest q in block vs latest k in block must be inside the window
        live = jnp.logical_and(
            live, q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale             # (bq, d)
        kk = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos_k < seq_k
        if causal:
            mask &= pos_q >= pos_k
        if window > 0:
            mask &= (pos_q - pos_k) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool):
    """q: (B, S, H, D); k, v: (B, S, KV, D). Returns (B, S, H, D).

    H = KV · G. Sequences are padded to block multiples internally.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    g = h // n_kv
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k

    # fold (B, S, H, D) -> (B·H, S, D) so one grid axis covers batch×head
    qf = jnp.moveaxis(qp, 2, 1).reshape(b * h, sqp, d)
    kf = jnp.moveaxis(kp, 2, 1).reshape(b * n_kv, skp, d)
    vf = jnp.moveaxis(vp, 2, 1).reshape(b * n_kv, skp, d)

    grid = (b * h, sqp // block_q, skp // block_k)
    kern = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_k=sk)

    def kv_map(hh, i, j):
        # head hh of q maps to kv head hh//g within its batch
        return ((hh // (h)) * n_kv + (hh % h) // g, j, 0)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, sqp, d)[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)
