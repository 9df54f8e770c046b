"""DLRM pairwise-dot feature interaction: (B, F, d) → (B, F(F-1)/2).

The Pallas kernel computes the batched Gram matrix G = X Xᵀ — one MXU
batched matmul per batch tile, fp32 accumulation. The static upper-triangle
compaction (a compile-time-constant shuffle) happens OUTSIDE the kernel in
plain XLA: Pallas forbids captured constant index arrays, and a fixed
gather is XLA's bread and butter anyway — it fuses with the downstream
top-MLP concat. The kernel owns the FLOPs; XLA owns the layout shuffle.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (bm, F, d)
    g = jax.lax.dot_general(
        x, x, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # (bm, F, F)
    o_ref[...] = g.astype(o_ref.dtype)


def dot_interaction(feats, *, interpret: bool, block_m: int = 128):
    """feats: (B, F, d) → (B, F(F-1)/2) upper-triangle pairwise dots."""
    b, f, d = feats.shape
    block_m = min(block_m, b)
    assert b % block_m == 0
    gram = pl.pallas_call(
        _kernel,
        grid=(b // block_m,),
        in_specs=[pl.BlockSpec((block_m, f, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_m, f, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, f), feats.dtype),
        interpret=interpret,
    )(feats)
    iu, ju = np.triu_indices(f, k=1)
    return gram[:, iu, ju]
