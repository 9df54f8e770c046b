"""Token-choice top-k MoE with sort-based (gather/scatter) dispatch.

Design (DESIGN.md §5): no one-hot dispatch einsums — those cost T·E·C·d MACs
of pure overhead and wreck the compute roofline. Instead, per routing group:

  1. router top-k → (T, k) expert ids + renormalized weights
  2. stable sort of the T·k assignments by expert id
  3. position-in-expert from run starts (cummax trick) → capacity mask
  4. scatter token slots into a (E, C) index table
  5. gather token activations → (E, C, d), 3 GEMMs per expert (SwiGLU)
  6. scatter-add back weighted by router prob

Expert weights are sharded E→"model" (expert parallel) and d_ff→"data"
(FSDP); the (G, E, C, d) dispatch buffer is sharded (data, model) so each
chip gathers only its experts' slots. Routing groups are sequences for
train/prefill and the whole batch for decode (S==1), keeping per-group
capacity C = ceil(T_g·k/E·cf) small and drops rare.

Aux losses: Switch load-balance loss + router z-loss, returned to the caller.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers


def moe_init(key, d_model, spec, *, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, f = spec.n_experts, spec.d_ff_expert
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(f)
    return {
        "router": layers._normal(k1, (d_model, e), s_in, jnp.float32),
        "w1": layers._normal(k2, (e, d_model, f), s_in, dtype),
        "w3": layers._normal(k3, (e, d_model, f), s_in, dtype),
        "w2": layers._normal(k4, (e, f, d_model), s_out, dtype),
    }


def capacity(tokens_per_group: int, spec) -> int:
    c = math.ceil(tokens_per_group * spec.top_k / spec.n_experts
                  * spec.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8, floor 8


def _positions_in_expert(sorted_ids):
    """sorted_ids: (G, N) expert id per sorted slot → position within its run."""
    n = sorted_ids.shape[-1]
    ar = jnp.arange(n)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones_like(sorted_ids[:, :1], bool),
         sorted_ids[:, 1:] != sorted_ids[:, :-1]], axis=-1)
    run_start = jax.lax.cummax(jnp.where(is_start, ar, 0), axis=1)
    return ar - run_start


def route(params, x, spec):
    """x: (G, T, d) → (expert_ids (G,T,k), weights (G,T,k), aux metrics)."""
    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (G,T,E)
    top_p, top_i = jax.lax.top_k(probs, spec.top_k)            # (G,T,k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * Σ_e fraction_tokens(e)·mean_prob(e)
    e = spec.n_experts
    frac = jnp.mean(
        (jax.nn.one_hot(top_i[..., 0], e)), axis=(0, 1))       # top-1 fraction
    mean_p = jnp.mean(probs, axis=(0, 1))
    lb_loss = e * jnp.sum(frac * mean_p)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_i, top_p, {"lb_loss": lb_loss, "z_loss": z_loss}


def moe_apply(params, x, spec, *, group="seq", dp_axes=("data",),
              ep_axis="model"):
    """x: (B, S, d). Returns (out (B, S, d), aux dict).

    Dispatches to the shard_map implementation when a production mesh is
    bound (launch/dryrun): GSPMD cannot infer that the batched dispatch
    gather/scatter is group-local and falls back to full replication —
    measured at ~22 TB of wire per kimi train step (EXPERIMENTS.md §Perf).
    The shard_map path keeps dispatch local and pays exactly one psum
    (combine) + one FSDP weight all-gather per layer.
    """
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    mesh = rules.get("_mesh") if rules else None
    if mesh is not None and "model" in mesh.axis_names:
        return _moe_apply_shard_map(params, x, spec, mesh, group=group)
    return _moe_apply_local(params, x, spec, group=group, dp_axes=dp_axes,
                            ep_axis=ep_axis)


def _moe_apply_local(params, x, spec, *, group="seq", dp_axes=("data",),
                     ep_axis="model"):
    """Single-host / GSPMD path (smoke tests, CPU training)."""
    b, s, d = x.shape
    if group == "seq" and s > 1:
        xg = x                                   # groups = sequences
    else:
        xg = x.reshape(1, b * s, d)              # decode: one global group
    g, t, _ = xg.shape
    k = spec.top_k
    e = spec.n_experts
    c = capacity(t, spec)

    top_i, top_p, aux = route(params, xg, spec)                 # (G,T,k)
    flat_ids = top_i.reshape(g, t * k)                          # (G, N)
    sort_idx = jnp.argsort(flat_ids, axis=-1, stable=True)      # (G, N)
    sorted_ids = jnp.take_along_axis(flat_ids, sort_idx, axis=-1)
    pos = _positions_in_expert(sorted_ids)                      # (G, N)
    keep = pos < c
    # slot in flattened (E*C [+1 overflow]) table
    slot = jnp.where(keep, sorted_ids * c + pos, e * c)
    token_of_sorted = sort_idx // k                             # (G, N) in [0,T)

    # scatter token index + weight into the table (overflow slot dropped)
    table = jnp.full((g, e * c + 1), t, jnp.int32)              # t = pad row
    table = table.at[jnp.arange(g)[:, None], slot].set(token_of_sorted)
    w_sorted = jnp.take_along_axis(top_p.reshape(g, t * k), sort_idx, axis=-1)
    w_table = jnp.zeros((g, e * c + 1), jnp.float32)
    w_table = w_table.at[jnp.arange(g)[:, None], slot].set(w_sorted)
    table = table[:, : e * c].reshape(g, e, c)
    w_table = w_table[:, : e * c].reshape(g, e, c)

    # gather activations: pad row t is zeros
    xpad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    xin = xpad[jnp.arange(g)[:, None], table.reshape(g, e * c)]
    xin = xin.reshape(g, e, c, d)
    xin = _constrain(xin, (dp_axes[0] if g > 1 else None, ep_axis, None, None))

    h = jnp.einsum("gecd,edf->gecf", xin, params["w1"].astype(xin.dtype))
    u = jnp.einsum("gecd,edf->gecf", xin, params["w3"].astype(xin.dtype))
    h = jax.nn.silu(h) * u
    out_e = jnp.einsum("gecf,efd->gecd", h, params["w2"].astype(h.dtype))
    out_e = out_e * w_table[..., None].astype(out_e.dtype)

    # scatter-add back to tokens
    flat_out = jnp.zeros((g, t + 1, d), out_e.dtype)
    flat_out = flat_out.at[
        jnp.arange(g)[:, None], table.reshape(g, e * c)
    ].add(out_e.reshape(g, e * c, d))
    out = flat_out[:, :t].reshape(b, s, d)
    aux["drop_fraction"] = 1.0 - keep.mean()
    return out.astype(x.dtype), aux


def _constrain(x, spec_tuple):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(x, P(*spec_tuple))
    except (ValueError, RuntimeError):
        return x


# ---------------------------------------------------------------------------
# shard_map path: dispatch stays chip-local; ONE bf16 psum combines expert
# outputs over the EP axis; FSDP weight shards are all-gathered explicitly.
# Wire per layer per chip ≈ 2·(G_loc·T·d)·bf16 (combine) + weights/dp·(n-1)
# — vs GSPMD's replicate-everything fallback (≈60 GB/layer for kimi).
# ---------------------------------------------------------------------------


def _moe_apply_shard_map(params, x, spec, mesh, *, group="seq"):
    from jax.sharding import PartitionSpec as P

    dp = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    ep = "model"
    n_ep = mesh.shape[ep]
    e = spec.n_experts
    b, s, d = x.shape
    dp_size = 1
    for n in dp:
        dp_size *= mesh.shape[n]
    if e % n_ep or b % dp_size or d % dp_size:
        return _moe_apply_local(params, x, spec, group=group)
    e_loc = e // n_ep

    def body(router, w1, w3, w2, xl):
        # xl (B_loc, S, d) replicated over ep; w* ((E_loc, d/dp, f) etc.)
        w1f = jax.lax.all_gather(w1, dp, axis=1, tiled=True)
        w3f = jax.lax.all_gather(w3, dp, axis=1, tiled=True)
        w2f = jax.lax.all_gather(w2, dp, axis=2, tiled=True)
        bl, sl, _ = xl.shape
        if group == "seq" and sl > 1:
            xg = xl
        else:
            xg = xl.reshape(1, bl * sl, d)
        g, t, _ = xg.shape
        k = spec.top_k
        c = capacity(t, spec)

        top_i, top_p, aux = route({"router": router}, xg, spec)
        flat_ids = top_i.reshape(g, t * k)
        sort_idx = jnp.argsort(flat_ids, axis=-1, stable=True)
        sorted_ids = jnp.take_along_axis(flat_ids, sort_idx, axis=-1)
        pos = _positions_in_expert(sorted_ids)
        keep = pos < c
        slot = jnp.where(keep, sorted_ids * c + pos, e * c)
        token_of_sorted = sort_idx // k
        table = jnp.full((g, e * c + 1), t, jnp.int32)
        table = table.at[jnp.arange(g)[:, None], slot].set(token_of_sorted)
        w_sorted = jnp.take_along_axis(top_p.reshape(g, t * k), sort_idx,
                                       axis=-1)
        w_table = jnp.zeros((g, e * c + 1), jnp.float32)
        w_table = w_table.at[jnp.arange(g)[:, None], slot].set(w_sorted)

        # this chip computes only ITS e_loc experts' slots
        rank = jax.lax.axis_index(ep)
        lo = rank * e_loc * c
        table_loc = jax.lax.dynamic_slice_in_dim(
            table[:, : e * c], lo, e_loc * c, axis=1)
        wt_loc = jax.lax.dynamic_slice_in_dim(
            w_table[:, : e * c], lo, e_loc * c, axis=1)

        xpad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
        xin = xpad[jnp.arange(g)[:, None], table_loc]        # (g, elc·c, d)
        xin = xin.reshape(g, e_loc, c, d)
        h = jnp.einsum("gecd,edf->gecf", xin, w1f.astype(xin.dtype))
        u = jnp.einsum("gecd,edf->gecf", xin, w3f.astype(xin.dtype))
        h = jax.nn.silu(h) * u
        out_e = jnp.einsum("gecf,efd->gecd", h, w2f.astype(h.dtype))
        out_e = out_e * wt_loc.reshape(g, e_loc, c, 1).astype(out_e.dtype)

        flat_out = jnp.zeros((g, t + 1, d), out_e.dtype)
        flat_out = flat_out.at[
            jnp.arange(g)[:, None], table_loc
        ].add(out_e.reshape(g, e_loc * c, d))
        out = jax.lax.psum(flat_out[:, :t], ep)              # bf16 combine
        aux["drop_fraction"] = 1.0 - keep.mean()
        # aux is model-invariant (computed from ep-replicated routing);
        # average over the data axes only
        aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp), aux)
        return out.reshape(bl, sl, d).astype(xl.dtype), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(ep, dp, None), P(ep, dp, None), P(ep, None, dp),
                  P(dp, None, None)),
        out_specs=(P(dp, None, None), P()))
    return fn(params["router"], params["w1"], params["w3"], params["w2"], x)
