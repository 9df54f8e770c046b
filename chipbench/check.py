"""The comparison that decides ``correct``.

A sample of the window's answered requests, drawn from the seed, is run
through the configuration's plain float32 reference (its own tower,
mixing weights, router and scoring, over the same drawn index). Two
numbers are compared, each against the limit the configuration states:

``score_err``
    the widest gap, over the sampled requests and their ``k`` answers,
    between the score the system returned for an object and the score
    the reference gives that object for that request, in units of the
    request's reference score spread (the standard deviation of its
    reference scores over every object of the index). It covers the
    tower, the mixing weights, the scan's scoring, and answers that went
    to the wrong request or were altered.
``route_rank_gap``
    for each request, the least, over the route sets of ``cr`` clusters
    that contain every cluster the answer came from, of the larger of
    (a) how far below the reference's ``cr``-th best router logit the
    set's weakest cluster lies, in units of the request's logit standard
    deviation, and (b) how far the reference's ``r``-th best score in the
    set lies above the reference score of the ``r``-th answer, in score
    spread units; the widest over the requests. A route that rounding
    flips between two near-tied clusters reads small; a wrong route, a
    missed object, a half-scanned cluster or a dropped route reads large.

A request that never got an answer, or got an exception, fails the run
on its own (``failed``).
"""
from __future__ import annotations

import functools
import itertools

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import data as data_lib

# f32 bytes of one block of dequantized rows in the exhaustive pass
_ROW_BLOCK_BYTES = 1 << 29
# requests the reference takes through its exhaustive pass at once
_REQUEST_BLOCK = 128


class Frozen(dict):
    """A configuration dict usable as a static ``jax.jit`` argument (its
    lists become tuples)."""

    def __init__(self, d):
        super().__init__({k: tuple(v) if isinstance(v, list) else v
                          for k, v in d.items()})

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def lower_rows(rows, how):
    """The control's rounding of stored rows (..., d) f32: ``"fp8"``
    (e4m3 under a per-row scale) or ``"int4"`` (symmetric, per row)."""
    if how is None:
        return rows
    amax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
    if how == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (rows / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if how == "int4":
        s = jnp.where(amax > 0, amax / 7.0, 1.0)
        return jnp.clip(jnp.round(rows / s), -7, 7) * s
    raise ValueError(f"unknown row rounding {how!r}")


def _cluster_block(c, cap, d, b):
    most = max(1, _ROW_BLOCK_BYTES // (cap * max(d, b) * 4))
    return max(n for n in range(1, min(c, most) + 1) if c % n == 0)


@functools.partial(jax.jit, static_argnames=("ref", "cfg", "k", "precision",
                                              "rows", "dist_max"))
def _reference(weights, tok, msk, loc, emb, scale, bloc, ids, *, ref, cfg,
               k, precision, rows, dist_max):
    """Per request: reference embedding, mixing weights, router logits,
    and every cluster's top-k (scores, slots) plus its score sum and sum
    of squares over the valid rows."""
    q = ref.encode(weights, tok, msk, cfg, precision)
    mix = ref.mixing_weights(weights, q)
    logits = ref.router_logits(weights, q, loc)
    table = ref.step_table(weights)
    c, cap, d = emb.shape
    nb = _cluster_block(c, cap, d, q.shape[0])
    st_precision = cfg["index_precision"]

    def block(j):
        e = jax.lax.dynamic_slice_in_dim(emb, j * nb, nb)
        s = jax.lax.dynamic_slice_in_dim(scale, j * nb, nb)
        x = lower_rows(data_lib.dequantized(e, s, st_precision), rows)
        lo = jax.lax.dynamic_slice_in_dim(bloc, j * nb, nb)
        ok = jax.lax.dynamic_slice_in_dim(ids, j * nb, nb) >= 0
        sc = ref.score(q, loc, mix, x, lo, table, dist_max)  # (B, nb, cap)
        vals, slots = jax.lax.top_k(jnp.where(ok[None], sc, -jnp.inf), k)
        sc = jnp.where(ok[None], sc, 0.0)
        return vals, slots, sc.sum(-1), (sc * sc).sum(-1)

    vals, slots, s1, s2 = jax.lax.map(block, jnp.arange(c // nb))
    # (c/nb, B, nb, ...) → (B, c, ...)
    def unblock(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((a.shape[0], c) + a.shape[3:])
    return q, mix, logits, unblock(vals), unblock(slots), unblock(s1), \
        unblock(s2)


@functools.partial(jax.jit, static_argnames=("ref", "rows", "dist_max",
                                              "st_precision"))
def _score_ids(weights, q, mix, loc, emb, scale, bloc, cl, slot, *, ref,
               rows, dist_max, st_precision):
    """Reference score of given (cluster, slot) objects per request."""
    x = lower_rows(data_lib.dequantized(emb[cl, slot], scale[cl, slot],
                                        st_precision), rows)   # (B, k, d)
    table = ref.step_table(weights)
    return jax.vmap(lambda qq, ll, mm, xx, lo: ref.score(
        qq[None], ll[None], mm[None], xx, lo, table, dist_max)[0])(
            q, loc, mix, x, bloc[cl, slot])


class Reference:
    """The reference's view of a sample of requests, computed in blocks
    of ``_REQUEST_BLOCK`` requests."""

    def __init__(self, ref, weights, model, index, buffers, counts, tok,
                 msk, loc, *, k, precision="f32", rows=None):
        self.ref, self.weights, self.k = ref, weights, k
        self.rows, self.buffers = rows, buffers
        self.offsets = data_lib.offsets_of(counts)
        self.counts = np.asarray(counts)
        self.dist_max = float(np.sqrt(2.0))
        self.st_precision = index["precision"]
        cfg = Frozen({**model, "index_precision": index["precision"]})
        parts = []
        for s in range(0, tok.shape[0], _REQUEST_BLOCK):
            sl = slice(s, s + _REQUEST_BLOCK)
            parts.append([np.asarray(a, np.float64)
                          if a.dtype != jnp.int32 else np.asarray(a)
                          for a in _reference(
                              weights, jnp.asarray(tok[sl]),
                              jnp.asarray(msk[sl]), jnp.asarray(loc[sl]),
                              buffers["emb"], buffers["scale"],
                              buffers["loc"], buffers["ids"], ref=ref,
                              cfg=cfg, k=k, precision=precision, rows=rows,
                              dist_max=self.dist_max)])
        (self.q, self.mix, self.logits, self.top_vals, self.top_slots,
         s1, s2) = (np.concatenate(p) for p in zip(*parts))
        n = self.counts.sum()
        mean = s1.sum(1) / n
        self.spread = np.sqrt(np.maximum(s2.sum(1) / n - mean ** 2, 0.0))
        self.loc = np.asarray(loc, np.float32)

    def score_ids(self, ids):
        """Reference scores (n, k) of global ids (n, k); -inf where < 0."""
        ids = np.asarray(ids)
        safe = np.where(ids >= 0, ids, 0)
        cl, slot = data_lib.cluster_of(safe, self.offsets)
        buf = self.buffers
        out = np.asarray(_score_ids(
            self.weights, jnp.asarray(self.q, jnp.float32),
            jnp.asarray(self.mix, jnp.float32), jnp.asarray(self.loc),
            buf["emb"], buf["scale"], buf["loc"],
            jnp.asarray(cl, jnp.int32), jnp.asarray(slot, jnp.int32),
            ref=self.ref, rows=self.rows, dist_max=self.dist_max,
            st_precision=self.st_precision), np.float64)
        return np.where(ids >= 0, out, -np.inf)

    def answers(self, *, cr):
        """The reference's own answers: top-k over its top-``cr`` routes."""
        routes = np.argsort(-self.logits, axis=1, kind="stable")[:, :cr]
        ids, scores = [], []
        for i, rs in enumerate(routes):
            v = self.top_vals[i, rs].reshape(-1)
            g = (self.offsets[rs][:, None] + self.top_slots[i, rs]).reshape(-1)
            order = np.argsort(-v, kind="stable")[:self.k]
            ids.append(np.where(np.isfinite(v[order]), g[order], -1))
            scores.append(v[order])
        return np.stack(ids), np.stack(scores)


def readings(ref: Reference, ids, scores, *, cr):
    """The two compared numbers for answers (ids, scores) (n, k) of the
    reference's requests (see the module docstring)."""
    ids = np.asarray(ids)
    scores = np.asarray(scores, np.float64)
    k = ids.shape[1]
    got = ref.score_ids(ids)
    spread = np.maximum(ref.spread, 1e-30)[:, None]
    err = np.abs(scores - got) / spread
    err = np.where(ids >= 0, err, np.inf)
    score_err = float(np.max(err)) if err.size else 0.0

    worst = 0.0
    for i in range(ids.shape[0]):
        valid = ids[i] >= 0
        if not valid.all():
            worst = np.inf
            break
        answer_cl = set(data_lib.cluster_of(ids[i], ref.offsets)[0].tolist())
        lg = ref.logits[i]
        lg_sd = max(float(lg.std()), 1e-30)
        cut = np.sort(lg)[::-1][cr - 1]
        best = np.inf
        for extra in _route_fill(answer_cl, lg, cr):
            route = sorted(answer_cl | set(extra))
            margin = max(0.0, float(max(cut - lg[c] for c in route)) / lg_sd)
            if margin >= best:      # completions come strongest first
                break
            pool = np.sort(ref.top_vals[i, route].reshape(-1))[::-1][:k]
            gap = np.maximum(pool - got[i, :len(pool)], 0.0).max()
            best = min(best, max(margin, float(gap) / spread[i, 0]))
        worst = max(worst, best)
    return {"score_err": score_err, "route_rank_gap": float(worst)}


def _route_fill(answer_cl, logits, cr):
    """Candidate completions of the answer's clusters to ``cr`` routes:
    every other cluster for one missing route, combinations of the
    strongest few for more."""
    missing = cr - len(answer_cl)
    if missing < 0:
        return []
    if missing == 0:
        return [()]
    others = [c for c in np.argsort(-logits, kind="stable").tolist()
              if c not in answer_cl]
    if missing == 1:
        return [(c,) for c in others]
    return list(itertools.combinations(others[:cr + 8], missing))


def sample(n_answered, size, rng):
    """Indices of the checked requests, drawn from the seed."""
    return np.sort(rng.choice(n_answered, size=min(size, n_answered),
                              replace=False))


def verdict(values, limits):
    """``correct`` and the per-number report: every number at or under
    its limit."""
    report = {name: {"value": float(values[name]),
                     "limit": float(limits[name])} for name in limits}
    ok = all(np.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in report.values())
    return ok, report
