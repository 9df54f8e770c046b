"""Smoke run of the LIST build and query path on a TPU, at paper width.

    python chip_smoke.py             # one chip: phases (a) build, (b) serve, (c) check
    python chip_smoke.py --chips 4   # the four-chip mesh path only

One process owns the chip(s) and runs every phase through the library's
own entry points (``repro.api``). Each phase prints one JSON line with
its wall and compile seconds and the device's ``peak_bytes_in_use``;
the last line is ``{"ok": true, "device": {...}}``.

(a) build — ``api.build`` with the registered ``list-dual-encoder``
    config at full width (12 layers, d_model 768, 12 heads, d_ff 3072,
    vocab 32,768, max_len 64; c=300 from its ``serve_queries`` shape)
    on a seeded 50k-object ``GeoCorpus``, for a few relevance and index
    steps. Every cut is printed.
(b) serve — 2,849,754 object embeddings at d=768 drawn from ``--seed``
    on the device (encoding them would cost ≈31 PFLOP, so the object
    tower is skipped; the query tower runs at full width on every
    request), routed by the phase-(a) router and packed by
    ``index.build_cluster_buffers`` at c=300 with the default 2×
    capacity, at bf16 and int8. Each of ``pallas`` and ``pallas-cm``
    serves a few hundred requests through ``Searcher.serve`` at k=20,
    batch 64, cr ∈ {1, 2}, and answers one ``Searcher.query`` call at
    the registered query batch of 4096.
(c) check — the served top-k of a subset of queries against a host
    float32 oracle that scores exactly the rows of the routed clusters
    in the same buffers, dequantized (overlap@20 must be ≥ 0.99), and
    recall@20 against an exhaustive scan of all objects (printed only).

The run fails — non-zero exit, no result line — without a TPU, when a
served plan holds no Mosaic kernel (``tpu_custom_call``), and on any
fallback: a breaker trip or fallback flush, a retried or poisoned flush,
a shed request, or (with ``--chips 4``) a shard scan that was retried,
hedged or served from the host. No phase's exception is caught.

``--chips 4`` builds the phase-(b) bf16 index with seeded, untrained
tower and router weights and answers the requests on chip 0 — through
the unsharded plan (printed only) and through a one-shard placement
(``snapshot.with_mesh(1)``, the same prefix and scan programs as the
mesh) — frees that copy, and serves the same requests from
``snapshot.with_mesh(4)``: the ids must equal the one-shard answers,
every shard must sit on its own device, and coverage must be 1.0.

JAX's persistent compilation cache is kept in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

K = 20                     # registered serve_queries topk
SERVE_BATCH = 64           # streaming server micro-batch
SERVE_CRS = (1, 2)
BACKENDS = ("pallas", "pallas-cm")
TIERS = ("bf16", "int8")   # f32 buffers (≈17.6 GB) do not fit one chip
N_REQUESTS = 256           # requests per (tier, backend, cr) server run
N_CHECK = 32               # requests checked against the oracles per run
MIN_OVERLAP = 0.99

BUILD_OBJECTS = 50_000     # phase-(a) corpus (Geo-Glue has 2,849,754)
BUILD_QUERIES = 2_000
REL_STEPS = 4
IDX_STEPS = 8
TRAIN_BATCH = 384          # phase-(a) batch: the train step compiles to ≈12.8 GB
                           # of the 16 GiB chip (512: ≈14.6 GB)
SPILL = 3


class CompileClock:
    """Sums the backend-compile seconds and persistent-cache hits JAX
    reports, so each phase can say how much of its wall time compiled."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Times one phase and prints its line: wall and compile seconds,
    persistent-cache hits, the largest ``peak_bytes_in_use`` over the
    devices, and whatever the phase adds to ``info``."""

    def __init__(self, name, clock, devices):
        self.name, self.clock, self.devices = name, clock, devices
        self.info = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.seconds, self.clock.cache_hits
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        print(json.dumps({
            "phase": self.name,
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "compile_s": round(self.clock.seconds - self.c0, 3),
            "cache_hits": self.clock.cache_hits - self.h0,
            "peak_bytes_in_use": int(peak), **self.info}), flush=True)
        return False


# ---------------------------------------------------------------------------
# Phase (a): build
# ---------------------------------------------------------------------------


def smoke_config(n_objects):
    """The registered config at full width; c from the serve_queries
    shape and the pseudo-negative window of the mine_negatives shape
    scaled from Geo-Glue down to ``n_objects``."""
    from repro.configs import get_config, get_shapes

    shapes = {s.name: s.dims for s in get_shapes("list-dual-encoder")}
    serve, mine = shapes["serve_queries"], shapes["mine_negatives"]
    scale = n_objects / serve["n_objects"]
    neg_start = max(1, round(mine["neg_start"] * scale))
    window = max(2 * get_config("list-dual-encoder").mcl_negatives,
                 round((mine["neg_end"] - mine["neg_start"]) * scale))
    return dataclasses.replace(get_config("list-dual-encoder"),
                               n_clusters=serve["n_clusters"],
                               neg_start=neg_start,
                               neg_end=neg_start + window), serve


def smoke_corpus(cfg, *, n_objects, n_queries, seed):
    from repro.data import GeoCorpus, GeoCorpusConfig
    return GeoCorpus(GeoCorpusConfig(
        n_objects=n_objects, n_queries=n_queries, max_len=cfg.max_len,
        vocab_size=cfg.vocab_size, seed=seed))


# ---------------------------------------------------------------------------
# Phase (b): the Geo-Glue-scale index
# ---------------------------------------------------------------------------


def draw_objects(index_params, norm, *, n_objects, d, seed, spill,
                 chunk=1 << 16):
    """``n_objects`` seeded object embeddings ~ N(0, 1) at width ``d``
    (stored bf16) and uniform locations, drawn and routed on the device
    a chunk at a time. → (emb (n, d) bf16, loc (n, 2) f32, top (n,
    spill) int32 preferred clusters, best first) as host arrays."""
    import jax
    import jax.numpy as jnp
    from repro.core import index as index_lib

    @jax.jit
    def draw(i):
        ke, kl = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                     i))
        emb = jax.random.normal(ke, (chunk, d), jnp.float32)
        emb = emb.astype(jnp.bfloat16)
        loc = jax.random.uniform(kl, (chunk, 2), jnp.float32)
        feats = index_lib.build_features(emb.astype(jnp.float32), loc, norm)
        top = index_lib.assign_clusters(index_params, feats, top=spill)
        return emb, loc, top.astype(jnp.int32)

    import ml_dtypes
    emb = np.empty((n_objects, d), ml_dtypes.bfloat16)
    loc = np.empty((n_objects, 2), np.float32)
    top = np.empty((n_objects, spill), np.int32)
    for i, s in enumerate(range(0, n_objects, chunk)):
        e = min(s + chunk, n_objects)
        ce, cl, ct = (np.asarray(x) for x in draw(i))
        emb[s:e], loc[s:e], top[s:e] = ce[:e - s], cl[:e - s], ct[:e - s]
    return emb, loc, top


def tier_snapshot(cfg, params, objects, *, precision):
    """The served snapshot: ``params`` = (tower params, router params,
    location normalizer) over the drawn objects packed at
    ``precision``, in the unit box (``dist_max`` = √2)."""
    from repro.core import index as index_lib
    from repro.core.snapshot import IndexSnapshot

    emb, loc, top = objects
    buffers = index_lib.build_cluster_buffers(
        top, emb, loc, n_clusters=cfg.n_clusters, spill=top.shape[1],
        precision=precision)
    return IndexSnapshot.from_parts(cfg, *params, buffers,
                                    dist_max=math.sqrt(2.0))


def requests_of(corpus, n, offset=0):
    """``n`` query rows of the corpus (cycled), as token/mask/loc arrays."""
    ids = (offset + np.arange(n)) % corpus.cfg.n_queries
    tok, msk = corpus.query_tokens(ids)
    return tok, msk, corpus.q_loc[ids].astype(np.float32)


def require_compiled(engine):
    """The engine compiles its kernels for the TPU (never interprets)."""
    import jax
    if engine.interpret or jax.default_backend() != "tpu":
        raise SystemExit("chip_smoke: the engine would interpret its "
                         "kernels")


def assert_compiled_kernel(engine, *, k, cr, backend, batch, precision):
    """The plan the engine serves holds a Mosaic kernel (never the
    interpreter, never a dense fallback)."""
    import jax.numpy as jnp

    snap = engine.snapshot
    fn = engine.query_fn(k=k, cr=cr, backend=backend, batch=batch,
                         precision=precision)
    L = snap.cfg.max_len
    buf = snap.buffers
    hlo = fn.lower(snap.rel_params, snap.index_params, snap.w_hat, snap.norm,
                   buf["emb"], buf["loc"], buf["ids"], buf["scale"],
                   jnp.zeros((batch, L), jnp.int32),
                   jnp.ones((batch, L), bool),
                   jnp.zeros((batch, 2), jnp.float32)).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise SystemExit(f"chip_smoke: the {backend}/{precision} plan "
                         f"(batch {batch}, cr {cr}) holds no Mosaic kernel")


def serve_run(searcher, requests, *, backend, cr, batch, k):
    """Stream ``requests`` through ``searcher.serve`` (closed loop) and
    fail on any fallback, retry or shed. → (ids (n, k), metrics)."""
    from repro.core import server as server_lib

    server = searcher.serve(server_lib.ServerConfig(
        batch_size=batch, k=k, cr=cr, backend=backend, cache_size=0,
        breaker_threshold=0))
    server.warmup()
    tok, msk, loc = requests
    rows = [(tok[i], msk[i], loc[i]) for i in range(len(tok))]
    results = asyncio.run(server_lib.closed_loop(server, rows,
                                                 concurrency=batch))
    m = server.metrics()
    server.close()
    faults = {"breaker_trips": m["breaker"]["trips"],
              "fallback_flushes": m["breaker"]["fallback_flushes"],
              "flush_retries": m["flush_retries"],
              "poisoned_requests": m["poisoned_requests"],
              "shed": sum(m["shed"].values())}
    if any(faults.values()) or any(r is None for r in results):
        raise SystemExit(f"chip_smoke: {backend} cr={cr} fell back: "
                         f"{faults}")
    return np.stack([r[0] for r in results]), m


# ---------------------------------------------------------------------------
# Phase (c): the oracles
# ---------------------------------------------------------------------------


class HostOracle:
    """Host float32 scoring of exactly the routed clusters' rows of one
    snapshot's buffers, dequantized — independent of every device scan.
    Routes and query embeddings come from the engine's own prefix, run
    in the serving batch shape."""

    def __init__(self, engine, *, batch):
        self.engine, self.batch = engine, batch
        snap = engine.snapshot
        self.w_hat = np.asarray(snap.w_hat, np.float64)
        self.dist_max = snap.meta.dist_max
        self.precision = snap.meta.precision
        self._rows, self._topk = {}, {}

    def _cluster(self, c):
        if c not in self._rows:
            buf = self.engine.snapshot.buffers
            at = np.int32(c)                # one gather program, any c
            emb = np.asarray(buf["emb"][at]).astype(np.float32)
            if self.precision == "int8":
                emb = emb * np.asarray(buf["scale"][at])[:, None]
            self._rows[c] = (emb, np.asarray(buf["loc"][at]),
                             np.asarray(buf["ids"][at]))
        return self._rows[c]

    def prefix(self, requests, *, cr):
        from repro.core import engine as engine_lib
        snap = self.engine.snapshot
        pre = self.engine.prefix_fn(cr=cr)
        return engine_lib.run_batched(
            lambda t, m, l: pre(snap.rel_params, snap.index_params,
                                snap.norm, t, m, l),
            list(requests), batch=self.batch)

    def topk(self, requests, *, cr, k):
        """Top-k ids of ``requests`` at ``cr`` routes, memoized per cr
        (every backend is checked against the same requests)."""
        if cr not in self._topk:
            self._topk[cr] = self._score(requests, cr=cr, k=k)
        return self._topk[cr]

    def _score(self, requests, *, cr, k):
        q_emb, w, top_c = self.prefix(requests, cr=cr)
        loc = requests[2]
        t = self.w_hat.shape[0]
        out = []
        for q in range(q_emb.shape[0]):
            emb, cl, ids = (np.concatenate(x) for x in zip(
                *(self._cluster(int(c)) for c in top_c[q])))
            trel = emb @ q_emb[q]
            dist = np.sqrt(((loc[q] - cl) ** 2).sum(-1))
            s_in = 1.0 - np.clip(dist / self.dist_max, 0.0, 1.0)
            srel = self.w_hat[np.clip(np.floor(s_in * t).astype(np.int64),
                                      0, t - 1)]
            st = np.where(ids >= 0, w[q, 0] * trel + w[q, 1] * srel, -np.inf)
            order = np.argsort(-st, kind="stable")[:k]
            out.append(np.where(np.isfinite(st[order]), ids[order], -1))
        return np.stack(out)


def overlap_at_k(got, want):
    """Mean over rows of |got ∩ want| / |want| over valid (≥ 0) ids."""
    vals = []
    for g, w in zip(got, want):
        w = set(w[w >= 0].tolist())
        vals.append(len(w & set(g[g >= 0].tolist())) / max(len(w), 1))
    return float(np.mean(vals))


def exhaustive_topk(snap, q_emb, w, q_loc, *, k):
    """Top-k over every object of the snapshot's buffers (all clusters),
    scored on the device at float32 matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine as engine_lib

    buf = snap.buffers
    int8 = snap.meta.precision == "int8"

    @jax.jit
    def run(qe, qw, ql, emb, loc, ids, scale, w_hat):
        def one(carry, c):
            st = engine_lib.score_candidates(
                qe, ql, qw, emb[c][None], loc[c][None], ids[c][None], w_hat,
                dist_max=snap.meta.dist_max,
                cand_scale=scale[c][None] if int8 else None)
            cand_i = jnp.broadcast_to(ids[c][None], st.shape)
            vals = jnp.concatenate([carry[0], st], axis=1)
            idx = jnp.concatenate([carry[1], cand_i], axis=1)
            top_v, pos = jax.lax.top_k(vals, k)
            return (top_v, jnp.take_along_axis(idx, pos, axis=1)), None

        init = (jnp.full((qe.shape[0], k), -jnp.inf, jnp.float32),
                jnp.full((qe.shape[0], k), -1, jnp.int32))
        with jax.default_matmul_precision("highest"):
            (_, top_i), _ = jax.lax.scan(one, init,
                                         jnp.arange(emb.shape[0]))
        return top_i

    return np.asarray(run(q_emb, w, q_loc, buf["emb"], buf["loc"],
                          buf["ids"], buf["scale"], snap.w_hat))


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


def run_one_chip(jax, clock, *, seed, build_objects=BUILD_OBJECTS,
                 build_queries=BUILD_QUERIES, rel_steps=REL_STEPS,
                 idx_steps=IDX_STEPS, train_batch=TRAIN_BATCH,
                 n_objects=None, serve_batch=SERVE_BATCH,
                 query_batch=None, n_requests=N_REQUESTS, n_check=N_CHECK):
    from repro import api

    devices = jax.devices()[:1]
    cfg, serve = smoke_config(build_objects)
    n_objects = serve["n_objects"] if n_objects is None else n_objects
    query_batch = serve["query_batch"] if query_batch is None else query_batch

    with Phase("a_build", clock, devices) as ph:
        corpus = smoke_corpus(cfg, n_objects=build_objects,
                              n_queries=build_queries, seed=seed)
        base = api.build(cfg, corpus, rel_steps=rel_steps,
                         idx_steps=idx_steps, batch=train_batch, seed=seed)
        ph.info = {
            "model": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                      "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                      "vocab_size": cfg.vocab_size, "max_len": cfg.max_len,
                      "n_clusters": cfg.n_clusters},
            "cuts": {"build_objects": [build_objects, serve["n_objects"]],
                     "rel_steps": rel_steps, "idx_steps": idx_steps,
                     "train_batch": [train_batch, 4096],
                     "neg_window": [cfg.neg_start, cfg.neg_end],
                     "weights": "trained a few steps from a seed"}}

    params = (base.rel_params, base.index_params, base.norm)
    with Phase("b_draw", clock, devices) as ph:
        objects = draw_objects(base.index_params, base.norm,
                               n_objects=n_objects, d=cfg.d_model,
                               seed=seed, spill=SPILL)
        ph.info = {"n_objects": n_objects, "d": cfg.d_model,
                   "cuts": {"object_tower": "skipped: embeddings drawn "
                            "from the seed"}}

    requests = requests_of(corpus, n_requests)
    check = tuple(a[:n_check] for a in requests)
    big = requests_of(corpus, query_batch)
    summary = {}
    for precision in TIERS:
        with Phase(f"b_pack_{precision}", clock, devices) as ph:
            snap = tier_snapshot(cfg, params, objects, precision=precision)
            ph.info = {"capacity": int(snap.buffers["capacity"]),
                       "n_clusters": cfg.n_clusters,
                       "emb_bytes": int(snap.buffers["emb"].nbytes),
                       "spilled": int(snap.buffers["n_spilled"])}
        oracle, served = None, {}
        for backend in BACKENDS:
            searcher = api.Searcher(snap, backend=backend)
            eng = searcher.engine
            require_compiled(eng)
            if oracle is None:
                oracle = HostOracle(eng, batch=serve_batch)
            for cr in SERVE_CRS:
                with Phase(f"b_serve_{precision}_{backend}_cr{cr}", clock,
                           devices) as ph:
                    ids, m = serve_run(searcher, requests, backend=backend,
                                       cr=cr, batch=serve_batch, k=K)
                    served[f"{backend}/cr{cr}"] = ids[:n_check]
                    assert_compiled_kernel(eng, k=K, cr=cr, backend=backend,
                                           batch=serve_batch,
                                           precision=precision)
                    ph.info = {"requests": m["requests"],
                               "engine_batches": m["engine_batches"],
                               "latency_ms": m["latency_ms"],
                               "warmup_s": m["compile_seconds"]}
                with Phase(f"c_check_{precision}_{backend}_cr{cr}", clock,
                           devices) as ph:
                    want = oracle.topk(check, cr=cr, k=K)
                    ov = overlap_at_k(ids[:n_check], want)
                    summary[f"{precision}/{backend}/cr{cr}"] = ov
                    ph.info = {"overlap@20_vs_host_oracle": ov,
                               "checked": n_check}
                    if ov < MIN_OVERLAP:
                        raise SystemExit(
                            f"chip_smoke: {precision}/{backend} cr={cr} "
                            f"overlap@20 {ov:.4f} < {MIN_OVERLAP}")
            with Phase(f"b_query_{precision}_{backend}_b{query_batch}",
                       clock, devices) as ph:
                big_ids, big_sc = searcher.query(*big, k=K, cr=1,
                                                 batch=query_batch)
                assert_compiled_kernel(eng, k=K, cr=1, backend=backend,
                                       batch=query_batch,
                                       precision=precision)
                if not np.isfinite(big_sc).all() or (big_ids < 0).all(1).any():
                    raise SystemExit(f"chip_smoke: {backend} batch "
                                     f"{query_batch} returned empty rows")
                served[f"{backend}/b{query_batch}"] = big_ids[:n_check]
                ov = overlap_at_k(big_ids[:n_check],
                                  oracle.topk(check, cr=1, k=K))
                summary[f"{precision}/{backend}/b{query_batch}"] = ov
                ph.info = {"queries": int(big_ids.shape[0]),
                           "overlap@20_vs_host_oracle": ov}
                if ov < MIN_OVERLAP:
                    raise SystemExit(f"chip_smoke: {precision}/{backend} "
                                     f"batch {query_batch} overlap@20 "
                                     f"{ov:.4f} < {MIN_OVERLAP}")
        with Phase(f"c_exhaustive_{precision}", clock, devices) as ph:
            q_emb, w, _ = oracle.prefix(check, cr=1)
            exact = exhaustive_topk(snap, q_emb, w, check[2], k=K)
            ph.info = {"recall@20_vs_exhaustive": {
                name: overlap_at_k(ids, exact)
                for name, ids in served.items()}}
        # the next tier's index and this one's do not fit the chip
        # together: free this one's device buffers outright
        free(snap.buffers.values())
        del snap, searcher, eng, oracle
    return summary


def run_four_chips(jax, clock, *, seed, build_objects=BUILD_OBJECTS,
                   build_queries=BUILD_QUERIES, n_objects=None,
                   serve_batch=SERVE_BATCH, n_requests=N_REQUESTS,
                   n_shards=4):
    """Single-device answers on chip 0, then the same requests from the
    mesh-sharded snapshot; ids must agree."""
    import jax.numpy as jnp
    from repro import api
    from repro.core import index as index_lib
    from repro.core import relevance

    devices = jax.devices()[:n_shards]
    cfg, serve = smoke_config(build_objects)
    n_objects = serve["n_objects"] if n_objects is None else n_objects
    corpus = smoke_corpus(cfg, n_objects=build_objects,
                          n_queries=build_queries, seed=seed)
    rel = relevance.relevance_init(jax.random.PRNGKey(seed), cfg)
    iparams = index_lib.index_init(jax.random.PRNGKey(seed + 1), cfg.d_model,
                                   cfg.n_clusters, hidden=cfg.index_mlp_hidden)
    norm = {"lo": jnp.zeros((2,), jnp.float32),
            "span": jnp.ones((2,), jnp.float32)}
    requests = requests_of(corpus, n_requests)

    with Phase("mesh_build_bf16", clock, devices) as ph:
        objects = draw_objects(iparams, norm, n_objects=n_objects,
                               d=cfg.d_model, seed=seed, spill=SPILL)
        snap = tier_snapshot(cfg, (rel, iparams, norm), objects,
                             precision="bf16")
        del objects
        ph.info = {"n_objects": n_objects,
                   "capacity": int(snap.buffers["capacity"]),
                   "cuts": {"weights": "seeded, untrained towers and "
                            "router", "object_tower": "skipped"}}
    with Phase("mesh_unsharded", clock, devices) as ph:
        fused = {backend: api.Searcher(snap, backend=backend).query(
            *requests, k=K, cr=1, batch=serve_batch)[0]
            for backend in BACKENDS}
        # chip 0 cannot hold this copy and a placed one together: keep
        # the global buffers on the host, as with_mesh does
        host = {key: np.asarray(arr) if isinstance(arr, jax.Array) else arr
                for key, arr in snap.buffers.items()}
        free(snap.buffers.values())
        snap = dataclasses.replace(snap, buffers=host)
    with Phase("mesh_single_device", clock, devices) as ph:
        one = snap.with_mesh(1)
        single = {backend: mesh_query(one, backend, requests,
                                      batch=serve_batch)[:2]
                  for backend in BACKENDS}
        device = str(one.shards.devices[0])
        free(one.shards.parts[0].values())
        del one
        # the unsharded plan is one fused program, the placed path a
        # prefix program then the scans: their query towers may round
        # apart on the chip, so this is printed, not gated
        ph.info = {"device": device,
                   "unsharded_vs_placed": {
                       backend: {"overlap@20": overlap_at_k(
                                     fused[backend], single[backend][0]),
                                 "rows_equal": rows_equal(
                                     fused[backend], single[backend][0])}
                       for backend in BACKENDS}}
    with Phase(f"mesh_shard_{n_shards}", clock, devices) as ph:
        sharded = snap.with_mesh(n_shards)
        shards = sharded.shards
        if len(set(shards.devices)) != n_shards:
            raise SystemExit(f"chip_smoke: shards share devices: "
                             f"{shards.devices}")
        for s, part in enumerate(shards.parts):
            for key, arr in part.items():
                if arr.devices() != {shards.devices[s]}:
                    raise SystemExit(f"chip_smoke: shard {s} {key} sits on "
                                     f"{arr.devices()}")
        ph.info = {"bytes_per_device": shards.nbytes_per_device()}
    agree = {}
    for backend in BACKENDS:
        with Phase(f"mesh_query_{backend}", clock, devices) as ph:
            ids, scores, health = mesh_query(sharded, backend, requests,
                                             batch=serve_batch)
            want_ids, want_scores = single[backend]
            agree[backend] = rows_equal(ids, want_ids)
            ph.info = {**health,
                       "rows_equal_single_device": agree[backend],
                       "scores_equal": bool(np.array_equal(scores,
                                                           want_scores))}
            if agree[backend] != 1.0:
                raise SystemExit(f"chip_smoke: mesh {backend} ids differ "
                                 f"from single-device on "
                                 f"{1 - agree[backend]:.2%} of rows")
    return agree


def rows_equal(a, b):
    """Share of rows whose top-k ids are equal, order included."""
    return float((a == b).all(1).mean())


def mesh_query(snap, backend, requests, *, batch):
    """Answer ``requests`` from a mesh-placed snapshot at cr=1 and fail on
    any degraded answer: coverage below 1, or a shard scan that was
    retried, hedged, skipped or served from the host. → (ids, scores,
    {coverage, shard stats})."""
    from repro import api
    searcher = api.Searcher(snap, backend=backend)
    ids, scores = searcher.query(*requests, k=K, cr=1, batch=batch)
    eng = searcher.engine
    health = {"coverage": eng.last_coverage, **eng.shard_stats}
    if eng.last_coverage != 1.0 or any(eng.shard_stats.values()):
        raise SystemExit(f"chip_smoke: mesh {backend} over "
                         f"{snap.meta.n_shards} shard(s) degraded: {health}")
    return ids, scores, health


def free(arrays):
    """Delete the device buffers among ``arrays`` now, not at collection."""
    import jax
    for arr in arrays:
        if isinstance(arr, jax.Array):
            arr.delete()
    gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); refusing to fall back",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"jax": jax.__version__, "compile_cache": cache_dir,
                      "devices": [d.device_kind for d in devices]}),
          flush=True)
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        result = run_four_chips(jax, clock, seed=args.seed)
    else:
        result = run_one_chip(jax, clock, seed=args.seed)
    print(json.dumps({"summary": result,
                      "total_wall_s": round(time.perf_counter() - t0, 3),
                      "total_compile_s": round(clock.seconds, 3)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
