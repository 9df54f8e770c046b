"""The program's own tracing: host spans, device scopes, counters.

* a profiled ``QueryEngine.query`` and server flush emit the ``repro.*``
  host spans, nested flush ⊃ query ⊃ pick / dispatch / sync / delta;
* every op of the lowered plans sits under a named device scope
  (``engine.DEVICE_SCOPES``), for each backend;
* ``encoder_passes`` counts the auto pick's measuring encode;
* a request's admit + queue + flush + resume waits add up to its latency;
* answers are bit-identical with the profiler on and off.
"""
import asyncio
import dataclasses
import glob
import os
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import engine as engine_lib
from repro.core import index as il
from repro.core import relevance
from repro.core import server as server_lib

DIST_MAX = 1.414


def build_parts(n, cap):
    cfg = dataclasses.replace(
        get_config("list-dual-encoder"),
        n_layers=2, d_model=32, n_heads=2, d_ff=64, vocab_size=512,
        max_len=8, spatial_t=50, n_clusters=4, index_mlp_hidden=(16,))
    rng = np.random.default_rng(3)
    params = relevance.relevance_init(jax.random.PRNGKey(0), cfg)
    c = cfg.n_clusters
    obj_emb = rng.normal(size=(n, cfg.d_model)).astype(np.float32)
    obj_loc = rng.uniform(size=(n, 2)).astype(np.float32)
    norm = il.loc_normalizer(jnp.asarray(obj_loc))
    iparams = il.index_init(jax.random.PRNGKey(5), cfg.d_model, c,
                            hidden=(16,))
    feats = il.build_features(jnp.asarray(obj_emb), jnp.asarray(obj_loc),
                              norm)
    top = np.asarray(il.assign_clusters(iparams, feats, top=2))
    buf = il.build_cluster_buffers(top, obj_emb, obj_loc, n_clusters=c,
                                   capacity=cap)
    return cfg, params, iparams, norm, buf


@pytest.fixture(scope="module")
def engine_parts():
    return build_parts(96, 64)


def make_engine(engine_parts, backend="auto"):
    cfg, params, iparams, norm, buf = engine_parts
    return engine_lib.QueryEngine.from_parts(
        cfg, params, iparams, norm, buf, dist_max=DIST_MAX, backend=backend)


def make_server(engine_parts, *, engine_backend="auto", **over):
    kw = dict(batch_size=4, max_delay_ms=5.0, k=5, cr=1, backend=None)
    kw.update(over)
    return server_lib.StreamingServer(make_engine(engine_parts,
                                                  engine_backend),
                                      server_lib.ServerConfig(**kw))


def make_requests(seed, n, cfg):
    rng = np.random.default_rng(seed)
    tok = rng.integers(2, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
    tok[:, 0] = 1
    msk = np.ones((n, cfg.max_len), bool)
    loc = rng.uniform(size=(n, 2)).astype(np.float32)
    return tok, msk, loc


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler. → (fn's result, the host's
    ``repro.*`` events as (name, start_ns, end_ns, stats))."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = int(ev.start_ns)
                    events.append((ev.name, s, s + int(ev.duration_ns),
                                   dict(ev.stats)))
    return out, sorted(events, key=lambda e: e[1])


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


def test_profiled_flushes_emit_nested_repro_spans(engine_parts, tmp_path):
    cfg = engine_parts[0]
    srv = make_server(engine_parts)
    srv.serve_all(*make_requests(10, 4, cfg))         # compile outside
    tok, msk, loc = make_requests(0, 12, cfg)
    b0 = srv.stats.engine_batches
    _, ev = traced(tmp_path, lambda: srv.serve_all(tok, msk, loc))
    flushes = named(ev, "repro.flush")
    assert len(flushes) == srv.stats.engine_batches - b0 == 3
    assert [f[3]["rows"] for f in flushes] == [4, 4, 4]
    assert [f[3]["seq"] for f in flushes] == [b0, b0 + 1, b0 + 2]
    queries = named(ev, "repro.query")
    assert len(queries) == 3
    for name in ("repro.assemble", "repro.query", "repro.resolve"):
        spans = named(ev, name)
        assert len(spans) == 3 and all(inside(s, flushes) for s in spans)
    # auto at batch 4 < 2·c: the pick routes the chunk (one dispatch and
    # one sync of its own), then the plan runs (one of each)
    picks = named(ev, "repro.pick")
    assert len(picks) == 3 and all(inside(p, queries) for p in picks)
    for name in ("repro.dispatch", "repro.sync"):
        spans = named(ev, name)
        assert len(spans) == 6 and all(inside(s, queries) for s in spans)
        assert sum(inside(s, picks) for s in spans) == 3
    assert not named(ev, "repro.delta")


def test_profiled_query_with_a_delta_spans_the_delta_merge(engine_parts,
                                                           tmp_path):
    cfg = engine_parts[0]
    srv = make_server(engine_parts, engine_backend="dense")
    rng = np.random.default_rng(9)
    srv.insert_objects(rng.normal(size=(3, cfg.d_model)).astype(np.float32),
                       rng.uniform(size=(3, 2)).astype(np.float32),
                       np.array([1000, 1001, 1002], np.int32))
    eng = srv.engine
    tok, msk, loc = make_requests(1, 6, cfg)
    eng.query(tok, msk, loc, k=5, batch=4)            # compile outside
    _, ev = traced(tmp_path, lambda: eng.query(tok, msk, loc, k=5, batch=4))
    query, = named(ev, "repro.query")
    delta, = named(ev, "repro.delta")
    assert inside(delta, [query])
    assert not named(ev, "repro.pick")                # explicit backend
    # two chunks of the base plan, two of the delta scan inside the delta
    dispatches = named(ev, "repro.dispatch")
    assert len(dispatches) == 4
    assert sum(inside(d, [delta]) for d in dispatches) == 2


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------


def op_scopes(hlo_text):
    """{instruction: innermost device scope or None} of every
    instruction of a compiled module whose ``op_name`` metadata is a
    path from the plan's ``jit(...)`` (parameters carry argument names,
    and reducer bodies paths of their own)."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.search(r"%?([\w.\-]+) = .*?metadata=\{op_name=\"([^\"]*)\"",
                      line)
        if not m or " parameter(" in line or not m[2].startswith("jit("):
            continue
        scope = next((p for p in reversed(m[2].split("/"))
                      if p in engine_lib.DEVICE_SCOPES), None)
        out[m[1]] = scope
    return out


def plan_args(engine_parts, b):
    cfg, params, iparams, norm, buf = engine_parts
    eng = make_engine(engine_parts, "dense")
    tok, msk, loc = make_requests(2, b, cfg)
    bufs = (buf["emb"], buf["loc"], buf["ids"], buf["scale"])
    return eng, params, iparams, norm, bufs, (tok, msk, loc)


@pytest.mark.parametrize("backend", ["pallas", "pallas-cm", "dense",
                                     "dense-cm"])
def test_every_op_of_the_query_plan_sits_under_a_scope(engine_parts,
                                                       backend):
    eng, params, iparams, norm, bufs, q = plan_args(engine_parts, 8)
    fn = engine_lib.make_query_fn(eng.cfg, cr=2, k=5, backend=backend,
                                  dist_max=DIST_MAX)
    text = fn.lower(params, iparams, eng.w_hat, norm, *bufs,
                    *q).compile().as_text()
    scopes = op_scopes(text)
    assert scopes and None not in scopes.values(), sorted(
        k for k, v in scopes.items() if v is None)
    want = {"tower", "route", "scan"} | (
        {"merge"} if backend.endswith("-cm") else set())
    assert set(scopes.values()) == want


def test_route_prefix_and_delta_plans_sit_under_scopes(engine_parts):
    eng, params, iparams, norm, bufs, q = plan_args(engine_parts, 8)
    route = engine_lib.make_route_fn(eng.cfg, cr=2)
    prefix = engine_lib.make_prefix_fn(eng.cfg, cr=2)
    delta = engine_lib.make_delta_scan_fn(eng.cfg, k=5, dist_max=DIST_MAX)
    d = bufs[0].shape[-1]
    rows = (np.zeros((128, d), np.float32), np.ones((128,), np.float32),
            np.zeros((128, 2), np.float32), np.full((128,), -1, np.int32))
    for fn, args, want in (
            (route, (params, iparams, norm, *q), {"tower", "route"}),
            (prefix, (params, iparams, norm, *q), {"tower", "route"}),
            (delta, (params, eng.w_hat, *rows, *q),
             {"tower", "route", "scan"})):
        scopes = op_scopes(fn.lower(*args).compile().as_text())
        assert None not in scopes.values()
        assert set(scopes.values()) == want


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,batch,passes", [
    (None, 4, 2),          # auto, batch·cr < 2·c: the pick encodes to route
    ("dense", 4, 1),       # an explicit backend skips the pick
    (None, 8, 1),          # auto, batch·cr ≥ 2·c: the bound decides alone
])
def test_encoder_passes_per_flush(engine_parts, backend, batch, passes):
    cfg = engine_parts[0]
    srv = make_server(engine_parts, batch_size=batch, backend=backend)
    tok, msk, loc = make_requests(4, 2 * batch, cfg)
    srv.serve_all(tok, msk, loc)
    assert srv.stats.engine_batches == 2
    assert srv.engine.stats["encoder_passes"] == 2 * passes
    assert srv.metrics()["encoder_passes_per_flush"] == passes


@pytest.mark.parametrize("backend", ["pallas", "pallas-cm"])
def test_scan_tile_counters_follow_the_live_extent(backend):
    """The live extent is the last live slot + 1 through inserts and
    deletes (0 once a cluster is emptied, past ``counts`` once it has
    holes), and one query call counts the kernel grid's tiles under it:
    the queries' routed pairs (query-major) or the plan rows that serve
    a query (cluster-major), none for the padding rows of a partial
    chunk, against the grid's tile count."""
    from repro.kernels import fused_topk_score as fts
    cfg, params, iparams, norm, buf = build_parts(1500, 1024)
    c, bn, per_row = cfg.n_clusters, 512, 2
    rng = np.random.default_rng(7)
    buf = il.insert_objects(
        buf, iparams, norm,
        jnp.asarray(rng.normal(size=(5, cfg.d_model)), jnp.float32),
        jnp.asarray(rng.uniform(size=(5, 2)), jnp.float32),
        np.arange(10_000, 10_005))
    ids = np.asarray(buf["ids"])
    fullest, emptied = np.argsort((ids >= 0).sum(1))[[-1, 0]]
    live = ids[fullest][ids[fullest] >= 0]
    buf = il.delete_objects(buf, np.concatenate(
        [live[10:-3], ids[emptied][ids[emptied] >= 0]]))
    ids = np.asarray(buf["ids"])
    want = np.array([np.flatnonzero(r >= 0).max() + 1 if (r >= 0).any()
                     else 0 for r in ids])
    extent = np.asarray(fts.live_extent(buf["ids"]))
    assert (extent == want).all()
    assert extent[emptied] == 0
    assert np.asarray(buf["counts"])[fullest] < extent[fullest]
    assert extent[fullest] > bn                   # spans both tiles

    eng = engine_lib.QueryEngine.from_parts(
        cfg, params, iparams, norm, buf, dist_max=DIST_MAX, backend=backend)
    tok, msk, loc = make_requests(5, 6, cfg)      # chunks of 4 and 2 + 2 pad
    eng.query(tok, msk, loc, k=5, cr=2, batch=4)
    top_c = np.asarray(eng.route(tok, msk, loc, cr=2))
    if backend == "pallas":
        live_tiles = int(np.sum(-(-extent[top_c] // bn)))
        grid = 2 * top_c[:4].size * per_row
    else:
        live_tiles = sum(int(np.sum(-(-extent[np.unique(top_c[s:s + 4])]
                                       // bn))) for s in (0, 4))
        grid = 2 * min(top_c[:4].size, c) * per_row
    assert eng.stats["scan_tiles_live"] == live_tiles
    assert eng.stats["scan_tiles_grid"] == grid

    srv = server_lib.StreamingServer(eng, server_lib.ServerConfig(
        batch_size=4, max_delay_ms=5.0, k=5, cr=2, backend=None))
    assert srv.metrics()["scan_live_tile_share"] is None
    before = dict(eng.stats)
    srv.serve_all(tok, msk, loc)
    live, grid = (eng.stats[c] - before[c]
                  for c in ("scan_tiles_live", "scan_tiles_grid"))
    assert 0 < live < grid
    assert (srv.stats.scan_tiles_live, srv.stats.scan_tiles_grid) == (live,
                                                                      grid)
    assert srv.metrics()["scan_live_tile_share"] == pytest.approx(live / grid)


def test_waits_add_up_to_each_requests_latency(engine_parts):
    cfg = engine_parts[0]
    srv = make_server(engine_parts, engine_backend="dense", backend="dense")
    srv.serve_all(*make_requests(10, 4, cfg))         # compile outside
    tok, msk, loc = make_requests(5, 6, cfg)
    s = srv.stats
    for i in range(len(tok)):
        before = dict(s.wait_s)

        async def one():
            # admitted 3 ms late, answered by the 5 ms deadline flush
            return await srv.submit(tok[i], msk[i], loc[i],
                                    t_arrival=time.perf_counter() - 3e-3)

        asyncio.run(one())
        part = {w: s.wait_s[w] - before[w] for w in server_lib.WAITS}
        assert all(v >= 0 for v in part.values()), part
        assert part["admit"] >= 3e-3
        assert part["queue"] >= 4e-3                  # the deadline timer
        assert sum(part.values()) == pytest.approx(s.latencies_s[-1],
                                                   abs=1e-3)
    m = srv.metrics()
    assert s.admitted == len(tok) and s.waited == len(tok) + 4
    assert m["admit_wait_ms"] >= 3.0 and m["queue_wait_ms"] > 0


def test_waits_sum_to_the_latencies_of_a_concurrent_batch(engine_parts):
    cfg = engine_parts[0]
    srv = make_server(engine_parts, engine_backend="dense", backend="dense")
    tok, msk, loc = make_requests(6, 10, cfg)
    srv.serve_all(tok, msk, loc)
    s = srv.stats
    assert s.waited == 10 and s.admitted == 0 and s.wait_s["admit"] == 0.0
    assert sum(s.wait_s.values()) == pytest.approx(sum(s.latencies_s),
                                                   abs=1e-3)


# ---------------------------------------------------------------------------
# The profiler changes nothing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_answers_are_bit_identical_with_the_profiler_on(engine_parts,
                                                        tmp_path, backend):
    cfg = engine_parts[0]
    eng = make_engine(engine_parts, backend)
    tok, msk, loc = make_requests(7, 10, cfg)
    off = eng.query(tok, msk, loc, k=5, cr=2, batch=4)
    on, ev = traced(tmp_path, lambda: eng.query(tok, msk, loc, k=5, cr=2,
                                                batch=4))
    assert named(ev, "repro.query")
    np.testing.assert_array_equal(on[0], off[0])
    np.testing.assert_array_equal(on[1], off[1])
