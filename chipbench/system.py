"""The system under test, driven through its own entry points.

This module and the tower adapters (``towers/<reference>.py``) are the
only modules of a run that import the program (``repro``). It hands the
benchmark's weights and buffers to ``IndexSnapshot.from_parts``, through
the configuration's tower adapter, serves through ``Searcher.serve``
(``StreamingServer.submit``) or ``Searcher.query``, and records a host
span around every call the server or the window makes into
``QueryEngine.query``. Nothing here depends on the tower.
"""
from __future__ import annotations

import asyncio
import dataclasses
import importlib
import math
import pathlib
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


def tower_adapter(config):
    """The module that hands the program the tower of the configuration's
    reference: ``towers/<reference>.py``. Raises ``LookupError``, naming
    the file to add, when there is none."""
    name = config["reference"]
    if not (BENCH / "towers" / f"{name}.py").is_file():
        raise LookupError(
            f"no tower adapter for reference {name!r}: add "
            f"chipbench/towers/{name}.py with program_config(model) and "
            "program_params(weights)")
    return importlib.import_module(f"chipbench.towers.{name}")


def searcher(config, weights, buffers):
    """The program's ``Searcher`` over the benchmark's weights and index,
    its tower built by the configuration's tower adapter."""
    from repro import api
    from repro.core.snapshot import IndexSnapshot
    tower = tower_adapter(config)
    snap = IndexSnapshot.from_parts(tower.program_config(config["model"]),
                                    *tower.program_params(weights), buffers,
                                    dist_max=math.sqrt(2.0))
    return api.Searcher(snap)


@dataclasses.dataclass
class Step:
    """One call into ``QueryEngine.query``: host start/end (perf_counter),
    the process's CPU seconds during it, the rows it answered, the
    backend the caller asked for, and the request arrays, for counting
    the work afterwards."""
    t0: float
    t1: float
    cpu_s: float
    rows: int
    backend: object
    arrays: tuple


class StepSpans:
    """Wraps one engine's ``query`` so every call is timed as a step, and
    annotated in the profiler's trace when one is being taken."""

    def __init__(self, engine):
        import jax
        self.engine = engine
        self.steps = []
        self.recording = False
        self._inner = engine.query
        self._annotation = jax.profiler.TraceAnnotation
        engine.query = self._query

    def _query(self, q_tokens, q_mask, q_loc, **kw):
        if not self.recording:
            return self._inner(q_tokens, q_mask, q_loc, **kw)
        t0, c0 = time.perf_counter(), time.process_time()
        with self._annotation("chipbench.step"):
            out = self._inner(q_tokens, q_mask, q_loc, **kw)
        t1, c1 = time.perf_counter(), time.process_time()
        arrays = (np.array(q_tokens), np.array(q_mask), np.array(q_loc))
        self.steps.append(Step(t0, t1, c1 - c0,
                               int(np.asarray(q_tokens).shape[0]),
                               kw.get("backend"), arrays))
        return out


def server_config(mix):
    from repro.core import server as server_lib
    return server_lib.ServerConfig(
        batch_size=mix["server"]["batch_size"],
        max_delay_ms=mix["server"]["max_delay_ms"],
        k=mix["k"], cr=mix["cr"], backend=None)


async def _open_loop(server, req, t_open, done):
    """Submit each request at its due time, whatever has completed;
    stamp each with its due time. Fills ``done``: per request the answer
    (or the exception), completion time and how late the submit ran."""

    async def one(i, due):
        done["late"][i] = time.perf_counter() - due
        try:
            done["answer"][i] = await server.submit(
                req.tokens[i], req.mask[i], req.loc[i], t_arrival=due)
        except Exception as e:  # noqa: BLE001 - every failure is counted
            done["answer"][i] = e
        done["t_done"][i] = time.perf_counter()

    tasks = []
    for i in range(len(req)):
        due = t_open + req.due[i]
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(i, due)))
    await asyncio.sleep(0)
    server.flush_now()
    await asyncio.wait(tasks, timeout=60.0)
    for t in tasks:
        t.cancel()


def serve_window(server, req, *, t_open):
    """Run an open-loop window. → per-request answer, latency and
    lateness, with the due time as the start of every latency."""
    n = len(req)
    done = {"answer": [None] * n, "t_done": np.full(n, np.nan),
            "late": np.full(n, np.nan)}
    asyncio.run(_open_loop(server, req, t_open, done))
    return done


def warm_server(server, req, *, n_flushes):
    """Warm every program a live flush runs (the route prefix the auto
    pick measures with, and both scan variants) on real requests, full
    flushes and a partial last one."""
    server.warmup()
    n = min(len(req), n_flushes * server.cfg.batch_size
            - server.cfg.batch_size // 2)
    server.serve_all(req.tokens[:n], req.mask[:n], req.loc[:n])


def bulk_window(search, req, mix, *, seconds):
    """Back-to-back ``Searcher.query`` calls, one in flight, cycling the
    distinct batches until ``seconds`` have passed. → (calls as
    (batch index, ids, scores), wall seconds)."""
    b = mix["call_batch"]
    calls = []
    t0 = time.perf_counter()
    while True:
        j = len(calls) % mix["distinct_calls"]
        sl = slice(j * b, (j + 1) * b)
        ids, scores = search.query(req.tokens[sl], req.mask[sl], req.loc[sl],
                                   k=mix["k"], cr=mix["cr"], batch=b)
        calls.append((j, ids, scores))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return calls, elapsed


def routes(engine, tokens, mask, loc, *, cr, batch):
    """Clusters each row was routed to, by the engine's own prefix
    program at the step's batch shape."""
    from repro.core import engine as engine_lib
    snap = engine.snapshot
    pre = engine.prefix_fn(cr=cr)
    _, _, top_c = engine_lib.run_batched(
        lambda t, m, l: pre(snap.rel_params, snap.index_params, snap.norm,
                            t, m, l),
        [tokens, mask, loc], batch=batch)
    return np.asarray(top_c)
