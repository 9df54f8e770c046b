"""One run of one cell: set-up, the measured window, the check, the
result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); the configuration's ``reference`` names its
plain reference (``references/<reference>.py``) and the tower adapter
that hands the program the same tower (``towers/<reference>.py``); each
per-layer metric is read by ``metrics/<name>.py``. A configuration with
a new query tower is added with those files alone.

The contract between the harness and those files:

* a configuration's ``model`` block has ``vocab_size`` and ``max_len``
  (the traffic reads them) and ``n_clusters``; every other key belongs to
  the tower, and only its reference and adapter read it;
* a reference module exports ``init_weights(key, model)``,
  ``calibrate_router(w, tokens, mask, loc, model)``,
  ``encode(w, tokens, mask, model, precision)`` → (B, index d) in f32 at
  ``HIGHEST`` (``precision`` names the control's lower step),
  ``mixing_weights``, ``router_logits``, ``step_table``, ``score`` and
  ``query_flops(model, lengths)``, the FLOPs the query phase needs before
  the scan (``reduce.step_mfu``); it imports nothing of the program;
* a tower adapter exports ``program_config(model)``, the program's
  configuration, and ``program_params(weights)``, the reference's
  weights in the program's layout as (relevance, index, norm) params.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


def log(obj):
    """An earlier line of the run's output (one JSON object)."""
    print(json.dumps(obj), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def use_compile_cache(jax, root):
    """Keep JAX's persistent compilation cache in
    ``JAX_COMPILATION_CACHE_DIR`` when set, else at ``<root>/.jax_cache``
    (a fixed path: the path is part of the cache key), and cache every
    program, however quick to compile. → the directory."""
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cell_spec(root, name, traffic_dir=None):
    """(benchmark spec, cell, configuration file, traffic mix, the cell's
    end-to-end and per-layer metric entries). Mixes are read from
    ``traffic_dir`` (default: this package's ``traffic/``)."""
    spec = load_json(pathlib.Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(pathlib.Path(root) / conf["file"])
    mix = load_json(pathlib.Path(traffic_dir or BENCH / "traffic")
                    / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return (spec, cell, config, mix, mine(spec["end_to_end"]),
            mine(spec["per_layer"]))


def reference_module(config):
    return importlib.import_module(
        f"chipbench.references.{config['reference']}")


def metric_reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class GcClock:
    """Pauses of Python's garbage collector while ``on``."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None and self.on:
            self.pauses.append(time.perf_counter() - self._t)


class CompileClock:
    """Counts the compiles and persistent-cache loads JAX reports."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self):
        return self.compiles + self.cache_hits


def seeds(seed):
    """Independent generators for the index, the weights, the traffic,
    the warm-up traffic and the check sample, from one seed of any size."""
    root = np.random.SeedSequence(int(seed))
    return [np.random.default_rng(s) for s in root.spawn(5)]


def jax_key(jax, rng):
    return jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))


def route_spread(step_routes, n_clusters):
    """Distinct clusters routed per step and the largest cluster's share
    of all routes."""
    distinct = [len(np.unique(r)) for r in step_routes]
    flat = np.concatenate([r.reshape(-1) for r in step_routes]) \
        if step_routes else np.zeros(0, np.int64)
    top = (np.bincount(flat, minlength=n_clusters).max() / flat.size
           if flat.size else 0.0)
    return {"route_distinct_per_step": {
                "mean": float(np.mean(distinct)) if distinct else 0.0,
                "min": int(min(distinct)) if distinct else 0,
                "max": int(max(distinct)) if distinct else 0},
            "route_top_cluster_share": float(top)}


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


class Setup:
    """What set-up makes from the seed: weights, index, the program's
    searcher (with a step span around its engine) and, for an open-loop
    mix, a warmed streaming server; plus the requests of the window."""

    def __init__(self, jax, config, mix, *, seed, seconds):
        from chipbench import check as check_lib
        from chipbench import data as data_lib
        from chipbench import system
        from chipbench import traffic as traffic_lib

        self.config, self.mix = config, mix
        clock = time.perf_counter
        self.phases = {}
        t = clock()
        self.model, self.index = config["model"], config["index"]
        system.tower_adapter(config)    # a missing adapter fails here
        self.ref = reference_module(config)
        r_index, r_weights, r_traffic, r_warm, self.r_check = seeds(seed)
        model = check_lib.Frozen(self.model)
        weights = jax.jit(self.ref.init_weights, static_argnums=1)(
            jax_key(jax, r_weights), model)
        cal = config["router_calibration"]
        creq = traffic_lib.draw(cal, n=cal["queries"],
                                vocab_size=self.model["vocab_size"],
                                max_len=self.model["max_len"], rng=r_weights)
        self.weights = jax.jit(self.ref.calibrate_router, static_argnums=4)(
            weights, creq.tokens, creq.mask, creq.loc, model)
        jax.block_until_ready(self.weights)
        self.phases["weights_s"], t = clock() - t, clock()
        self.counts = data_lib.fill_counts(self.index, r_index)
        self.buffers = data_lib.draw_index(jax_key(jax, r_index), self.index,
                                           self.counts)
        jax.block_until_ready(self.buffers["emb"])
        self.phases["index_s"], t = clock() - t, clock()
        self.search = system.searcher(config, self.weights, self.buffers)
        self.spans = system.StepSpans(self.search.engine)
        vocab, max_len = self.model["vocab_size"], self.model["max_len"]
        self.server = None
        if mix["kind"] == "open_loop":
            self.req = traffic_lib.open_loop(mix, seconds=seconds,
                                             vocab_size=vocab,
                                             max_len=max_len, rng=r_traffic)
            warm = traffic_lib.draw(
                mix, n=mix["warmup_flushes"] * mix["server"]["batch_size"],
                vocab_size=vocab, max_len=max_len, rng=r_warm)
            self.server = self.search.serve(system.server_config(mix))
            system.warm_server(self.server, warm,
                               n_flushes=mix["warmup_flushes"])
        else:
            self.req = traffic_lib.closed_loop(mix, vocab_size=vocab,
                                               max_len=max_len, rng=r_traffic)
            b = mix["call_batch"]
            for j in range(mix["warmup_calls"]):
                sl = slice(j * b, (j + 1) * b)
                self.search.query(self.req.tokens[sl], self.req.mask[sl],
                                  self.req.loc[sl], k=mix["k"], cr=mix["cr"],
                                  batch=b)
        jax.effects_barrier()
        self.phases["traffic_and_warmup_s"] = clock() - t

    def free_program(self):
        """Drop the program's state (server, engine, plans); the drawn
        index and weights stay for the reference."""
        if self.server is not None:
            self.server.close()
        self.server = self.search = self.spans = None
        gc.collect()

    def reference(self, rows, *, precision="f32", lower=None):
        from chipbench import check as check_lib
        return check_lib.Reference(
            self.ref, self.weights, self.model, self.index, self.buffers,
            self.counts, self.req.tokens[rows], self.req.mask[rows],
            self.req.loc[rows], k=self.mix["k"], precision=precision,
            rows=lower)


def run(root, workload, *, seed, seconds, trace, t_process, devices, peaks,
        traffic_dir=None):
    """Run one cell once. → the result dict (the last line's object)."""
    import jax
    from chipbench import check as check_lib
    from chipbench import system
    from chipbench import trace as trace_lib

    t_run = time.time()
    spec, cell, config, mix, e2e, layer = cell_spec(root, workload,
                                                    traffic_dir)
    clock = CompileClock(jax)
    gcc = GcClock()
    su = Setup(jax, config, mix, seed=seed, seconds=seconds)
    t = time.perf_counter()
    gc.collect()
    gc.freeze()         # set-up's objects are never garbage: stop scanning
    su.phases["gc_freeze_s"] = time.perf_counter() - t
    # process start → here: interpreter, imports, TPU runtime start-up
    su.phases["start_s"] = t_run - t_process
    gcc.on = True
    model, index, req = su.model, su.index, su.req
    search, spans, server = su.search, su.spans, su.server
    counts, r_check = su.counts, su.r_check
    if server is not None:
        before = dict(server.metrics())

    # --- the window ------------------------------------------------------
    prof_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    compiles0 = clock.count()
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=options)
    spans.recording = True
    with jax.profiler.TraceAnnotation("chipbench.window"):
        if server is not None:
            t_open = time.perf_counter() + 0.01
            setup_s = time.time() - t_process + 0.01
            done = system.serve_window(server, req, t_open=t_open)
            window_s = float(np.nanmax(done["t_done"]) - t_open) \
                if np.isfinite(done["t_done"]).any() else 0.0
        else:
            setup_s = time.time() - t_process
            calls, window_s = system.bulk_window(search, req, mix,
                                                 seconds=seconds)
    spans.recording = False
    gcc.on = False
    if trace:
        jax.profiler.stop_trace()
    window_compiles = clock.count() - compiles0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # --- what the window did ----------------------------------------------
    steps = spans.steps
    fallback_rows = sum(s.rows for s in steps if s.backend is not None)
    values = {}
    if server is not None:
        after = server.metrics()
        answers = done["answer"]
        ok = np.array([isinstance(a, tuple) for a in answers])
        failed = int((~ok).sum()) + fallback_rows
        attempted = len(req)
        t_due = t_open + req.due
        latency_ms = (done["t_done"] - t_due) * 1e3
        lat_ok = latency_ms[ok]
        values["p50_ms"] = percentile(lat_ok, 50) if ok.any() else None
        values["p95_ms"] = percentile(lat_ok, 95) if ok.any() else None
        # answered over the window: what a cell offered past capacity
        # reports, where its tails swing with the backlog
        values["qps"] = int(ok.sum()) / window_s if window_s > 0 else None
        late_ms = done["late"][np.isfinite(done["late"])] * 1e3
        log({"generator_late_ms": {
                "p50": percentile(late_ms, 50), "p95": percentile(late_ms, 95),
                "max": float(late_ms.max())},
             "requests": attempted, "answered": int(ok.sum()),
             "window_s": window_s,
             "shed": sum(after["shed"].values()),
             "poisoned": after["poisoned_requests"],
             "breaker_fallback_flushes":
                 after["breaker"]["fallback_flushes"],
             "fallback_rows": fallback_rows})
        server_window = {
            "engine_queries": after["engine_queries"]
            - before["engine_queries"],
            "engine_batches": after["engine_batches"]
            - before["engine_batches"],
            "batch_size": mix["server"]["batch_size"]}
        answered = [i for i in range(attempted) if ok[i]]
        got_ids = np.stack([answers[i][0] for i in answered]) \
            if answered else np.zeros((0, mix["k"]), np.int32)
        got_scores = np.stack([answers[i][1] for i in answered]) \
            if answered else np.zeros((0, mix["k"]), np.float32)
        checked_rows = np.asarray(answered, np.int64)
        step_batch = mix["server"]["batch_size"]
    else:
        b = mix["call_batch"]
        attempted = len(calls) * b
        failed = fallback_rows
        values["qps"] = attempted / window_s
        log({"calls": len(calls), "queries": attempted,
             "window_s": window_s, "fallback_rows": fallback_rows})
        server_window = None
        got_ids = np.concatenate([c[1] for c in calls])
        got_scores = np.concatenate([c[2] for c in calls])
        checked_rows = np.concatenate([np.arange(j * b, (j + 1) * b)
                                       for j, _, _ in calls])
        step_batch = b
    values["setup_s"] = setup_s
    slow = sorted(steps, key=lambda st: st.t0 - st.t1)[:3]
    t_first = steps[0].t0 if steps else 0.0
    log({"slowest_steps": [{"at_s": st.t0 - t_first, "wall_s": st.t1 - st.t0,
                            "cpu_s": st.cpu_s, "rows": st.rows}
                           for st in slow],
         "gc_pauses": {"count": len(gcc.pauses),
                       "total_s": float(sum(gcc.pauses)),
                       "max_s": float(max(gcc.pauses, default=0.0))}})
    log({"setup_s": setup_s, "setup_phases": su.phases,
         "compiles_in_window": window_compiles,
         "compiles": clock.compiles, "compile_s": clock.seconds,
         "cache_hits": clock.cache_hits,
         "steps": len(steps), "memory_peak_bytes": peak})

    # routes of every step, by the engine's own prefix program (a bulk
    # window repeats its few distinct batches: route each once)
    memo = {}
    step_routes = []
    for st in steps:
        key = st.arrays[2].tobytes()
        if key not in memo:
            memo[key] = system.routes(search.engine, *st.arrays,
                                      cr=mix["cr"], batch=step_batch)
        step_routes.append(memo[key])
    log(route_spread(step_routes, index["n_clusters"]))

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}}
    if trace:
        tr = trace_lib.load(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)
        lo, hi = trace_lib.window_of(tr)
        ctx = {"trace": tr, "window": (lo, hi), "steps": steps,
               "step_routes": step_routes, "counts": counts, "model": model,
               "ref": su.ref,
               "index": index, "mix": mix, "peaks": peaks,
               "server_window": server_window}
        for m in layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = trace_lib.busy_ns(tr, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": trace_lib.top_ops(tr, lo, hi),
                               "idle_gaps": trace_lib.idle_gaps(tr, lo, hi)}
        log({"kernel_names": tr.kernel_names[:20],
             "trace_devices": len(tr.devices),
             "trace_ops": sum(len(d) for d in tr.devices),
             "trace_lines": tr.lines[:40],
             "top_op_stats": {name: tr.op_stats.get(name, "")
                              for name, _ in result["breakdown"]
                              ["device_ops"][:5]}})
    else:
        for m in e2e:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    # --- the check, with the program's state freed ------------------------
    del server, search, spans, steps
    su.free_program()
    pick = check_lib.sample(len(checked_rows), mix["check_sample"], r_check)
    reference = su.reference(checked_rows[pick])
    numbers = check_lib.readings(reference, got_ids[pick], got_scores[pick],
                                 cr=mix["cr"])
    correct, report = check_lib.verdict(numbers, config["check"]["limits"])
    everything = len(checked_rows) == attempted and failed == 0
    result["correct"] = bool(correct and everything and len(pick) > 0)
    result["check"] = report
    log({"checked_requests": len(pick), "answered": len(checked_rows)})
    return result
