"""One traced run of a cell, with the per-layer readings that need more
than the harness hands its metric readers.

    python3 chipbench/layers.py --workload <cell> --seed <n> [--seconds 30] [--fixture]

It runs the cell once as ``run.py --workload <cell> --trace 1`` does
(``harness.run``, whose result line it prints first), keeping on the
side what that run holds but does not hand its readers, and then prints
one JSON line of:

* ``scopes_ms``: device ms per step under each of the program's device
  scopes (``tower``, ``route``, ``scan`` less the Mosaic kernel and the
  side buffers' ``relayout``, ``merge``), the unscoped rest (``None``)
  and the kernel, beside
  ``nonscan`` (busy less the kernel, as ``nonscan_device_ms`` reads it),
  which the scopes and the rest add up to. Ops go to their scopes
  through the device plane's module line and the compiled text of the
  plans the window ran;
* ``counters``: the engine's encoder passes per step; in a serve cell
  the server's request waits over the window (mean ms of admit, queue,
  flush, resume) beside the mean latency they add up to;
* ``traced``: the window's end-to-end numbers with the tracer on.

``--fixture`` also writes ``chiprun_out/trace_scopes_<workload>.json.gz``:
about the first second of steps of the window's trace, with the module
executions and scope maps, for ``tests/test_program_trace.py``.
"""
from __future__ import annotations

import argparse
import collections
import gzip
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
CATEGORIES = ("tower", "route", "scan", "relayout", "merge", None, "kernel")


def plan_maps(jax, engine, mix, batch):
    """{module name: [instruction → scope map]} of the plans the cell's
    window can run, lowered with the snapshot's arrays as the engine
    calls them, so each compile is the one the window ran. A server
    warms both scans and the route program (``system.warm_server``); a
    bulk call's structural pick runs the cluster-major scan alone."""
    from chipbench import program_trace
    snap = engine.snapshot
    buf = snap.buffers
    length = engine.cfg.max_len
    q = (jax.numpy.asarray(np.zeros((batch, length), np.int32)),
         jax.numpy.asarray(np.zeros((batch, length), bool)),
         jax.numpy.asarray(np.zeros((batch, 2), np.float32)))
    serve = mix["kind"] == "open_loop"
    lowered = []
    for backend in ("pallas-cm", "pallas") if serve else ("pallas-cm",):
        fn = engine.query_fn(k=mix["k"], cr=mix["cr"], backend=backend,
                             batch=batch)
        lowered.append(fn.lower(snap.rel_params, snap.index_params,
                                snap.w_hat, snap.norm, buf["emb"],
                                buf["loc"], buf["ids"], buf["scale"], *q))
    if serve:
        lowered.append(engine.route_fn(cr=mix["cr"]).lower(
            snap.rel_params, snap.index_params, snap.norm, *q))
    maps = {}
    for low in lowered:
        text = low.compile().as_text()
        name = text.split(None, 2)[1].rstrip(",")    # "HloModule <name>,"
        maps.setdefault(name, []).append(
            program_trace.instruction_scopes(text))
    return maps


def scope_means(scoped, windows, step_ns):
    """Mean device ms per step of each category, ``nonscan`` from the
    harness's own attribution (``trace.step_device_ns``), and the widest
    gap of any step between the two."""
    from chipbench import program_trace
    per = program_trace.step_scope_ns(scoped, windows)
    out = {str(c): float(np.mean([p.get(c, 0) for p in per])) / 1e6
           for c in CATEGORIES}
    nonscan = [b - k for b, k in step_ns]
    out["nonscan"] = float(np.mean(nonscan)) / 1e6
    out["max_step_gap_ms"] = max(
        (abs(sum(v for c, v in p.items() if c != "kernel") - n) / 1e6
         for p, n in zip(per, nonscan)), default=0.0)
    return out


def reduce_fixture(obj):
    """What the fixture test recomputes from a fixture object: per-step
    scope ms, idle ms per program span, dispatches per span."""
    from chipbench import program_trace
    from chipbench import trace as trace_lib
    tr = trace_lib.Trace.from_json(obj)
    modules = [[tuple(m) for m in dev] for dev in obj["modules"]]
    scoped = program_trace.op_scopes(tr, modules, obj["maps"])
    windows = trace_lib.step_windows(tr)
    window = trace_lib.window_of(tr)
    span = "repro.flush" if tr.spans("repro.flush") else "repro.query"
    return {"steps": len(windows),
            "scopes_ms": scope_means(scoped, windows,
                                     trace_lib.step_device_ns(tr)),
            "step_idle_ms": program_trace.span_idle_ms(tr, window, span),
            "dispatches_per_step": program_trace.spans_within(
                tr, window, "repro.dispatch", span)}


def cut_fixture(tr, modules, maps, lo, hi):
    """The trace between ``lo`` and ``hi`` as a fixture object, with a
    ``chipbench.window`` span over the cut."""
    kept = [[m for m in dev if lo <= m[1] < hi] for dev in modules]
    names = {m[0] for dev in kept for m in dev}
    return {"devices": [[e for e in dev if lo <= e[1] < hi]
                        for dev in tr.devices],
            "host": [("chipbench.window", lo, hi - lo)]
            + [e for e in tr.host if lo <= e[1] < hi],
            "kernel_names": sorted(tr.kernel_names),
            "modules": kept,
            "maps": {n: v for n, v in maps.items() if n in names}}


class Tap:
    """What one ``harness.run`` holds and does not hand its readers,
    kept by wrapping the benchmark's own entry points for the run."""

    def __init__(self, jax, mix):
        from chipbench import harness, system
        from chipbench import program_trace
        from chipbench import trace as trace_lib
        self.jax, self.mix = jax, mix
        self.engine = self.server = self.done = self.t_open = None
        self.bulk = self.trace = self.ctx = self.maps = None
        self.counters = {}
        serve_window, bulk_window = system.serve_window, system.bulk_window
        load, reader = trace_lib.load, harness.metric_reader

        def tap_serve(server, req, *, t_open):
            self.engine, self.server = server.engine, server
            self.t_open = t_open
            before = self._counts()
            self.done = serve_window(server, req, t_open=t_open)
            self._since(before)
            self.due = req.due
            return self.done

        def tap_bulk(search, req, mix, *, seconds):
            self.engine = search.engine
            before = self._counts()
            self.bulk = bulk_window(search, req, mix, seconds=seconds)
            self._since(before)
            return self.bulk

        def tap_load(profile_dir):
            self.trace = load(profile_dir)
            self.modules = program_trace.load_modules(profile_dir)
            return self.trace

        def tap_reader(name):
            inner = reader(name)

            def read(ctx):
                if self.maps is None:       # the program is still alive
                    self.ctx = ctx
                    self.maps = plan_maps(self.jax, self.engine, self.mix,
                                          self._batch())
                return inner(ctx)
            return read

        system.serve_window, system.bulk_window = tap_serve, tap_bulk
        trace_lib.load, harness.metric_reader = tap_load, tap_reader

    def _batch(self):
        return (self.mix["server"]["batch_size"] if self.server is not None
                else self.mix["call_batch"])

    def _counts(self):
        out = {"passes": self.engine.stats["encoder_passes"]}
        if self.server is not None:
            s = self.server.stats
            out.update(wait_s=dict(s.wait_s), waited=s.waited,
                       admitted=s.admitted, batches=s.engine_batches,
                       server_passes=s.encoder_passes,
                       latencies=len(s.latencies_s))
        return out

    def _since(self, b):
        from repro.core import server as server_lib
        a = self._counts()
        c = self.counters
        c["encoder_passes"] = a["passes"] - b["passes"]
        if self.server is None:
            return
        s = self.server.stats
        n, admitted = a["waited"] - b["waited"], a["admitted"] - b["admitted"]
        for w in server_lib.WAITS:
            c[f"{w}_wait_ms"] = 1e3 * (a["wait_s"][w] - b["wait_s"][w]) / max(
                admitted if w == "admit" else n, 1)
        c["waits_sum_ms"] = sum(c[f"{w}_wait_ms"] for w in server_lib.WAITS)
        lat = list(s.latencies_s)[b["latencies"]:]
        c["server_mean_latency_ms"] = 1e3 * float(np.mean(lat))
        c["flushes"] = a["batches"] - b["batches"]
        c["encoder_passes_per_flush"] = (
            (a["server_passes"] - b["server_passes"]) / max(c["flushes"], 1))
        c["requests_waited"], c["requests_admitted"] = n, admitted

    def readings(self):
        """The side readings of the run, as one dict."""
        from chipbench import program_trace
        from chipbench import trace as trace_lib
        tr, steps = self.trace, self.ctx["steps"]
        out = {"counters": dict(self.counters)}
        out["counters"]["encoder_passes_per_step"] = (
            self.counters["encoder_passes"] / max(len(steps), 1))
        if self.done is not None:
            ok = np.array([isinstance(a, tuple) for a in self.done["answer"]])
            lat = (self.done["t_done"] - (self.t_open + self.due))[ok] * 1e3
            out["traced"] = {"p50_ms": float(np.percentile(lat, 50)),
                             "p95_ms": float(np.percentile(lat, 95)),
                             "client_mean_latency_ms": float(np.mean(lat)),
                             "flush_call_ms": 1e3 * float(np.mean(
                                 [s.t1 - s.t0 for s in steps]))}
        else:
            calls, window_s = self.bulk
            out["traced"] = {"qps": len(calls) * self.mix["call_batch"]
                             / window_s}
        scoped = program_trace.op_scopes(tr, self.modules, self.maps)
        windows = trace_lib.step_windows(tr)
        out["scopes_ms"] = scope_means(scoped, windows,
                                       trace_lib.step_device_ns(tr))
        rest = collections.Counter()
        for dev, cats in zip(tr.devices[:1], scoped[:1]):
            for (name, _, d), (cat, _, _) in zip(dev, cats):
                if cat is None:
                    rest[name] += d
        out["unscoped_ops_ms_per_step"] = [
            [n, ns / 1e6 / max(len(windows), 1)]
            for n, ns in rest.most_common(8)]
        out["modules"] = {
            "executions": sum(len(m) for m in self.modules),
            "names": collections.Counter(
                m[0] for dev in self.modules for m in dev).most_common(8),
            "maps": {n: [len(m) for m in v] for n, v in self.maps.items()}}
        return out

    def fixture(self, seconds=1.0):
        """About ``seconds`` of whole steps from the window's start, as a
        fixture object with what it reduced to when recorded."""
        from chipbench import trace as trace_lib
        windows = trace_lib.step_windows(self.trace)
        first = [w for w in windows if w[1] <= windows[0][0] + seconds * 1e9]
        obj = cut_fixture(self.trace, self.modules, self.maps,
                          first[0][0] - 1, first[-1][1] + 1)
        obj["recorded"] = reduce_fixture(obj)
        return obj


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fixture", action="store_true")
    args = ap.parse_args(argv)
    t_process = time.time()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import device, harness
    harness.use_compile_cache(jax, ROOT)
    _, cell, _, mix, _, _ = harness.cell_spec(ROOT, args.workload)
    devices, peaks = device.require(jax, cell["chips"])
    tap = Tap(jax, mix)
    result = harness.run(ROOT, args.workload, seed=args.seed,
                         seconds=args.seconds, trace=True,
                         t_process=t_process, devices=devices, peaks=peaks)
    print(json.dumps(result), flush=True)
    out = {"workload": args.workload, "seed": args.seed,
           "device_kind": devices[0].device_kind, **tap.readings()}
    if args.fixture:
        obj = tap.fixture()
        obj["recorded"]["device_kind"] = devices[0].device_kind
        path = ROOT / "chiprun_out" / f"trace_scopes_{args.workload}.json.gz"
        path.parent.mkdir(exist_ok=True)
        with gzip.open(path, "wt") as f:
            json.dump(obj, f)
        out["fixture"] = {"path": str(path), "bytes": path.stat().st_size,
                          "recorded": obj["recorded"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
