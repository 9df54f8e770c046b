"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 chipbench/tests/record_fixture.py [--workload geoglue-bf16.serve]

Runs a cell's set-up and a one-second traced window on the chip, and
writes ``fixtures/trace_<workload>.json.gz``: the trace's device and
host events as ``trace.load`` keeps them, plus the reductions computed
from the trace when it was recorded.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="geoglue-bf16.serve")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import device, harness, system
    harness.use_compile_cache(jax, ROOT)
    from chipbench import trace as trace_lib
    _, cell, config, mix, _, _ = harness.cell_spec(ROOT, args.workload)
    device.require(jax, cell["chips"])
    su = harness.Setup(jax, config, mix, seed=1, seconds=args.seconds)
    prof = tempfile.mkdtemp(prefix="chipbench-fixture-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(prof, profiler_options=options)
    su.spans.recording = True
    with jax.profiler.TraceAnnotation("chipbench.window"):
        system.serve_window(su.server, su.req,
                            t_open=time.perf_counter() + 0.01)
    jax.profiler.stop_trace()
    tr = trace_lib.load(prof)
    lo, hi = trace_lib.window_of(tr)
    obj = tr.to_json()
    obj["recorded"] = {
        "window_ns": [lo, hi], "busy_ns": trace_lib.busy_ns(tr, lo, hi),
        "kernel_ns": trace_lib.kernel_ns(tr, lo, hi),
        "steps": len(su.spans.steps),
        "kernel_stats": {n: tr.op_stats.get(n, "") for n in tr.kernel_names},
        "device_kind": jax.devices()[0].device_kind}
    out = ROOT / "chipbench" / "tests" / "fixtures" / (
        f"trace_{args.workload}.json.gz")
    with gzip.open(out, "wt") as f:
        json.dump(obj, f)
    print(json.dumps({"fixture": str(out), "bytes": out.stat().st_size,
                      **{k: v for k, v in obj["recorded"].items()
                         if k != "kernel_stats"}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
