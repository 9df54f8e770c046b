"""Run one benchmark cell once on the accelerator this process owns.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is looked up by name in
``BENCHMARK.json``. Earlier lines of standard output are JSON objects
that say what the run did (set-up time, compiles inside the window, how
late the load generator ran, the spread of the routes); the last line is
the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end metrics with ``--trace 0``, per-layer metrics read from a
profiler trace with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``check``: each number compared with the
reference beside its limit, which also end standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
program's sources (``src/repro``), the run exits non-zero and prints no
result. JAX's persistent compilation cache is kept in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache``
at the root of the checkout.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def process_start():
    """Wall-clock time this process started (from /proc), else the time
    this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(line.split()[1]) for line in f
                        if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return T_IMPORT


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_process = process_start()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import device, harness
    cache = harness.use_compile_cache(jax, ROOT)
    try:
        spec, cell, *_ = harness.cell_spec(ROOT, args.workload)
        devices, peaks = device.require(jax, cell["chips"])
    except (device.NoAccelerator, KeyError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    harness.log({"workload": args.workload, "seed": args.seed,
                 "jax": jax.__version__, "compile_cache": cache,
                 "device_kind": devices[0].device_kind})
    result = harness.run(ROOT, args.workload, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         t_process=t_process, devices=devices, peaks=peaks)
    for name, r in result["check"].items():
        print(f"check {name} {r['value']!r} limit {r['limit']!r}",
              file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
