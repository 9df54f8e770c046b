"""Find the highest open-loop rate a serve cell sustains: one set-up, one
short window per rate, in one process that owns the chip.

    python3 chipbench/sweep.py --workload <serve cell> --rates 400,500,600 \
        [--seed 1] [--seconds 10]

Per rate it prints one JSON line: offered and completed rates, p50/p95
latency from the due time, the mean latency of the window's last tenth
over its first tenth (a backlog that grows drives it far above 1), and
how late the generator ran. The benchmark's own runs never sweep: the
rate a cell offers is fixed in its traffic mix.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import device, harness, system, traffic
    harness.use_compile_cache(jax, ROOT)

    _, cell, config, mix, _, _ = harness.cell_spec(ROOT, args.workload)
    device.require(jax, cell["chips"])
    su = harness.Setup(jax, config, mix, seed=args.seed,
                       seconds=args.seconds)
    rng = np.random.default_rng(args.seed + 1)
    for rate in (float(r) for r in args.rates.split(",")):
        m = {**mix, "rate_qps": rate}
        req = traffic.open_loop(m, seconds=args.seconds,
                                vocab_size=config["model"]["vocab_size"],
                                max_len=config["model"]["max_len"], rng=rng)
        t_open = time.perf_counter() + 0.01
        done = system.serve_window(su.server, req, t_open=t_open)
        lat = (done["t_done"] - (t_open + req.due)) * 1e3
        ok = np.isfinite(lat)
        tenth = max(1, len(req) // 10)
        print(json.dumps({
            "rate_qps": rate, "requests": len(req),
            "answered": int(ok.sum()),
            "completed_qps": float(ok.sum() / (np.nanmax(done["t_done"])
                                              - t_open)),
            "p50_ms": float(np.percentile(lat[ok], 50)),
            "p95_ms": float(np.percentile(lat[ok], 95)),
            "backlog_ratio": float(np.nanmean(lat[-tenth:])
                                   / np.nanmean(lat[:tenth])),
            "late_p95_ms": float(np.nanpercentile(done["late"], 95) * 1e3)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
