"""Per-step reductions the per-layer metric readers share.

A step is one call into ``QueryEngine.query``: one server flush in an
open-loop cell, one batched call in a closed-loop cell. ``ctx`` is the
dict the harness hands every reader (see ``harness.run``).
Each function returns None when the run holds nothing to read.
"""
from __future__ import annotations

import numpy as np

from chipbench import device as device_lib
from chipbench import trace as trace_lib
from chipbench import work as work_lib


def steps(ctx, kind):
    """The window's steps, or None when the cell is not of ``kind``."""
    if ctx["mix"]["kind"] != kind or not ctx["steps"]:
        return None
    return ctx["steps"]


def kernel_s(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.kernel_names:
        return None
    return trace_lib.kernel_ns(tr, *ctx["window"]) / 1e9


def busy_s(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    return trace_lib.busy_ns(tr, *ctx["window"]) / 1e9


def per_step(ctx, kind):
    """(device busy ns, kernel ns) of each step, attributed by the step's
    span in the trace; None without steps or a trace."""
    tr = ctx["trace"]
    if steps(ctx, kind) is None or tr is None or not tr.devices:
        return None
    return trace_lib.step_device_ns(tr) or None


def scan_device_ms(ctx, kind):
    st = per_step(ctx, kind)
    if st is None or not ctx["trace"].kernel_names:
        return None
    return float(np.mean([k for _, k in st])) / 1e6


def nonscan_device_ms(ctx, kind):
    st = per_step(ctx, kind)
    if st is None:
        return None
    return float(np.mean([b - k for b, k in st])) / 1e6


def device_idle(ctx, kind):
    b = busy_s(ctx)
    lo, hi = ctx["window"]
    if steps(ctx, kind) is None or b is None or hi <= lo:
        return None
    return 100.0 * (1.0 - b / ((hi - lo) / 1e9))


def scan_roofline(ctx, kind):
    """Σ over steps of the least scan time the chip could take, over the
    kernels' measured time."""
    st, k = steps(ctx, kind), kernel_s(ctx)
    if st is None or not k:
        return None
    idx, peaks = ctx["index"], ctx["peaks"]
    bound = 0.0
    for routes in ctx["step_routes"]:
        flops, nbytes = work_lib.scan_work(routes, ctx["counts"], d=idx["d"],
                                           precision=idx["precision"])
        bound += work_lib.bound_seconds(
            flops, nbytes,
            peak_flops=device_lib.scan_peak(peaks, idx["precision"]),
            hbm_bytes_per_s=peaks["hbm_bytes_per_s"])
    return 100.0 * bound / k


def step_mfu(ctx, kind):
    """FLOPs the steps required (the reference's ``query_flops`` on the
    real tokens, the scan of valid rows) over the steps' wall time at the
    chip's bf16 peak."""
    st = steps(ctx, kind)
    if st is None:
        return None
    idx = ctx["index"]
    flops = 0.0
    for step, routes in zip(st, ctx["step_routes"]):
        lengths = np.asarray(step.arrays[1]).sum(-1)
        flops += ctx["ref"].query_flops(ctx["model"], lengths)
        flops += work_lib.scan_work(routes, ctx["counts"], d=idx["d"],
                                    precision=idx["precision"])[0]
    wall = sum(s.t1 - s.t0 for s in st)
    return 100.0 * flops / (wall * ctx["peaks"]["bf16_flops"])
