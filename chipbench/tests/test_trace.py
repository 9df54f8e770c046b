"""Trace reduction: on hand-made events, and on a trace recorded on the
chip (``fixtures/trace_geoglue-bf16.serve.json.gz``,
``record_fixture.py``)."""
import gzip
import json
import pathlib

import numpy as np
import pytest

from chipbench import trace as trace_lib

FIXTURE = (pathlib.Path(__file__).resolve().parent / "fixtures"
           / "trace_geoglue-bf16.serve.json.gz")


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 10), (20, 5), (30, 0)]
    assert trace_lib.union_ns(iv) == 20
    assert trace_lib.union_ns(iv, lo=8, hi=22) == 9


def hand_trace():
    dev = [("fusion.1", 0, 10), ("k.1", 10, 30), ("fusion.2", 60, 10),
           ("k.1", 100, 20)]
    host = [("chipbench.window", 0, 130), ("chipbench.step", 0, 45),
            ("np.asarray(jax.Array)", 70, 25)]
    return trace_lib.Trace([dev], host, ["k.1"])


def test_busy_kernel_top_ops_and_gaps_on_hand_events():
    tr = hand_trace()
    lo, hi = trace_lib.window_of(tr)
    assert (lo, hi) == (0, 130)
    assert trace_lib.busy_ns(tr, lo, hi) == 70
    assert trace_lib.kernel_ns(tr, lo, hi) == 50
    assert trace_lib.top_ops(tr, lo, hi)[0] == ["k.1", 50e-9]
    gaps = trace_lib.idle_gaps(tr, lo, hi)
    # gaps: 40-60 (host in the step span? no: step ends at 45 → window),
    # 70-100 (host waiting on a transfer), 120-130 (window)
    assert [g[0] for g in gaps] == ["np.asarray(jax.Array)",
                                    "chipbench.window", "chipbench.window"]
    assert sum(g[1] for g in gaps) == pytest.approx(60e-9)
    # the one step span [0, 45) owns fusion.1 and the first kernel call
    assert trace_lib.step_device_ns(tr) == [(40, 30)]


def test_kernel_detection_is_the_mosaic_custom_call_only():
    assert trace_lib.is_kernel(
        '%q = f32[] custom-call(x), custom_call_target="tpu_custom_call"')
    assert not trace_lib.is_kernel(
        '%c = s32[] custom-call(x), custom_call_target="ConcatBitcast"')
    assert trace_lib.op_name("%fusion.3 = bf16[2] fusion(x)") == "fusion.3"


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_chip_trace_reduces_as_when_recorded():
    with gzip.open(FIXTURE, "rt") as f:
        obj = json.load(f)
    tr = trace_lib.Trace.from_json(obj)
    rec = obj["recorded"]
    lo, hi = trace_lib.window_of(tr)
    assert [lo, hi] == rec["window_ns"]
    busy = trace_lib.busy_ns(tr, lo, hi)
    kern = trace_lib.kernel_ns(tr, lo, hi)
    assert busy == pytest.approx(rec["busy_ns"])
    assert kern == pytest.approx(rec["kernel_ns"])
    assert 0 < kern <= busy <= hi - lo
    assert tr.kernel_names and all(
        trace_lib.is_kernel(rec["kernel_stats"][n]) for n in tr.kernel_names)
    assert len(trace_lib.step_windows(tr)) == rec["steps"]
    gaps = trace_lib.idle_gaps(tr, lo, hi)
    assert sum(g[1] for g in gaps) <= (hi - lo - busy) / 1e9 + 1e-9
    # every device operation of the window runs inside some step's span
    per_step = trace_lib.step_device_ns(tr)
    assert sum(b for b, _ in per_step) == pytest.approx(busy)
    assert sum(k for _, k in per_step) == pytest.approx(kern)


def _step_device_ns_by_union(trace):
    """Per step, the union of every device's operations clipped to the
    step, recomputed for each step: what ``step_device_ns`` returned
    before it merged each device's operations once."""
    out = []
    names = set(trace.kernel_names)
    k = max(len(trace.devices), 1)
    for a, b in trace_lib.step_windows(trace):
        busy = kern = 0
        for dev in trace.devices:
            busy += trace_lib.union_ns([(e[1], e[2]) for e in dev], a, b)
            kern += trace_lib.union_ns(
                [(e[1], e[2]) for e in dev if e[0] in names], a, b)
        out.append((busy / k, kern / k))
    return out


def overlapping_trace(seed):
    """Two devices of overlapping, nested, touching and empty operations,
    and steps that overlap each other, touch operations' ends, are empty
    or lie outside every operation."""
    rng = np.random.default_rng(seed)
    devices = []
    for _ in range(2):
        starts = np.sort(rng.integers(0, 5000, 400))
        ops = [(str(rng.choice(["k.1", "fusion.1", "k.2", "copy.1"])),
                int(s), int(rng.choice([0, 1, 5, 40, 300])))
               for s in starts]
        ops += [("k.1", 100, 50), ("fusion.1", 150, 50), ("k.2", 120, 10)]
        devices.append(sorted(ops, key=lambda e: e[1]))
    steps = [("chipbench.step", int(a), int(d)) for a, d in zip(
        rng.integers(-200, 5600, 60), rng.choice([0, 1, 50, 400, 3000], 60))]
    steps += [("chipbench.step", 150, 50), ("chipbench.step", 200, 0),
              ("chipbench.step", 6000, 100)]
    host = sorted([("chipbench.window", -500, 7000)] + steps,
                  key=lambda e: e[1])
    return trace_lib.Trace(devices, host, ["k.1", "k.2"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_step_device_ns_equals_the_union_per_step_on_overlaps(seed):
    tr = overlapping_trace(seed)
    got = trace_lib.step_device_ns(tr)
    assert len(got) == 63
    assert got == _step_device_ns_by_union(tr)


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_step_device_ns_equals_the_union_per_step_on_the_chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        tr = trace_lib.Trace.from_json(json.load(f))
    got = trace_lib.step_device_ns(tr)
    assert got and got == _step_device_ns_by_union(tr)
