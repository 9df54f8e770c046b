"""Trace reduction: on hand-made events, and on a trace recorded on the
chip (``fixtures/trace_geoglue-bf16.serve.json.gz``,
``record_fixture.py``)."""
import gzip
import json
import pathlib

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
