"""Reductions of the program's own spans and scopes: on hand-made events,
and on a trace recorded on the chip with its module executions and scope
maps (``fixtures/trace_scopes_geoglue-bf16.serve.json.gz``,
``layers.py --fixture``)."""
import gzip
import json
import pathlib

import pytest

from chipbench import harness, layers
from chipbench import program_trace as pt
from chipbench import trace as trace_lib

FIXTURE = (pathlib.Path(__file__).resolve().parent / "fixtures"
           / "trace_scopes_geoglue-bf16.serve.json.gz")


def hand_trace():
    # one flush [0, 100): route program [10, 30), query program [40, 90)
    # whose layer loop (while, 40-60) holds two body ops, then the kernel
    dev = [("convert.1", 10, 5), ("fusion.2", 15, 15),
           ("while", 40, 20), ("fusion.7", 42, 6), ("fusion.8", 50, 6),
           ("copy.3", 60, 5), ("k.1", 65, 20), ("fusion.9", 85, 5),
           ("cumsum.1", 95, 3)]
    host = [("chipbench.window", 0, 120), ("chipbench.step", 0, 100),
            ("repro.flush", 0, 100), ("repro.query", 2, 96),
            ("repro.dispatch", 3, 2), ("repro.dispatch", 35, 2),
            ("repro.sync", 31, 4), ("repro.dispatch", 105, 2)]
    tr = trace_lib.Trace([dev], host, ["k.1"])
    modules = [[("jit_route_fn", 10, 20), ("jit_query_fn", 40, 50),
                ("jit_cumsum", 95, 3)]]
    maps = {"jit_route_fn": [{"convert.1": "tower", "fusion.2": "route"}],
            "jit_query_fn": [
                {"while": "merge"},          # a plan that did not run
                {"while": "tower", "fusion.7": "tower", "fusion.8": "tower",
                 "copy.3": "scan", "k.1": "scan", "fusion.9": "merge"}]}
    return tr, modules, maps


def test_idle_and_dispatches_per_flush_on_hand_events():
    tr, _, _ = hand_trace()
    window = trace_lib.window_of(tr)
    # flush [0, 100): busy 10-30, 40-90, 95-98 → idle 100 - 73
    assert pt.span_idle_ms(tr, window, "repro.flush") == pytest.approx(27e-6)
    assert pt.spans_within(tr, window, "repro.dispatch",
                           "repro.flush") == 2
    assert pt.span_idle_ms(tr, window, "repro.pick") is None
    assert pt.spans_within(tr, window, "repro.dispatch", "nothing") is None


@pytest.mark.parametrize("spans", [True, False])
def test_metric_readers_read_the_spans_and_nothing_without_them(spans):
    tr, _, _ = hand_trace()
    if not spans:                        # a program older than its spans
        tr.host = [e for e in tr.host if not e[0].startswith("repro.")]
    window = trace_lib.window_of(tr)
    ctx = {"trace": tr, "window": window, "steps": [object()],
           "mix": {"kind": "open_loop"}}
    got = {n: harness.metric_reader(n)(ctx)
           for n in ("step_idle_ms.serve", "encoder_passes.serve",
                     "step_idle_ms.bulk")}
    assert got == ({"step_idle_ms.serve": pytest.approx(27e-6),
                    "encoder_passes.serve": 2, "step_idle_ms.bulk": None}
                   if spans else dict.fromkeys(got))
    ctx["mix"] = {"kind": "closed_loop"}
    # a bulk step's span is the call's: repro.query [2, 98), 73 busy
    idle = harness.metric_reader("step_idle_ms.bulk")(ctx)
    assert idle == (pytest.approx(23e-6) if spans else None)


def test_each_op_takes_the_scope_of_its_module_and_innermost_time():
    tr, modules, maps = hand_trace()
    scoped = pt.op_scopes(tr, modules, maps)
    assert [c for c, _, _ in scoped[0]] == [
        "tower", "route", "tower", "tower", "tower", "scan", "kernel",
        "merge", None]
    per, = pt.step_scope_ns(scoped, trace_lib.step_windows(tr))
    # the loop owns 40-60 less its body ops; copy.3 is scan prep
    assert per == {"tower": 25, "route": 15, "scan": 5, "kernel": 20,
                   "merge": 5, None: 3}
    busy, kern = trace_lib.step_device_ns(tr)[0]
    assert sum(per.values()) == busy and per["kernel"] == kern


def test_instruction_scopes_read_the_innermost_named_scope():
    text = """HloModule jit_query_fn, is_scheduled=true
ENTRY %main.1 (p.1: f32[8], w.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="q_tokens"}
  %w.1 = f32[8]{0} parameter(1), metadata={op_name="rel_params"}
  %convert.2 = bf16[8]{0} convert(%w.1)
  %fusion.3 = f32[8]{0} fusion(%p.1, %convert.2), kind=kLoop, """ \
        """calls=%fused_computation, metadata={op_name=""" \
        """"jit(query_fn)/tower/while/body/dot_general" stack_frame_id=2}
  %transpose.1 = f32[8]{0} transpose(%p.1), """ \
        """metadata={op_name="jit(query_fn)/scan/relayout/transpose"}
  %sort.2 = f32[8]{0} sort(%fusion.3), """ \
        """metadata={op_name="jit(query_fn)/scan/merge/top_k"}
  %copy.4 = f32[8]{0} copy(%sort.2)
  ROOT %add.5 = f32[8]{0} add(%copy.4, %transpose.1), """ \
        """metadata={op_name="jit(query_fn)/add"}
}
"""
    assert pt.instruction_scopes(text) == {
        "fusion.3": "tower", "transpose.1": "relayout", "sort.2": "merge",
        "add.5": None,
        # no metadata of their own: the scope of their users
        "convert.2": "tower", "w.1": "tower", "p.1": "tower",
        "copy.4": None}
    assert pt.module_name("jit_query_fn(2916835188384110637)") == \
        "jit_query_fn"


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_scopes_add_up_to_the_non_kernel_device_time():
    with gzip.open(FIXTURE, "rt") as f:
        obj = json.load(f)
    rec = obj["recorded"]
    got = layers.reduce_fixture(obj)
    assert got["steps"] == rec["steps"] > 0
    assert got["scopes_ms"] == pytest.approx(rec["scopes_ms"])
    assert got["step_idle_ms"] == pytest.approx(rec["step_idle_ms"])
    ms = got["scopes_ms"]
    # tower + route + scan prep + merge + unscoped = nonscan, step by step
    assert ms["max_step_gap_ms"] < 1e-6
    assert sum(ms[c] for c in ("tower", "route", "scan", "relayout",
                               "merge", "None")) \
        == pytest.approx(ms["nonscan"])
    assert ms["tower"] > 0 and ms["route"] > 0 and ms["scan"] > 0
    assert ms["None"] < 0.05 * ms["nonscan"]
    # the auto pick's route encode, then the plan's: two tower passes
    assert got["dispatches_per_step"] == pytest.approx(2.0)
    assert 0 < got["step_idle_ms"]
