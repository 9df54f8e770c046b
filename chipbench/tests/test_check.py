"""The comparison that decides ``correct``, at a size a CPU holds.

The program passes; the control (the reference one precision step down)
and each fault a serving cell can have, planted underneath a whole run,
fail."""
import pathlib
import time

import pytest

import jax

from chipbench import control, harness
from repro.core import engine as engine_lib

TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def run(workload, seed, trace=False):
    return harness.run(TINY, workload, seed=seed, seconds=1.5, trace=trace,
                       t_process=time.time(), devices=jax.devices()[:1],
                       peaks=PEAKS, traffic_dir=TINY / "traffic")


@pytest.mark.parametrize("workload", ["tiny.serve", "tiny.bulk"])
def test_program_passes_and_control_fails(workload):
    _, _, config, mix, _, _ = harness.cell_spec(TINY, workload,
                                                TINY / "traffic")
    limits = config["check"]["limits"]
    for seed in (7, 2 ** 33 + 1, 12345):
        r = control.readings_for_seed(jax, config, mix, seed, 1.0)
        assert all(r["program"][k] <= limits[k] for k in limits), r
        assert any(r["control"][k] > limits[k] for k in limits), r


def _alter_one_answer(ids, scores):
    """Every request's last answer is another object's id."""
    ids = ids.copy()
    ids[:, -1] = (ids[:, -1] + 1) % 3000
    return ids, scores


def _leave_out_half(ids, scores):
    """The second half of the rows get the first half's answers."""
    ids, scores = ids.copy(), scores.copy()
    h = ids.shape[0] // 2
    if h:
        ids[h:2 * h], scores[h:2 * h] = ids[:h], scores[:h]
    return ids, scores


@pytest.mark.parametrize("workload", ["tiny.serve", "tiny.bulk"])
@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, workload,
                                                  fault):
    inner = engine_lib.QueryEngine.query

    def broken(self, *a, **kw):
        return fault(*inner(self, *a, **kw))

    monkeypatch.setattr(engine_lib.QueryEngine, "query", broken)
    r = run(workload, seed=99)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["check"].values())


def test_a_sound_run_is_correct_and_reports_every_number():
    r = run("tiny.serve", seed=5)
    assert r["correct"] is True and r["failed"] == 0
    assert list(r)[-1] == "check"
    assert set(r["check"]) == {"score_err", "route_rank_gap"}
    assert set(r["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
