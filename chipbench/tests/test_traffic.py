"""The traffic generator: counts fixed by the mix, Poisson or on/off."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]


def serve_mix(**over):
    mix = json.loads((BENCH / "traffic" / "serve.json").read_text())
    mix.update(over)
    return mix


def window(mix, seed, seconds=10.0):
    return traffic.open_loop(mix, seconds=seconds, vocab_size=1000,
                             max_len=64, rng=np.random.default_rng(seed))


def test_every_seed_asks_for_the_same_count_and_token_lengths():
    a, b = window(serve_mix(), 1), window(serve_mix(), 2 ** 31 + 5)
    assert len(a) == len(b) == 4800
    lengths = traffic.keyword_lengths(serve_mix()["keywords"], 64)
    assert np.array_equal(a.mask.sum(1), lengths[a.keyword])
    assert np.array_equal(b.mask.sum(1), lengths[b.keyword])
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 10


def test_same_seed_same_window():
    a, b = window(serve_mix(), 7), window(serve_mix(), 7)
    assert np.array_equal(a.tokens, b.tokens)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.loc, b.loc)


def test_bursts_send_on_factor_times_the_mean_rate_while_on():
    bursts = {"period_s": 2.0, "on_share": 0.25, "on_factor": 3.0}
    req = window(serve_mix(bursts=bursts), 3)
    assert len(req) == 4800
    on = (req.due % 2.0) < 0.5
    # on spans hold a quarter of the time at 3x the mean rate: 75% of
    # arrivals; the off spans the other 25% at a third of the mean
    assert on.mean() == pytest.approx(0.75, abs=0.02)


def test_bursts_refuse_a_schedule_over_the_mean():
    bursts = {"period_s": 2.0, "on_share": 0.5, "on_factor": 3.0}
    with pytest.raises(ValueError):
        window(serve_mix(bursts=bursts), 3)
