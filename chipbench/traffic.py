"""The one traffic generator: a mix file's parameters + a seed → requests.

A mix file (``traffic/<name>.json``) holds only parameters:

``kind``
    ``"open_loop"`` — requests due at Poisson arrival times at
    ``rate_qps`` through the streaming server; or ``"closed_loop"`` —
    back-to-back batched calls of ``call_batch`` queries, one in flight.
``k``, ``cr``
    results per query and clusters routed per query.
``server``
    streaming-server settings (``batch_size``, ``max_delay_ms``) of an
    open-loop mix.
``keywords``
    ``pool`` distinct keyword strings drawn Zipf(``zipf_s``); each
    string's length, CLS included, is log-normal with median
    ``len_median`` and shape ``len_sigma``, clipped to
    ``[len_min, max_len]``. Lengths follow the string's popularity rank
    from a fixed template, so every seed asks for the same token counts.
``locations``
    ``hotspots`` centres uniform in the unit box, picked Zipf(``zipf_s``);
    a request lies ``N(0, sigma)`` around its centre, clipped to the box.
    ``hotspots: 0`` draws locations uniform in the box.
``bursts`` (optional, open loop)
    on/off load: for the first ``on_share`` of every ``period_s`` the
    arrival rate is ``on_factor`` times ``rate_qps``, for the rest of
    the period the rate that keeps the mean at ``rate_qps``.
``check_sample``
    requests whose answers are compared with the reference.
``assumed``
    where each parameter comes from; read by no code.

Every seed gives the same number of requests: an open-loop window of
``seconds`` holds ``round(rate_qps * seconds)`` arrivals, placed as a
Poisson process (modulated by ``bursts``) conditioned on that count.
"""
from __future__ import annotations

import dataclasses

import numpy as np

CLS = 1          # token id of the leading CLS token; 0 is padding


@dataclasses.dataclass
class Requests:
    tokens: np.ndarray        # (n, L) int32
    mask: np.ndarray          # (n, L) bool
    loc: np.ndarray           # (n, 2) float32
    due: np.ndarray           # (n,) seconds after the window opens
    keyword: np.ndarray       # (n,) keyword rank

    def __len__(self):
        return self.tokens.shape[0]


def zipf_probs(n, s):
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return p / p.sum()


def keyword_lengths(kw, max_len):
    """Token count (CLS included) of each keyword rank: a fixed template."""
    rng = np.random.default_rng(1)
    raw = np.exp(np.log(kw["len_median"])
                 + kw["len_sigma"] * rng.standard_normal(kw["pool"]))
    return np.clip(np.rint(raw), kw["len_min"], max_len).astype(np.int64)


def draw(mix, *, n, vocab_size, max_len, rng):
    """``n`` requests of ``mix`` (content only; ``due`` left at 0)."""
    kw, lc = mix["keywords"], mix["locations"]
    lengths = keyword_lengths(kw, max_len)
    ranks = rng.choice(kw["pool"], size=n, p=zipf_probs(kw["pool"],
                                                         kw["zipf_s"]))
    # each distinct keyword's tokens, drawn once per seed
    uniq, inv = np.unique(ranks, return_inverse=True)
    words = rng.integers(2, vocab_size, (uniq.size, max_len), dtype=np.int64)
    pos = np.arange(max_len)
    words = np.where(pos[None] < lengths[uniq][:, None], words, 0)
    words[:, 0] = CLS
    tokens = words[inv].astype(np.int32)
    if lc["hotspots"]:
        centres = rng.uniform(0.0, 1.0, (lc["hotspots"], 2))
        spot = rng.choice(lc["hotspots"], size=n,
                          p=zipf_probs(lc["hotspots"], lc["zipf_s"]))
        loc = centres[spot] + lc["sigma"] * rng.standard_normal((n, 2))
    else:
        loc = rng.uniform(0.0, 1.0, (n, 2))
    loc = np.clip(loc, 0.0, 1.0).astype(np.float32)
    return Requests(tokens, tokens != 0, loc, np.zeros(n), ranks)


def burst_knots(bursts, seconds):
    """(times, cumulative arrival share) at the edges of the on and off
    spans of a ``bursts`` schedule over ``[0, seconds]``."""
    period, share = bursts["period_s"], bursts["on_share"]
    on = bursts["on_factor"]
    off = (1.0 - share * on) / (1.0 - share)
    if off < 0:
        raise ValueError("bursts: on_share * on_factor exceeds 1")
    starts = np.arange(0.0, seconds, period)
    t = np.unique(np.clip(np.concatenate(
        [starts, starts + share * period, [seconds]]), 0.0, seconds))
    in_on = (t[:-1] - np.floor(t[:-1] / period) * period) < share * period
    mass = np.diff(t) * np.where(in_on, on, off)
    cum = np.concatenate([[0.0], np.cumsum(mass)])
    return t, cum / cum[-1]


def open_loop(mix, *, seconds, vocab_size, max_len, rng):
    """The whole window's arrivals, sorted by due time."""
    n = int(round(mix["rate_qps"] * seconds))
    req = draw(mix, n=n, vocab_size=vocab_size, max_len=max_len, rng=rng)
    if "bursts" in mix:
        t, cum = burst_knots(mix["bursts"], seconds)
        req.due = np.sort(np.interp(rng.uniform(0.0, 1.0, n), cum, t))
    else:
        req.due = np.sort(rng.uniform(0.0, seconds, n))
    return req


def closed_loop(mix, *, vocab_size, max_len, rng):
    """``distinct_calls`` batches of ``call_batch`` queries; the window
    cycles through them."""
    n = mix["call_batch"] * mix["distinct_calls"]
    return draw(mix, n=n, vocab_size=vocab_size, max_len=max_len, rng=rng)
