"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a data file under ``traffic/``. This module turns its
parameters into concrete requests from the seed, so a new mix is a new
data file and never new code. The arithmetic follows the program's own
generator (``serve/workload.py``: lognormal lengths clipped to a range,
Poisson arrivals), copied here so that the yardstick does not move when
the program's copy changes.

Every seed gets the same work in another order. Lengths are the
distribution's quantiles at ``(i + 0.5) / n``, and Poisson gaps are the
exponential's quantiles scaled to fill their segment exactly; the seed
permutes them and draws the token ids. Runs with different seeds then
differ by ordering and content, not by how much work they hold, which
keeps the spread between runs down to what the system itself adds.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    """One request: its prompt, its output budget and, in an open loop,
    when it is due relative to the start of the measured window."""
    prompt: np.ndarray
    max_tokens: int
    due: float | None = None
    segment: str = ""


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``dist``:
    ``lognormal`` (``median``, ``sigma``) or ``uniform`` over the whole
    numbers, clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _lengths(mix: dict, n: int, rng) -> tuple[np.ndarray, np.ndarray]:
    p = rng.permutation(quantiles(mix["prompt"], n))
    o = rng.permutation(quantiles(mix["output"], n))
    return p, o


def _items(mix: dict, n: int, rng, vocab: int) -> list[Item]:
    p, o = _lengths(mix, n, rng)
    return [Item(rng.integers(0, vocab, int(a), dtype=np.int32), int(b))
            for a, b in zip(p, o)]


def poisson_times(rate: float, t0: float, t1: float, rng) -> np.ndarray:
    """``round(rate * (t1 - t0))`` arrivals in ``[t0, t1)`` whose gaps are
    the exponential's quantiles, in an order drawn from ``rng``."""
    n = max(1, int(round(rate * (t1 - t0))))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    ends = np.cumsum(gaps) / gaps.sum()
    return t0 + (t1 - t0) * (ends - ends[0] * 0.5)


def _segments(mix: dict, window_s: float) -> list[tuple[str, float, float]]:
    return [("preroll", -mix["preroll_s"], 0.0), ("window", 0.0, window_s),
            ("drain", window_s, window_s + mix["drain_s"])]


def open_loop(mix: dict, seed: int, vocab: int, window_s: float) -> list[Item]:
    """Arrivals from ``-preroll_s`` to ``window_s + drain_s`` at the mix's
    fixed rate, each segment with its own stratified set. Items due in
    ``[0, window_s)`` are the measured ones."""
    rate = mix["rate_per_s"]
    out = []
    for i, (name, t0, t1) in enumerate(_segments(mix, window_s)):
        rng = rng_for(seed, 10 + i)
        times = poisson_times(rate, t0, t1, rng)
        items = _items(mix, len(times), rng, vocab)
        for t, it in zip(times, items):
            it.due, it.segment = float(t), name
        out += items
    return out


class ClosedLoop:
    """An endless stream of requests for closed-loop clients, drawn in
    blocks of ``BLOCK`` with the same lengths in each, permuted."""

    BLOCK = 64

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self._buf: list[Item] = []
        self._n = 0

    def next(self) -> Item:
        if not self._buf:
            rng = rng_for(self.seed, 100 + self._n)
            self._buf = _items(self.mix, self.BLOCK, rng, self.vocab)
            self._n += 1
        return self._buf.pop(0)


def prompt_lengths(mix: dict, window_s: float) -> set[int]:
    """Every prompt length a run of the mix sends. The set is the same
    for every seed, since a seed only reorders the lengths: an open loop
    sends each segment's quantiles, a closed loop one block's."""
    if mix["kind"] == "open_loop":
        counts = [max(1, int(round(mix["rate_per_s"] * (t1 - t0))))
                  for _, t0, t1 in _segments(mix, window_s)]
    else:
        counts = [ClosedLoop.BLOCK]
    return {int(n) for c in counts for n in quantiles(mix["prompt"], c)}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(np.percentile(v, q))
