"""Open-loop traffic from a mix file: lengths, arrivals and token ids.

The shapes are those of `core/workload.py` (lognormal prompt and output
lengths clipped to a range, exponential inter-arrival gaps at a fixed rate),
copied here so that the yardstick does not move with the program.

Every seed serves the same work. Lengths and gaps are drawn at stratified
quantiles of their distributions and shuffled with a fixed seed, from the
mix alone; `--seed` draws the token ids (and, elsewhere, the weights). With
some tens of requests in a window, shuffling by `--seed` moved the p90 of
TTFT by 2x between seeds (on the chip, yi6b.longdoc), so the schedule
itself does not move with the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

_STD_NORMAL = NormalDist()


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator keyed by a seed of any size and a salt."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *salt])


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as the client sees it: due time in seconds from the
    start of the traffic, prompt token ids, and the output length."""
    idx: int
    due: float
    prompt: Tuple[int, ...]
    out_len: int
    phase: str          # "warm", "window" or "tail"


def lognormal_quantiles(mu: float, sigma: float, clip, n: int) -> np.ndarray:
    """`n` lengths at the mid-quantiles (i + 1/2) / n of a lognormal,
    truncated to int and clipped as `core/workload.py` does."""
    z = np.array([_STD_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(mu + sigma * z).astype(int), clip[0], clip[1])


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """`n` inter-arrival gaps at the mid-quantiles of an exponential,
    scaled so that they sum to `span` seconds (rate n / span)."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (span / g.sum())


def phase_requests(mix: dict, n: int, span: float, rng: np.random.Generator
                   ) -> List[Tuple[float, int, int]]:
    """(offset, prompt_len, out_len) for `n` requests over `span` seconds:
    the stratified lengths and gaps, each permuted by `rng`."""
    if n <= 0:
        return []
    p, o = mix["prompt"], mix["output"]
    ins = lognormal_quantiles(p["mu"], p["sigma"], p["clip"], n)
    outs = lognormal_quantiles(o["mu"], o["sigma"], o["clip"], n)
    gaps = exponential_gaps(n, span)
    ins, outs, gaps = rng.permutation(ins), rng.permutation(outs), \
        rng.permutation(gaps)
    # the first arrival opens the phase; the gap it would have waited
    # closes it, so the phase spans exactly `span`
    offs = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t), int(i), int(x)) for t, i, x in zip(offs, ins, outs)]


def build_requests(mix: dict, seed: int, window_s: float, vocab: int
                   ) -> List[Req]:
    """Warm-up, window and tail requests of one run, in due order.

    The warm-up phase is part of set-up; the window starts at
    `mix["warm_s"]`; the tail keeps arrivals flowing while requests due in
    the window are followed after it closes."""
    rate = float(mix["rate"])
    phases = (("warm", float(mix["warm_s"])), ("window", float(window_s)),
              ("tail", float(mix["grace_s"])))
    drawn = []
    start = 0.0
    for k, (name, span) in enumerate(phases):
        n = int(round(rate * span))
        for t, pl, ol in phase_requests(mix, n, span, seed_rng(0, 1, k)):
            drawn.append((start + t, pl, ol, name))
        start += span
    drawn.sort(key=lambda r: r[0])
    tok_rng = seed_rng(seed, 2)
    return [Req(i, due, tuple(tok_rng.integers(1, vocab, pl).tolist()), ol,
                name) for i, (due, pl, ol, name) in enumerate(drawn)]


def length_range(mix: dict) -> Tuple[int, int]:
    """The smallest and largest prompt a mix can draw."""
    lo, hi = mix["prompt"]["clip"]
    return int(lo), int(hi)
