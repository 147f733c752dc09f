"""The one general traffic generator. A traffic mix is a data file of
parameters under ``benchmarks/traffic/``; this reads it.

Serving mixes give:

- ``arrivals``: ``{"kind": "closed", "clients": N}`` (each client sends its
  next request when its last one finishes), ``{"kind": "poisson", "rate":
  r}`` or ``{"kind": "burst", "rate": r, "burst": k}`` (k requests together,
  bursts Poisson at r/k a second): open loops, timed from the due time.
- ``prompt_len`` / ``output_len``: ``{"dist": "exponential", "mean", "min",
  "max"}`` (the law a published mean alone fixes), ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "fixed", "value"}``; the
  traffic file names the public source of the numbers.

Every seed gives the SAME requests by size, in the same order: the
distributions' quantiles on a fixed grid of 128, paired and ordered once by
a fixed shuffle. The seed draws the token ids (uniform over the vocabulary)
and the weights. On the chip, letting the seed reorder the sizes moved a
closed loop's rate by 3% and its tails by 15% from seed to seed (which
requests meet in one engine step), where two runs of one seed differ by
0.5%: the seed was changing the work. The price: a run sees one
interleaving, so a tail read from it is not a bounded metric (PERF.md,
PR 23).
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

POOL = 128  # requests in one cycle of the length grid


@dataclasses.dataclass
class Req:
    index: int
    prompt: list[int]
    max_new_tokens: int


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "exponential":
        x = -float(spec["mean"]) * np.log1p(-q)
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(
        np.int64
    )


class RequestSource:
    """Request ``i`` of a run, a pure function of (seed, i).

    One cycle of ``POOL`` requests holds a fixed set of (prompt
    length, output length, first-request share) triples: the quantiles of
    the two distributions, paired and ordered once and for all by a fixed
    shuffle that no seed touches. The seed draws the tokens.
    """

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec, self.seed, self.vocab = spec, int(seed), int(vocab)
        n = POOL
        fixed = np.random.default_rng(0xF1E1D)  # not the seed
        self._plen = _quantile_lengths(spec["prompt_len"], n)
        self._olen = fixed.permutation(_quantile_lengths(spec["output_len"], n))
        self._share = fixed.permutation(0.05 + 0.95 * (np.arange(n) + 0.5) / n)
        self._orders: dict[int, np.ndarray] = {}

    def _slot(self, i: int) -> int:
        cycle, j = divmod(i, POOL)
        if cycle not in self._orders:
            self._orders[cycle] = np.random.default_rng(
                [0xF1E1D, cycle]  # not the seed: see the module docstring
            ).permutation(POOL)
        return int(self._orders[cycle][j])

    def lengths(self, i: int, first: bool = False) -> tuple[int, int]:
        """(prompt, output) lengths of request ``i``. A closed loop's first
        request per client is cut to a fixed share of its output, as if
        caught mid-flight, so the clients start out of step (the steady
        state's residual life) and the ramp stays short."""
        k = self._slot(i)
        olen = int(self._olen[k])
        if first:
            olen = max(1, int(math.ceil(olen * float(self._share[k]))))
        return int(self._plen[k]), olen

    def request(self, i: int, first: bool = False) -> Req:
        plen, olen = self.lengths(i, first)
        rng = np.random.default_rng([self.seed, 0x70C, i])
        return Req(i, rng.integers(0, self.vocab, plen).tolist(), olen)


def arrival_times(spec: dict, seed: int, horizon_s: float) -> list[float]:
    """Due times (seconds from the start) of an open loop's requests up to
    ``horizon_s``; empty for a closed loop."""
    kind = spec["kind"]
    if kind == "closed":
        return []
    rng = np.random.default_rng([int(seed), 0xA221])
    rate = float(spec["rate"])
    burst = int(spec.get("burst", 1)) if kind == "burst" else 1
    if kind not in ("poisson", "burst"):
        raise ValueError(f"unknown arrivals kind {kind!r}")
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(burst / rate))
        if t >= horizon_s:
            return out
        out.extend([t] * burst)
