"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and turns it into requests and arrival times.

Every run of a mix offers the same schedule: the same sizes in the same
order and, open loop, the same arrival times.  The mix fixes a set of
``sizes`` (prompt length, output length) values taken at evenly spaced
quantiles of its length distributions; each block of ``sizes``
consecutive requests is a permutation of that set, drawn from the mix's
``schedule_seed``, with prompt and output lengths permuted independently.
Open-loop arrival gaps are the exponential distribution's quantiles at the
mix's rate, in an order drawn the same way, so every block has the same
mean rate.  The run's seed makes the prompt token ids (per request index),
as it makes the weights; it does not change the work.  (Near the knee the
order of a window's few tens of arrivals alone moves the TTFT tail by
tens of percent between orders, so an order per seed would measure the
order.)

Mix keys:
  loop           "open" (arrivals on a schedule) or "closed" (``clients``
                 each send the next request when the previous one finishes)
  rate_per_s     open loop: mean arrivals per second
  clients        closed loop: concurrent clients
  prompt         length distribution of prompts (see ``_quantiles``)
  output         length distribution of outputs (tokens to generate)
  sizes          number of quantile points per distribution
  schedule_seed  orders the sizes and gaps
  warm_s         seconds the traffic runs before the measured window opens
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}``, rounded and clipped to
    [min, max]."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
        vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        vals = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _seed_words(seed: int) -> List[int]:
    """A non-negative seed of any size as 32-bit words for SeedSequence."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


class Traffic:
    def __init__(self, mix: Dict, seed: int, *, vocab: int, max_len: int):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.n = int(mix["sizes"])
        self.vocab = int(vocab)
        self.words = _seed_words(seed)
        self.schedule = _seed_words(mix["schedule_seed"])
        self.prompt_set = _quantiles(mix["prompt"], self.n)
        self.output_set = _quantiles(mix["output"], self.n)
        longest = int(self.prompt_set.max() + self.output_set.max())
        if longest > max_len:
            raise ValueError(f"prompt + output up to {longest} tokens exceeds "
                             f"max_len {max_len}")
        self.warm_s = float(mix.get("warm_s", 0.0))
        if self.loop == "open":
            self.rate = float(mix["rate_per_s"])
            q = (np.arange(self.n) + 0.5) / self.n
            self.gap_set = -np.log1p(-q) / self.rate
            self.clients = 0
        else:
            self.clients = int(mix["clients"])
        self._blocks: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
        self._arrivals: List[float] = []

    def _rng(self, *tag: int) -> np.random.Generator:
        return np.random.default_rng(self.words + [0x5EED] + list(tag))

    def _block(self, b: int):
        if b not in self._blocks:
            rng = np.random.default_rng(self.schedule + [0x5EED, 1, b])
            gaps = (rng.permutation(self.gap_set) if self.loop == "open"
                    else None)
            self._blocks[b] = (rng.permutation(self.prompt_set),
                               rng.permutation(self.output_set), gaps)
        return self._blocks[b]

    def sizes(self, i: int) -> Tuple[int, int]:
        """(prompt length, output length) of request ``i``."""
        p, o, _ = self._block(i // self.n)
        return int(p[i % self.n]), int(o[i % self.n])

    def request(self, i: int) -> Tuple[List[int], int]:
        """Request ``i``: (prompt token ids, tokens to generate)."""
        n_prompt, n_out = self.sizes(i)
        ids = self._rng(2, i).integers(1, self.vocab, size=n_prompt)
        return ids.tolist(), n_out

    def arrival(self, i: int) -> float:
        """Open loop: seconds from traffic start at which request ``i`` is
        due (the first is due at the first gap)."""
        while len(self._arrivals) <= i:
            j = len(self._arrivals)
            gap = float(self._block(j // self.n)[2][j % self.n])
            prev = self._arrivals[-1] if self._arrivals else 0.0
            self._arrivals.append(prev + gap)
        return self._arrivals[i]

    def prompt_lengths(self) -> List[int]:
        """Every prompt length this mix can send (for warm-up)."""
        return sorted(set(int(x) for x in self.prompt_set))


def percentile(values, p: float) -> float:
    """Nearest-rank-interpolated percentile (numpy's linear rule)."""
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), p))
