"""The one traffic generator. A mix is a data file of parameters
(``chipbench/traffic/<name>.json``); this module turns it, a cell's rate
and a seed into requests.

Every seed gets the same sizes and inter-arrival gaps, in the same order:
lengths are the distribution's quantiles at stratified points
``(i + 0.5) / pool``, permuted by the mix's own ``order_seed``; the run's
seed draws the token ids (and, in the harness, the weights). The program
does the same work for every seed, since it decodes each request to its
length whatever the tokens, so the spread between seeds is the system's,
not the generator's.

Mix keys:

- ``arrival``: ``"poisson"`` (open loop at the cell's ``rate_per_s``) or
  ``"backlog"`` (a queue filled before the window and kept topped up);
- ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``
  (integers, both ends included);
- ``pool`` (backlog mixes): how many stratified points one permutation
  cycle holds; a poisson mix takes ``round(rate * seconds)``, the
  expected count of its window, so each seed's window holds the same
  sizes and gaps;
- ``order_seed``: the seed of the permutations of sizes and gaps;
- ``ending``: ``"drain"`` (requests due in the window run to completion
  after it) or ``"cancel"`` (what is left at the close is cancelled).
"""

from __future__ import annotations

import dataclasses
import itertools
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

_SEED_SPACE = 2 ** 63


@dataclasses.dataclass(frozen=True)
class Item:
    """One request before it becomes the program's ``Request``."""
    idx: int
    prompt: np.ndarray          # (plen,) int32 token ids
    target_gen: int
    due: float                  # seconds after the window opens (poisson)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """Integer lengths at the stratified points (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec['min']), int(spec['max'])
    if spec['dist'] == 'uniform':
        v = lo + np.floor(u * (hi - lo + 1))
    elif spec['dist'] == 'lognormal':
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.round(spec['median'] * np.exp(spec['sigma'] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


class Traffic:
    """Requests of one mix for one seed."""

    def __init__(self, mix: dict, *, seed: int, vocab: int,
                 rate_per_s: float = 0.0, seconds: float = 0.0):
        self.mix = mix
        self.vocab = int(vocab)
        self.rate = float(rate_per_s)
        self.seconds = float(seconds)
        self.rng = np.random.default_rng(int(seed) % _SEED_SPACE)
        self.order = np.random.default_rng(int(mix['order_seed']))
        if mix['arrival'] == 'poisson' and self.rate <= 0:
            raise ValueError('a poisson mix needs the cell rate_per_s > 0')
        self.pool = (max(1, round(self.rate * self.seconds))
                     if mix['arrival'] == 'poisson' else int(mix['pool']))
        self._prompts = _quantiles(mix['prompt_len'], self.pool)
        self._outputs = _quantiles(mix['output_len'], self.pool)
        self.max_prompt = int(mix['prompt_len']['max'])
        self.max_output = int(mix['output_len']['max'])
        self.min_output = int(mix['output_len']['min'])

    @property
    def open_loop(self) -> bool:
        return self.mix['arrival'] == 'poisson'

    @property
    def drains(self) -> bool:
        return self.mix['ending'] == 'drain'

    def tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def items(self) -> Iterator[Item]:
        """Endless stream of requests: each cycle is a fresh permutation
        (from ``order_seed``) of the same stratified lengths and, for
        poisson mixes, of the same stratified gaps, so the due times of a
        cycle span about ``pool / rate`` seconds."""
        idx = 0
        t = 0.0
        n = self.pool
        gaps = (-np.log1p(-(np.arange(n) + 0.5) / n) / self.rate
                if self.open_loop else np.zeros(n))
        while True:
            p = self.order.permutation(self._prompts)
            o = self.order.permutation(self._outputs)
            g = self.order.permutation(gaps)
            for i in range(n):
                t += float(g[i])
                yield Item(idx, self.tokens(int(p[i])), int(o[i]), t)
                idx += 1

    def arrivals(self) -> List[Item]:
        """Poisson mix: the window's requests, one cycle of the pool
        (its gaps add up to just under the window)."""
        return [it for it in itertools.islice(self.items(), self.pool)
                if it.due < self.seconds]

    def warmup(self) -> List[Item]:
        """Requests that compile every shape the window uses before it
        opens: the longest prompt less one token (a partial last page, so
        the tail-zero op runs, and enough pages for the int8 tier to age
        some out) with the shortest output, one per ``warmup`` count."""
        n = int(self.mix.get('warmup', 1))
        return [Item(-1 - i, self.tokens(self.max_prompt - 1),
                     self.min_output, 0.0) for i in range(n)]
