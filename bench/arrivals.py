"""The benchmark's traffic generator: one general reader of the mix files
under ``bench/traffic/``.

Adapted from the program's open-loop harness (``serving/traffic.py``:
arrivals stamped at their intended time, lengths drawn from choice lists),
extended with a closed backlog, windowed measurement and stratified draws.

A mix is one of two kinds:

* ``poisson``: an open loop. Requests arrive at ``rate`` per second
  whatever the server does, and each is timed from its intended arrival.
* ``backlog``: offline batch work. The queue is topped up to ``backlog``
  waiting requests before every engine step, so it never runs dry; the
  first ``ramp`` requests get outputs spread evenly up to the longest, so
  the slots finish at staggered times from the start.

The warm-up before the window lasts ``warmup_steps`` decode steps of the
engine: the window opens at the same point of the mix on every run of a
seed, whatever the speed of its first steps.

Every seed gets the same work: requests are drawn in blocks of ``block``,
and within a block the prompt and output lengths are allotted to their
weights exactly (largest remainder) and the Poisson gaps are the block's
exponential quantiles; the seed only orders them and picks the token ids.
So two seeds offer the same sizes and the same mean load, in another order.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Mix:
    kind: str
    prompt_lens: Tuple[int, ...]
    output_lens: Tuple[int, ...]
    prompt_weights: Tuple[float, ...] = ()
    output_weights: Tuple[float, ...] = ()
    rate: float = 0.0
    backlog: int = 0
    ramp: int = 0
    block: int = 64
    warmup_steps: int = 0

    def __post_init__(self):
        if self.kind not in ("poisson", "backlog"):
            raise ValueError(f"unknown mix kind {self.kind!r}")
        if self.kind == "poisson" and not self.rate > 0:
            raise ValueError("a poisson mix needs a rate > 0")
        if self.kind == "backlog" and self.backlog < 1:
            raise ValueError("a backlog mix needs backlog >= 1")
        for lens, w in ((self.prompt_lens, self.prompt_weights),
                        (self.output_lens, self.output_weights)):
            if not lens or min(lens) < 1:
                raise ValueError("lengths must be >= 1")
            if w and len(w) != len(lens):
                raise ValueError("one weight per length")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Mix":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in d.items() if k in fields}
        return cls(**kw)

    @property
    def longest(self) -> int:
        return max(self.prompt_lens) + max(self.output_lens)


@dataclasses.dataclass
class Item:
    prompt: np.ndarray
    max_new: int
    gap_s: float          # time since the previous arrival (poisson)


def allot(values: Sequence[int], weights: Sequence[float],
          n: int) -> List[int]:
    """``n`` values in the proportions ``weights`` (uniform when empty),
    rounded by largest remainder."""
    w = np.asarray(weights if weights else [1.0] * len(values), np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return [int(v) for v, c in zip(values, counts) for _ in range(c)]


class Source:
    """The request stream of one mix and seed: ``take()`` returns the next
    request. Deterministic for a given (mix, vocab, seed)."""

    def __init__(self, mix: Mix, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed))
        self._buf: List[Item] = []
        self.taken = 0

    def _block(self) -> List[Item]:
        m, n, rng = self.mix, self.mix.block, self.rng
        plens = rng.permutation(allot(m.prompt_lens, m.prompt_weights, n))
        olens = rng.permutation(allot(m.output_lens, m.output_weights, n))
        if m.kind == "poisson":
            q = (np.arange(n) + 0.5) / n
            gaps = rng.permutation(-np.log1p(-q) / m.rate)
        else:
            gaps = np.zeros(n)
        return [Item(prompt=rng.integers(0, self.vocab, size=int(p),
                                         dtype=np.int32),
                     max_new=int(o), gap_s=float(g))
                for p, o, g in zip(plens, olens, gaps)]

    def take(self) -> Item:
        if not self._buf:
            self._buf = self._block()[::-1]
        item = self._buf.pop()
        if self.taken < self.mix.ramp:
            # the first cohort finishes at staggered times
            top = max(self.mix.output_lens)
            item.max_new = max(1, round(top * (self.taken + 1)
                                        / self.mix.ramp))
        self.taken += 1
        return item


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-quantile (0 < q <= 1) of all ``values`` by nearest rank:
    the smallest value with at least a share ``q`` of the sample at or
    below it. ``None`` for an empty sample."""
    v = sorted(values)
    if not v:
        return None
    k = max(int(np.ceil(q * len(v))) - 1, 0)
    return float(v[k])


def window_rate(count: float, seconds: float) -> float:
    """Work done in the window over the window's whole length."""
    if seconds <= 0:
        raise ValueError("empty window")
    return count / seconds
