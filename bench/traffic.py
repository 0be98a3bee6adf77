"""The one traffic generator: turns a mix's parameter file into requests.

A mix is a JSON file under ``bench/traffic/`` named after the cell's
``traffic``.  Its keys:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when its previous one finishes) or ``"open"`` (arrivals on a schedule at
  ``rate`` requests per second, ``arrivals`` ``"poisson"`` or ``"gamma"``
  with coefficient of variation ``cv``).
* ``prompt`` / ``output``: token-length distributions, each
  ``{"dist": "uniform", "lo", "hi"}`` or
  ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped to
  ``[lo, hi]``); ``max_total`` caps prompt + output.
* ``pool``: how many (prompt, output) size pairs are drawn, once, from
  ``sizes_seed``, which also fixes their order and the arrival gaps.  So
  every run seed serves the same sizes at the same times, and the seed
  changes only the token ids: runs differ in content, not in work.
* ``ramp_steps`` (closed loop): scheduler steps served before the window,
  counted as set-up, so that the window starts from a steady mix.
* ``ramp_s`` (open loop): seconds of arrivals served before the window,
  counted as set-up, so that the window opens on a loaded scheduler.

Token ids are drawn from the run seed.  Nothing here touches JAX.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _draw(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def size_pool(mix: Dict) -> np.ndarray:
    """The mix's fixed (prompt, output) pairs, shape (pool, 2)."""
    rng = np.random.default_rng(int(mix.get("sizes_seed", 0)))
    n = int(mix["pool"])
    prompt = _draw(rng, mix["prompt"], n)
    output = _draw(rng, mix["output"], n)
    cap = mix.get("max_total")
    if cap is not None:
        output = np.minimum(output, int(cap) - prompt)
        if (output < 1).any():
            raise ValueError("max_total leaves a request no output token")
    return np.stack([prompt, output], axis=1)


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    rid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    due: float = 0.0        # open loop: seconds after the window opens


class Traffic:
    """The requests of one run, in the order they are sent.

    ``next_item()`` hands out the pool's pairs in the mix's fixed order,
    cycling through further permutations when one is used up.  The first
    request of closed-loop client ``i`` keeps only a share ``(i + 1) / clients`` of its
    output, so the clients' requests end at spread-out times and the mix of
    prefilling and decoding rows is steady from the start.
    """

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.pool = size_pool(mix)
        self._order_rng = np.random.default_rng(
            [int(mix.get("sizes_seed", 0)), 1])
        self._tok_rng = np.random.default_rng([int(seed) % (1 << 63), 2])
        self._order: List[int] = []
        self._next_rid = 0
        self._gaps = None
        if mix["loop"] == "open":
            self._gaps = self._gap_pool()

    def _gap_pool(self) -> np.ndarray:
        """Inter-arrival gaps in seconds, drawn once from ``sizes_seed``."""
        mix = self.mix
        rng = np.random.default_rng([int(mix.get("sizes_seed", 0)), 3])
        n, rate = int(mix["pool"]), float(mix["rate"])
        if mix["arrivals"] == "poisson":
            return rng.exponential(1.0 / rate, n)
        if mix["arrivals"] == "gamma":
            shape = 1.0 / float(mix["cv"]) ** 2
            return rng.gamma(shape, 1.0 / (rate * shape), n)
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")

    def next_item(self, due: float = 0.0, share: float = 1.0) -> Item:
        if not self._order:
            self._order = list(self._order_rng.permutation(len(self.pool)))
        p, o = self.pool[self._order.pop(0)]
        prompt = self._tok_rng.integers(0, self.vocab, int(p)).astype(np.int32)
        max_new = max(1, int(np.ceil(int(o) * share)))
        item = Item(rid=self._next_rid, prompt=prompt, max_new=max_new,
                    due=due)
        self._next_rid += 1
        return item

    def first_items(self) -> List[Item]:
        """Closed loop: one staggered first request per client."""
        c = int(self.mix["clients"])
        return [self.next_item(share=(i + 1) / c) for i in range(c)]

    def schedule(self, seconds: float) -> List[Item]:
        """Open loop: every request due within ``seconds`` of the start."""
        order = self._order_rng.permutation(len(self._gaps))
        due = np.cumsum(self._gaps[order])
        return [self.next_item(due=float(t)) for t in due[due < seconds]]

    def max_lengths(self) -> Dict[str, int]:
        return {"prompt": int(self.pool[:, 0].max()),
                "output": int(self.pool[:, 1].max()),
                "min_prompt": int(self.pool[:, 0].min())}
