"""Request generators for the traffic mixes in ``bench/traffic/<mix>.json``.

A mix names its request classes (share, and lognormal prompt and output
lengths with a median, a sigma and clip bounds). The lengths are not drawn
independently per request: each "deck" of ``deck`` requests holds a fixed
set of lengths, the class shares and the lognormal quantiles at
(i + 0.5) / n, or at i / (n - 1) where a length spec says
``"quantiles": "inclusive"`` (so that a deck with few of a class still holds
both of its clip bounds). The classes keep fixed, evenly spread places in
each deck, and the order of the lengths within a class is a fixed shuffle
that depends on the deck's index alone. Open-loop mixes get their
inter-arrival gaps the same way, from the exponential quantiles of
``rate_per_s``. So every seed offers the same work in the same order, and
the seed picks only the token ids: how many requests end in one tick, and
so how often admissions share a padded bucket, does not change with it.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

SEED_MOD = 2 ** 63
ORDER_KEY = 0x5EED  # the fixed shuffles of every deck, the same for all seeds


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the lognormal quantiles (i + 0.5) / n, or i / (n - 1)
    with ``"quantiles": "inclusive"``, clipped."""
    if n <= 0:
        return np.zeros(0, np.int64)
    if spec.get("quantiles", "midpoint") == "inclusive" and n > 1:
        q = np.arange(n) / (n - 1)
    else:
        q = (np.arange(n) + 0.5) / n
    nd = NormalDist()
    z = np.array([nd.inv_cdf(p) if 0 < p < 1 else math.copysign(math.inf, p - 0.5)
                  for p in q])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at the exponential quantiles."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def class_counts(mix: dict) -> list[int]:
    """Requests of each class in one deck (largest remainder rounding)."""
    deck = mix["deck"]
    raw = [c["share"] * deck for c in mix["classes"]]
    counts = [math.floor(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[:deck - sum(counts)]:
        counts[i] += 1
    return counts


class RequestStream:
    """The mix's requests in order, deck by deck; ``seed`` picks the ids.

    ``next()`` returns a dict with ``id``, ``cls``, ``prompt_len``,
    ``output_len`` and, for open loops, ``due_s`` (seconds after the start
    of the stream). ``tokens(req, vocab)`` gives the prompt ids.
    """

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed) % SEED_MOD
        self.counts = class_counts(mix)
        deck = mix["deck"]
        self._prompts = [lognormal_quantiles(c["prompt"], n)
                         for c, n in zip(mix["classes"], self.counts)]
        self._outputs = [lognormal_quantiles(c["output"], n)
                         for c, n in zip(mix["classes"], self.counts)]
        self._gaps = (exponential_quantiles(mix["rate_per_s"], deck)
                      if mix.get("loop") == "open" else None)
        self._buf: list[dict] = []
        self._deck_index = 0
        self._next_id = 0
        self._clock = 0.0

    def _deal(self):
        rng = np.random.default_rng([ORDER_KEY, self._deck_index])
        # classes sit at evenly spread, fixed places in the deck and the
        # order within each is fixed: where the rare long requests fall
        # relative to each other sets the tail of the wait, and how many
        # replies end in one tick sets the padding; neither may change with
        # the seed
        places = [((k + 0.5) / n, i) for i, n in enumerate(self.counts)
                  for k in range(n)]
        labels = np.array([i for _, i in sorted(places)])
        prompts = [rng.permutation(p) for p in self._prompts]
        outputs = [rng.permutation(o) for o in self._outputs]
        gaps = None if self._gaps is None else rng.permutation(self._gaps)
        taken = [0] * len(self.counts)
        for j, ci in enumerate(labels):
            k = taken[ci]
            taken[ci] += 1
            req = {"id": self._next_id,
                   "cls": self.mix["classes"][ci]["name"],
                   "prompt_len": int(prompts[ci][k]),
                   "output_len": int(outputs[ci][k])}
            if gaps is not None:
                self._clock += float(gaps[j])
                req["due_s"] = self._clock
            self._next_id += 1
            self._buf.append(req)
        self._deck_index += 1

    def next(self) -> dict:
        if not self._buf:
            self._deal()
        return self._buf.pop(0)

    def until(self, horizon_s: float) -> list[dict]:
        """Open loop: every request due before ``horizon_s``."""
        out = []
        while True:
            if not self._buf:
                self._deal()
            if self._buf[0]["due_s"] >= horizon_s:
                return out
            out.append(self._buf.pop(0))

    def tokens(self, req: dict, vocab: int) -> np.ndarray:
        """Prompt ids of ``req``: uniform over [3, vocab), from (seed, id)."""
        rng = np.random.default_rng([self.seed, 2, req["id"]])
        return rng.integers(3, vocab, req["prompt_len"]).astype(np.int32)
