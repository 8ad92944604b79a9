"""Byte-level text corpus: packing + deterministic batch slicing.

No external datasets ship in this container, so the LM-quality benchmarks
use either synthetic streams or a corpus built from this repository's own
source/docs (a few hundred KB of real, structured text — enough for the
relative model comparisons in benchmarks/lm_ppl.py).
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

VOCAB = 256  # bytes


# the tracked source trees; untracked output (logs, caches, traces) under
# the checkout must not change what the corpus holds
CORPUS_DIRS = ("src", "benchmarks", "examples", "tests")
SPLIT_SEEDS = {"train": 0, "val": 1}


def repo_corpus(root: str = None, max_bytes: int = 4 << 20) -> bytes:
    """Concatenate this repo's source and docs into a corpus: the top-level
    ``*.md`` files, then every ``.py``/``.md``/``.toml``/``.txt`` file
    under ``CORPUS_DIRS``, each in sorted order."""
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__))))
    paths = [os.path.join(root, fn) for fn in sorted(os.listdir(root))
             if fn.endswith(".md")]
    for top in CORPUS_DIRS:
        for dirpath, dirnames, files in os.walk(os.path.join(root, top)):
            dirnames[:] = sorted(d for d in dirnames if not d.startswith(".")
                                 and d not in ("results", "__pycache__"))
            paths += [os.path.join(dirpath, fn) for fn in sorted(files)
                      if fn.endswith((".py", ".md", ".toml", ".txt"))]
    buf = bytearray()
    for path in paths:
        with open(path, "rb") as f:
            buf += f.read()
        if len(buf) >= max_bytes:
            return bytes(buf[:max_bytes])
    return bytes(buf)


class ByteCorpus:
    """Deterministic (seed, step) -> batch slicing over a packed byte array."""

    def __init__(self, data: Optional[bytes] = None, seed: int = 0):
        data = data if data is not None else repo_corpus()
        if len(data) < 1 << 16:
            data = data * ((1 << 16) // max(1, len(data)) + 1)
        self.arr = np.frombuffer(data, np.uint8).astype(np.int32)
        self.seed = seed

    def batch(self, step: int, batch: int, seq_len: int, split: str = "train"):
        n = len(self.arr) - seq_len - 1
        train_cut = int(n * 0.9)
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, SPLIT_SEEDS[split]]))
        if split == "train":
            starts = rng.integers(0, train_cut, batch)
        else:
            starts = rng.integers(train_cut, n, batch)
        idx = starts[:, None] + np.arange(seq_len + 1)[None, :]
        chunk = self.arr[idx]
        return {"inputs": chunk[:, :-1], "labels": chunk[:, 1:]}
