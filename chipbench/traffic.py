"""The one traffic generator: a traffic file's parameters + ``--seed`` ->
inputs. Imports neither jax nor the program (the load generator's child
process uses it).

Seeds change the content of the work and never its amount:

* ``token_rows``: fixed-length rows of ids, uniform over the vocabulary; row
  ``i`` depends on ``(seed, i)`` alone, so the reference can ask for the same
  rows again.
* ``requests``: a pool of ``pool`` (prompt length, output length) pairs taken
  at evenly spaced quantiles of two clipped lognormals, paired and ordered
  once by the file's ``pool_seed`` and cycled from its first pair by every
  seed; prompt ids are uniform, unshared, from the seed.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, stream)])


class TokenRows:
    """Map-style dataset (``len`` + ``[i]``) of ``{"input_ids": int32[seq]}``."""

    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.seq_len = int(params["seq_len"])
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self.n_rows = int(params.get("rows", 1 << 16))

    def __len__(self) -> int:
        return self.n_rows

    def row(self, i: int) -> np.ndarray:
        return _rng(self.seed, 1, i).integers(
            0, self.vocab_size, self.seq_len).astype(np.int32)

    def __getitem__(self, i: int) -> dict:
        return {"input_ids": self.row(i)}

    def batch(self, step: int, batch_size: int) -> np.ndarray:
        """Rows of step ``step`` of a loader that walks the rows in order."""
        return np.stack([self.row(step * batch_size + j) for j in range(batch_size)])


def lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the evenly spaced quantiles ``(i + 0.5) / n`` of a
    lognormal with this median and sigma, clipped to ``[min, max]``."""
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        length = int(round(spec["median"] * math.exp(spec["sigma"] * z)))
        out.append(max(int(spec["min"]), min(int(spec["max"]), length)))
    return out


class RequestPlan:
    """Request ``i`` of a run: its prompt ids and how many tokens to ask for.

    The pool's pairing and cyclic order come from the file's ``pool_seed``; the
    run's seed draws the ids alone. So every seed offers the same lengths in
    the same order: in a closed loop, which long prompts prefill at once and
    which part of the cycle a window of fixed length covers are both part of
    the work (the traffic file's ``ordering`` has the readings). A tail read
    under one fixed order is that order's alone."""

    def __init__(self, params: dict, vocab_size: int, seed: int):
        n = int(params["pool"])
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        prompts = lognormal_quantiles(params["prompt_len"], n)
        outputs = lognormal_quantiles(params["output_len"], n)
        rng = _rng(params["pool_seed"], 2)
        self.prompt_lens = [prompts[j] for j in rng.permutation(n)]
        self.output_lens = [outputs[j] for j in rng.permutation(n)]

    def lengths(self, i: int) -> tuple[int, int]:
        j = i % len(self.prompt_lens)
        return self.prompt_lens[j], self.output_lens[j]

    def prompt(self, i: int) -> list[int]:
        n, _ = self.lengths(i)
        return _rng(self.seed, 3, i).integers(1, self.vocab_size, n).tolist()
