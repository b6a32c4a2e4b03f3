"""Training traffic: fixed-length sequences of seeded random tokens.

Parameters (the traffic file): ``seq_len``, ``tokens_per_step`` and
``distinct_batches`` (how many different batches cycle; the work of a step
does not depend on the token values, so a few are enough and cost no host
time inside the window).
"""

from __future__ import annotations

import itertools
from typing import Iterator, Mapping

import numpy as np


def sequences_per_step(params: Mapping) -> int:
    seqs, rest = divmod(params["tokens_per_step"], params["seq_len"])
    if rest:
        raise ValueError("tokens_per_step is not a whole number of sequences")
    return seqs


def generate(params: Mapping, *, seed: int, vocab_size: int
             ) -> Iterator[np.ndarray]:
    """Endless iterator of ``[sequences_per_step, seq_len]`` int32 batches;
    the same seed gives the same batches."""
    rng = np.random.default_rng(seed)
    shape = (sequences_per_step(params), params["seq_len"])
    batches = [rng.integers(0, vocab_size, size=shape, dtype=np.int32)
               for _ in range(params["distinct_batches"])]
    return itertools.cycle(batches)
