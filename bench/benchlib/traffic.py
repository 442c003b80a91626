"""Traffic drawn from a traffic file and ``--seed``.

Every seed gets the same amount of work: the same number of clients,
prompts of the same length; the seed only chooses the prompts' tokens.
"""
from __future__ import annotations

import numpy as np


def rng(*keys: int) -> np.random.Generator:
    """A generator for any whole-number keys, negative or beyond 64 bits."""
    return np.random.default_rng([int(k) % (1 << 63) for k in keys])


def prompt(seed: int, client: int, k: int, length: int, vocab: int
           ) -> np.ndarray:
    """The ``k``-th prompt of a client: ``length`` token ids uniform over
    the vocabulary, shape (1, length), int32."""
    return rng(seed, 20, client, k).integers(
        0, vocab, (1, length)).astype(np.int32)
