"""Prompts for a model endpoint: lengths from a fixed set, token ids uniform
over the configuration's vocabulary.

Mix parameters: ``prompt_lengths``, ``length_weights``, ``deck``.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from bench.traffic.decks import deck_stream


def requests(mix: Dict, seed: int, vocab_size: int) -> Iterator[np.ndarray]:
    """Endless int32 prompts."""
    rng = np.random.default_rng([seed, 2])
    for n in deck_stream(mix["prompt_lengths"], mix["length_weights"],
                         mix["deck"], rng):
        yield rng.integers(0, vocab_size, n, dtype=np.int32)
