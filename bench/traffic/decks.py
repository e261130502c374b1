"""Draws that give every seed the same work in another order.

A deck holds each choice as many times as its weight asks (largest-remainder
apportionment of the deck's size), and the seed only shuffles it. So two seeds
send the same mix of sizes and the run-to-run spread is not the seed's.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence

import numpy as np


def apportion(weights: Sequence[float], n: int) -> List[int]:
    """Whole counts summing to ``n`` in proportion to ``weights``."""
    w = np.asarray(weights, float)
    if n <= 0 or (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"cannot apportion {n} over weights {list(weights)}")
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def deck_stream(choices: Sequence, weights: Sequence[float], deck: int,
                rng: np.random.Generator) -> Iterator:
    """Endless draws: shuffled decks of ``deck`` cards, one after another."""
    cards = [c for c, k in zip(choices, apportion(weights, deck)) for _ in range(k)]
    while True:
        for i in rng.permutation(len(cards)):
            yield cards[i]
