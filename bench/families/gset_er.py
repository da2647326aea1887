"""gset_er: a Max-Cut instance of a Gset shape, as a seeded Erdos-Renyi
graph G(n, p) with ``p = edges / C(n, 2)``, so the expected edge count
(and degree ``2 * edges / n``) matches the Gset instance; every edge has
weight ``weight``. The program gets the bias-free Ising couplings
J = -W. Keys read from the configuration: ``n``, ``edges``, ``weight``."""
from __future__ import annotations

import numpy as np

from bench.lib import gen


def weights(rng: np.random.Generator, n: int, edges: int,
            weight: int = 1) -> np.ndarray:
    """Symmetric (n, n) int32 weight matrix of one G(n, p) graph."""
    p = edges / (n * (n - 1) / 2)
    iu = np.triu_indices(n, k=1)
    present = rng.random(len(iu[0])) < p
    W = np.zeros((n, n), dtype=np.int32)
    W[iu[0][present], iu[1][present]] = weight
    return W + W.T


def instances(config: dict, rng: np.random.Generator, count: int,
              n: int) -> list:
    return [gen.maxcut_levels(weights(rng, n, int(config["edges"]),
                                      int(config["weight"])))
            for _ in range(count)]
