"""random_qubo: the paper's section IV instances, the benchmark's own copy.

Each upper-triangle edge is present with probability ``density``, its
level uniform in {-max_level..-1, 1..max_level}; symmetric, zero diagonal,
int16. Keys read from the configuration: ``n``, ``density``,
``max_level``."""
from __future__ import annotations

import numpy as np


def instances(config: dict, rng: np.random.Generator, count: int,
              n: int) -> list:
    iu = np.triu_indices(n, k=1)
    m = len(iu[0])
    present = rng.random((count, m)) < float(config["density"])
    mags = rng.integers(1, int(config["max_level"]) + 1, size=(count, m))
    signs = rng.choice(np.array([-1, 1]), size=(count, m))
    w = np.where(present, mags * signs, 0).astype(np.int16)
    J = np.zeros((count, n, n), dtype=np.int16)
    J[:, iu[0], iu[1]] = w
    return list(J + J.transpose(0, 2, 1))
