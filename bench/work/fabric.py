"""fabric: the checkerboard fabric (``fabric-jax``) at its default effort:
every tile of the instance, for every restart (the traffic's ``runs``)
and every one of ``max(4, 2 * tiles)`` outer sweeps, ``inner_runs`` runs
each."""
from __future__ import annotations

import math

from bench.work import anneal


def call_instances(config: dict, traffic: dict) -> list:
    """The (n_spins, runs) anneal instances of one solve."""
    die = int(config["die_spins"])
    n = int(config["n"])
    tiles = max(1, math.ceil(n / (die - 1)))
    return anneal.fabric_instances(n, int(traffic["runs"]),
                                   int(config["solver_opts"]["inner_runs"]),
                                   die, max(4, 2 * tiles))
