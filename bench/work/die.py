"""die: one die per problem, as the registry's ``engine`` runs it: every
problem of a call annealed ``runs`` times at its own size."""
from __future__ import annotations


def call_instances(config: dict, traffic: dict) -> list:
    """The (n_spins, runs) anneal instances of one call."""
    return ([(int(config["n"]), int(traffic["runs"]))]
            * int(traffic["problems_per_call"]))
