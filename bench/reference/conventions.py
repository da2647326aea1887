"""How the program turns a call's seed into each anneal's LFSR inits —
input conventions the reference must follow to anneal the same runs.

* A registry ``engine`` call with seed ``s`` anneals pad bucket ``b``'s
  problem ``p`` (bucket order) from ``LFSR(s + 7919*b + 7919*p)`` at init
  swing 0.5; every cell here has one bucket (all sizes pad to 64).
* A service flush runs the same engine call with the service's seed, so a
  request's inits depend on its position in its flush.
* ``fabric-jax`` with seed ``s`` runs its search from seed ``s + 104729``
  (see ``reference.lns``), with ``max(4, 2 * tiles)`` outer sweeps.
"""
ENGINE_SEED_STRIDE = 7919
ENGINE_SWING = 0.5
FABRIC_SEED_OFFSET = 104729


def engine_seeds(call_seed: int, positions) -> list:
    return [int(call_seed) + ENGINE_SEED_STRIDE * int(p) for p in positions]


def fabric_outer_sweeps(n_tiles: int) -> int:
    return max(4, 2 * n_tiles)
