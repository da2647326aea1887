"""Faults planted under the timed path, for the tests that must see
``correct`` come out false. Each returns the replacement to patch in."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def state_unchanged(original):
    """The anneal returns its initial state: a step that does nothing."""
    def run(self, J, v0, key=None, record_every=0):
        res = original(self, J, v0, key=key, record_every=record_every)
        v = jnp.asarray(v0, jnp.float32)
        s = jnp.where(v >= 0.5, 1.0, -1.0)
        Jf = jnp.asarray(J, jnp.float32)
        e = -0.5 * jnp.einsum("pri,pij,prj->pr", s, Jf, s,
                              precision="highest")
        return type(res)(v_final=v, sigma=s, energy=e)
    return run


def half_batch(original):
    """Only the first half of the batch is annealed; the second half is
    answered with the first half's results."""
    def run(self, J, v0, key=None, record_every=0):
        J, v0 = np.asarray(J), np.asarray(v0)
        h = max(1, J.shape[0] // 2)
        res = original(self, J[:h], v0[:h])
        idx = np.arange(J.shape[0]) % h
        return type(res)(v_final=res.v_final[idx], sigma=res.sigma[idx],
                         energy=res.energy[idx])
    return run


def flip_one_spin(original):
    """The registry's answer altered where it is produced: one spin of
    the first problem's best configuration flipped."""
    def solve(self, suite, *a, **kw):
        rep = original(self, suite, *a, **kw)
        s = np.array(rep.best_sigma[0])
        s[0] = -s[0]
        rep.best_sigma[0] = s
        return rep
    return solve


def lower_first_energy(original):
    """The fabric's answer altered where it is produced: the first
    restart's reported energy one level lower than its spins give."""
    def solve(self, suite, *a, **kw):
        rep = original(self, suite, *a, **kw)
        e = np.array(rep.energies[0], dtype=np.float64)
        e[int(np.argmin(e))] -= 1.0
        rep.energies[0] = e
        return rep
    return solve


def flip_served_spin(original):
    """The service's answer altered on its way to the client."""
    import dataclasses

    def deliver(self, r, o, res):
        if res is not None:
            s = np.array(res.sigma)
            s[0] = -s[0]
            res = dataclasses.replace(res, sigma=s)
        return original(self, r, o, res)
    return deliver


def no_psum(original):
    """The fabric's field exchange without the exchange between chips:
    each die's partial fields, never summed across the mesh."""
    def make(mesh):
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.distributed.fabric import FABRIC_AXIS

        def partial_fields(J_loc, s_loc):
            return jnp.einsum("rc,nc->rn", s_loc, J_loc)

        return jax.shard_map(partial_fields, mesh=mesh,
                             in_specs=(P(None, FABRIC_AXIS),
                                       P(None, FABRIC_AXIS)),
                             out_specs=P(None, None), check_vma=False)
    return staticmethod(make)
