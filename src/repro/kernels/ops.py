"""jit'd public wrappers around the Pallas kernels.

``fused_anneal`` is the thin back-compat shim kept for existing callers;
new code should go through ``repro.core.engine.AnnealEngine``, which owns
path/block-size selection and the autotune cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.binarize import sign_pm1
from ..core.device_model import DeviceModel
from ..core.hamiltonian import ising_energy
from ..core.perturbation import PerturbationConfig
from .ising_anneal import DEFAULT_BLOCK_R, dies_per_tile, fused_anneal_kernel


def fused_anneal(J, v0, dev: DeviceModel, pert: PerturbationConfig,
                 interpret: bool | None = None, block_r: int | None = None,
                 j_dtype: str = "float32"):
    """Full anneal via the fused VMEM kernel (schedule derived in-kernel).

    Returns (v_final, sigma, energy) matching ``core.annealer.anneal``'s
    noise-free outputs. ``interpret=None`` resolves through the kernel's
    ``default_interpret`` (True off-TPU).
    """
    if j_dtype == "int8":
        # The jit'd kernel wrapper only sees traced values; guard the silent
        # astype(int8) truncation/wraparound here, where J is concrete.
        try:
            Jn = np.asarray(J)
        except Exception:
            Jn = None
        if Jn is not None and (np.any(Jn != np.round(Jn)) or
                               np.any(np.abs(Jn) > 127)):
            raise ValueError("j_dtype='int8' requires integer coupling "
                             "levels in [-127, 127] (run DeviceModel."
                             "quantize first)")
    if block_r is None:
        block_r = DEFAULT_BLOCK_R
    Jf = jnp.asarray(J, jnp.float32)
    kernel = functools.partial(fused_anneal_kernel, dev=dev, pert=pert,
                               block_r=block_r, j_dtype=j_dtype,
                               interpret=interpret)
    split = _batch_split(Jf)
    if split is not None:
        kernel = _per_shard(*split, dev, pert, block_r, j_dtype, interpret)
    v = kernel(Jf, jnp.asarray(v0, jnp.float32))
    sigma = sign_pm1(v, dev.threshold)
    return v, sigma, ising_energy(Jf, sigma)


def _batch_split(J):
    """``(mesh, axis)`` when J's problem axis is sharded over more than one
    device — the fabric's die-aligned batches — else None."""
    sh = getattr(J, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.size == 1 \
            or not sh.spec or sh.spec[0] is None:
        return None
    return sh.mesh, sh.spec[0]


def tile_dies(J) -> int:
    """Dies per kernel tile for the batch J (P,N,N): a batch sharded over
    devices is packed slice by slice, so its per-device problem count
    decides."""
    P = J.sharding.shard_shape(J.shape)[0] if _batch_split(J) else J.shape[0]
    return dies_per_tile(P, J.shape[-1])


@functools.lru_cache(maxsize=16)
def _per_shard(mesh, axis, dev, pert, block_r, j_dtype, interpret):
    """The kernel run on each device's slice of the problem axis. XLA
    cannot partition a Mosaic kernel itself, and problems never interact,
    so each die anneals exactly the sub-instances it holds, packing them
    two a tile on its own slice."""
    kernel = functools.partial(fused_anneal_kernel, dev=dev, pert=pert,
                               block_r=block_r, j_dtype=j_dtype,
                               interpret=interpret)
    spec = P(axis, None, None)
    return jax.jit(jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False))
