"""The main path's kernels compile for a TPU v5e, checked without the chip.

Each test lowers a kernel at the shapes the system runs and compiles it
for a described (not attached) ``v5e:2x2`` topology, so a kernel the TPU
compiler refuses — a misaligned slice, too much VMEM, a Mosaic call XLA
cannot partition — fails here instead of on the chip. A compiled Pallas
kernel shows up as a ``tpu_custom_call`` in the executable; interpret
mode would leave plain HLO loops instead.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (DEFAULT_PERTURBATION, NOMINAL, DeviceModel, anneal)
from repro.kernels import fused_anneal_kernel
from repro.kernels.sb_kernel import fused_sb_kernel

#: the paper protocol cell (configs/ising64.py "chip64")
P64, R64, N64 = 256, 1024, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("j_dtype", ["float32", "int8"])
def test_fused_anneal_kernel_compiles_at_chip64(one_chip, j_dtype):
    # int8 is the unit-schedule (gradient-descent baseline) fast path
    if j_dtype == "int8":
        dev, pert = DeviceModel(tau_leak_sweeps=float("inf")), NOMINAL
    else:
        dev, pert = DeviceModel(), DEFAULT_PERTURBATION
    txt = _compiled_text(
        lambda J, v: fused_anneal_kernel(J, v, dev=dev, pert=pert,
                                         block_r=256, j_dtype=j_dtype,
                                         interpret=False),
        _sds((P64, N64, N64), one_chip), _sds((P64, R64, N64), one_chip))
    assert "tpu_custom_call" in txt


def test_sb_kernel_compiles(one_chip):
    txt = _compiled_text(
        lambda J, x, y: fused_sb_kernel(J, x, y, variant="bSB", n_steps=400,
                                        block_r=64, interpret=False),
        _sds((8, N64, N64), one_chip), _sds((8, 64, N64), one_chip),
        _sds((8, 64, N64), one_chip))
    assert "tpu_custom_call" in txt


def test_scan_anneal_compiles_at_chip64(one_chip):
    dev = DeviceModel()
    txt = _compiled_text(
        lambda J, v: anneal(J, v, dev=dev, pert=DEFAULT_PERTURBATION).energy,
        _sds((P64, N64, N64), one_chip), _sds((P64, R64, N64), one_chip))
    assert "tpu_custom_call" not in txt      # plain XLA: no Pallas kernel


def test_fused_kernel_runs_per_die_on_a_sharded_fabric_batch(topo):
    """A fabric color phase shards its batch over the dies. XLA refuses to
    partition a Mosaic kernel, so the engine runs it once per die on that
    die's slice: one kernel, no gathering of the batch."""
    from repro.kernels.ops import _per_shard
    mesh = Mesh(np.asarray(topo.devices), ("fabric",))
    batch = NamedSharding(mesh, P("fabric", None, None))
    kernel = _per_shard(mesh, "fabric", DeviceModel(), DEFAULT_PERTURBATION,
                        8, "float32", False)
    compiled = kernel.lower(_sds((64, N64, N64), batch),
                            _sds((64, 8, N64), batch)).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt and "all-gather" not in txt
    assert compiled.output_shardings.spec == P("fabric", None, None)
