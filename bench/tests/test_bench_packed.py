"""``kernel.packed_pct``'s reader on made-up traces: the share of the
anneal kernel's device time spent in the kernels that pack two dies into
one tile (names ending in ``_x2``)."""
import types

import pytest

from bench.lib import manifest
from bench.trace import Reduced


def _trace(ops):
    t, timeline = 0, []
    for name, ns in ops:
        timeline.append((t, t + ns, name))
        t += ns
    return Reduced(lo=0, hi=t, devices=[timeline], calls=[(0, t)], host=[])


@pytest.mark.parametrize("ops,share", [
    ([("fused_anneal_kernel.1", 30), ("copy.4", 10)], 0.0),
    ([("fused_anneal_kernel_x2.1", 30), ("copy.4", 10)], 100.0),
    ([("fused_anneal_kernel_int8_x2.1", 30)], 100.0),
    ([("fused_anneal_kernel_x2.1", 20), ("fused_anneal_kernel.1", 20)],
     50.0),
    ([("fused_anneal_kernel_int8_x2.1", 10),
      ("fused_anneal_kernel_int8.1", 10)], 50.0),
    ([("copy.4", 10)], None),                   # no anneal kernel at all
])
def test_packed_share_reader(ops, share):
    reader = manifest.code("metrics", "kernel.packed_pct")
    assert reader.read(types.SimpleNamespace(trace=_trace(ops))) == share


def test_packed_share_reader_without_a_trace():
    reader = manifest.code("metrics", "kernel.packed_pct")
    assert reader.read(types.SimpleNamespace(trace=None)) is None


def test_packed_and_int8_shares_read_the_same_packed_int8_kernel():
    """The packed int8 kernel still counts as int8."""
    trace = _trace([("fused_anneal_kernel_int8_x2.1", 30)])
    int8 = manifest.code("metrics", "kernel.int8_pct")
    assert int8.read(types.SimpleNamespace(trace=trace)) == 100.0
