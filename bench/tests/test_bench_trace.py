"""The trace reduction, on a small trace recorded here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace


def test_union_of_intervals():
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_ns([(0, 10), (2, 3), (10, 12)]) == 12


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    trace.start(str(d))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                f(x).block_until_ready()
    finally:
        trace.stop()
    return trace.reduce(trace.find(str(d)), jax.devices()[:1])


def test_spans_fix_the_window(recorded):
    assert len(recorded.calls) == 3
    assert recorded.lo == recorded.calls[0][0]
    assert recorded.hi == recorded.calls[-1][1]


def test_busy_and_idle(recorded):
    assert 0 < recorded.busy_s() <= recorded.window_s()
    assert 0 <= recorded.idle_pct() < 100
    per_call = [sum(recorded.busy_ns(s, e)) for s, e in recorded.calls]
    assert all(b > 0 for b in per_call)


def test_ops_by_name(recorded):
    dot = recorded.op_ns(r"dot")
    assert len(dot) == 1 and dot[0] > 0
    assert recorded.op_ns(r"no-such-kernel") == [0]


def test_breakdown_shape(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert len(b["idle_gaps"]) <= trace.TOP
    assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])
    assert sum(s for _, s in b["idle_gaps"]) <= recorded.window_s()
