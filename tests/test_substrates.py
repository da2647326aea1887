"""Data pipeline, optimizer, checkpointing, fault tolerance, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, load_pytree, save_pytree
from repro.data import DataState, SyntheticLM, make_batch_iterator
from repro.distributed import StragglerDetector, StepFailure, resilient_step
from repro.optim import (AdamWConfig, adamw, apply_updates,
                         clip_by_global_norm, init_opt_state,
                         int8_compress, int8_decompress,
                         linear_warmup_cosine)


# ---- data ------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    ds = SyntheticLM(vocab_size=1000, seq_len=32, global_batch=8)
    t1, l1 = ds.batch_at(5)
    t2, l2 = ds.batch_at(5)
    assert np.array_equal(t1, t2)
    assert np.array_equal(t1[:, 1:], l1[:, :-1])   # next-token labels
    # resume from a checkpointed step
    st = DataState(step=3)
    it = make_batch_iterator(ds, st)
    b3 = next(it)
    assert np.array_equal(b3["tokens"], ds.batch_at(3)[0])
    assert st.step == 4


def test_data_shards_disjoint():
    ds = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=8)
    s0, _ = ds.batch_at(0, shard=0, num_shards=2)
    s1, _ = ds.batch_at(0, shard=1, num_shards=2)
    assert s0.shape == (4, 16)
    assert not np.array_equal(s0, s1)


def test_data_learnable_structure():
    ds = SyntheticLM(vocab_size=64, seq_len=64, global_batch=4)
    t, l = ds.batch_at(0)
    # consecutive deltas constant per row -> bigram-learnable
    d = (l - t) % 64
    assert (d.std(axis=1) < d.std() + 64).all()


# ---- optimizer --------------------------------------------------------------

def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = init_opt_state(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0)
    for _ in range(100):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        upd, opt = adamw(g, opt, params, cfg)
        params = apply_updates(params, upd)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_clipping():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, max_norm=1.0)
    assert np.isclose(float(norm), np.sqrt(1000.0))
    cn = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert np.isclose(cn, 1.0, rtol=1e-5)


def test_schedule_shape():
    assert float(linear_warmup_cosine(0, 10, 100)) == 0.0
    assert float(linear_warmup_cosine(10, 10, 100)) == pytest.approx(1.0)
    assert float(linear_warmup_cosine(100, 10, 100)) == pytest.approx(0.1, abs=0.02)


def test_int8_compression_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(256,)) * 3, jnp.float32)
    q, s = int8_compress(x)
    y = int8_decompress(q, s)
    assert q.dtype == jnp.int8
    assert float(jnp.abs(x - y).max()) <= float(s) * 0.51


def test_compressed_psum_error_feedback(rng):
    from repro.optim import compressed_psum
    from repro.launch.mesh import _mesh_kwargs
    mesh = jax.make_mesh((1,), ("d",), **_mesh_kwargs(1))
    x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)

    def f(x):
        out, resid = compressed_psum(x, "d")
        return out, resid

    out, resid = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(None),
        out_specs=jax.sharding.PartitionSpec(None)))(x)
    np.testing.assert_allclose(np.asarray(out + resid), np.asarray(x),
                               rtol=1e-5, atol=1e-6)


# ---- checkpointing ----------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.asarray(3)}}
    p = os.path.join(tmp_path, "x.npz")
    save_pytree(p, tree, {"step": 7})
    out = load_pytree(p, tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    assert int(out["b"]["c"]) == 3


def test_checkpointer_latest_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"w": np.zeros(3)}
    for s in (10, 20, 30):
        ck.save(s, {"w": np.full(3, s)})
    assert ck.latest_step() == 30
    restored, meta = ck.restore(tree)
    assert meta["step"] == 30
    assert restored["w"][0] == 30
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(files) == 2   # keep=2 retention


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    p = os.path.join(tmp_path, "x.npz")
    save_pytree(p, {"w": np.zeros((2, 2))})
    with pytest.raises(ValueError):
        load_pytree(p, {"w": np.zeros((3, 3))})


# ---- fault tolerance ---------------------------------------------------------

def test_resilient_step_retries_and_restores():
    calls = {"n": 0, "restores": 0}

    def flaky(state, batch):
        calls["n"] += 1
        if calls["n"] < 3:
            raise StepFailure("injected device failure")
        return state + 1, {"loss": 1.0}

    def restore():
        calls["restores"] += 1
        return 100

    run = resilient_step(flaky, restore, max_retries=3)
    state, metrics = run(0, None)
    assert state == 101            # restored to 100, then +1
    assert calls["restores"] == 2


def test_resilient_step_nan_guard():
    def bad(state, batch):
        return state, {"loss": float("nan")}

    run = resilient_step(bad, lambda: 0, max_retries=1)
    with pytest.raises(StepFailure):
        run(0, None)


def test_resilient_step_propagates_programming_bugs():
    """Regression: a bare RuntimeError (jax tracer misuse, API bugs) must
    fail loudly on the FIRST call — not burn the restore/retry budget
    replaying a deterministic bug four times before surfacing it wrapped
    in a StepFailure."""
    calls = {"n": 0, "restores": 0}

    def buggy(state, batch):
        calls["n"] += 1
        raise RuntimeError("leaked tracer: jax API misuse")

    def restore():
        calls["restores"] += 1
        return 0

    run = resilient_step(buggy, restore, max_retries=3)
    with pytest.raises(RuntimeError) as ei:
        run(0, None)
    assert not isinstance(ei.value, StepFailure)   # the original, unwrapped
    assert calls["n"] == 1 and calls["restores"] == 0


def test_resilient_step_retries_xla_runtime_errors():
    """Genuine device failures (the XLA runtime error types) still get the
    restore-and-replay treatment."""
    from repro.distributed.fault_tolerance import RETRYABLE_ERRORS
    xla_types = [e for e in RETRYABLE_ERRORS if e is not StepFailure]
    assert xla_types, "jax runtime error types missing from RETRYABLE_ERRORS"
    calls = {"n": 0}

    def flaky(state, batch):
        calls["n"] += 1
        if calls["n"] < 2:
            raise xla_types[0]("RESOURCE_EXHAUSTED: device OOM")
        return state + 1, {"loss": 0.5}

    run = resilient_step(flaky, lambda: 7, max_retries=2)
    state, _ = run(0, None)
    assert state == 8 and calls["n"] == 2          # restored to 7, then +1


def test_straggler_detector():
    det = StragglerDetector(patience=3)
    flagged = False
    for _ in range(20):
        flagged |= det.observe(0.1)
    assert not flagged
    for _ in range(10):
        flagged |= det.observe(10.0)   # persistent outlier host
    assert flagged
