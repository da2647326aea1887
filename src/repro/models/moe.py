"""Top-k routed MoE with sort-based (active-FLOPs-only) dispatch.

Design notes for scale:
* The GShard one-hot dispatch einsum costs O(T * E * C * D) FLOPs — at 64
  experts it would exceed the expert FLOPs themselves and poison the roofline
  with fake compute. We instead route via argsort + gather, whose HLO FLOPs
  are ~ the true active compute 2 * E * C * (3 D F) (SwiGLU), plus O(T k D)
  data movement.
* Expert weights shard over 'model' on the EXPERT axis when divisible
  (olmoe: 64/16), else on the d_ff axis (granite: 40 experts, d_ff 512).
  The sharding decision lives in zoo.param_specs, not here.
* Capacity: C = ceil(T * k / E * capacity_factor); overflow tokens are
  dropped (their combine weight contributes nothing) — standard drop policy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import act_fn, active_mesh, dense_init, split_keys


def init_moe(key, d_model: int, d_ff: int, n_experts: int):
    ks = split_keys(key, ["router", "wi", "wg", "wo"])
    return {
        "router": dense_init(ks["router"], d_model, n_experts),
        "wi": jax.vmap(lambda k: dense_init(k, d_model, d_ff))(
            jax.random.split(ks["wi"], n_experts)),
        "wg": jax.vmap(lambda k: dense_init(k, d_model, d_ff))(
            jax.random.split(ks["wg"], n_experts)),
        "wo": jax.vmap(lambda k: dense_init(k, d_ff, d_model))(
            jax.random.split(ks["wo"], n_experts)),
    }


def _moe_compute(params, x, *, top_k: int, cap: int, act: str,
                 constrain: bool = True):
    """Batch-local sort-based dispatch + expert SwiGLU + combine.

    Runs either under GSPMD (constrain=True: batch-sharding constraints on
    every routing tensor) or inside a shard_map body (constrain=False: all
    shapes already local). If the expert weights' F axis is locally sliced
    (shard_map path), the returned tensor is a PARTIAL sum over F — callers
    psum it; combine-before-psum is what shrinks the all-reduce from
    (B, E, cap, D) to (B, S, D) granularity.
    """
    from .common import shard as _shard
    shard = _shard if constrain else (lambda t, *a: t)
    b, s, d = x.shape
    e = params["router"].shape[-1]
    tk = s * top_k
    brow = jnp.arange(b)[:, None]

    logits = (x.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))          # (B,S,E)
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(gates, top_k)                       # (B,S,k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(b, tk)
    flat_t = jnp.broadcast_to(jnp.repeat(jnp.arange(s), top_k)[None], (b, tk))
    flat_w = w.reshape(b, tk)
    order = jnp.argsort(flat_e, axis=-1)                       # stable, per row
    se = jnp.take_along_axis(flat_e, order, axis=1)
    st = jnp.take_along_axis(flat_t, order, axis=1)
    sw = jnp.take_along_axis(flat_w, order, axis=1)
    se = shard(se, "batch", None)
    counts = jnp.zeros((b, e), jnp.int32).at[brow, se].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts               # exclusive
    pos = jnp.arange(tk)[None] - jnp.take_along_axis(starts, se, axis=1)
    keep = pos < cap
    # overflow tokens write ZEROS into a clamped slot — additive no-op, and
    # avoids a sink row (the +1 row forced a (B, E*cap+1, D) pad+copy pair
    # per layer in the compiled HLO)
    dest = jnp.where(keep, se * cap + pos, e * cap - 1)
    xg = jnp.take_along_axis(x, st[..., None], axis=1)         # (B,Tk,D)
    buf = jnp.zeros((b, e * cap, d), x.dtype)
    buf = buf.at[brow, dest].add(jnp.where(keep[..., None], xg, 0))
    xe = shard(buf.reshape(b, e, cap, d), "batch", None, None, None)

    a = act_fn(act)
    hi = jnp.einsum("becd,edf->becf", xe, params["wi"].astype(x.dtype))
    hg = jnp.einsum("becd,edf->becf", xe, params["wg"].astype(x.dtype))
    ye = jnp.einsum("becf,efd->becd", a(hg) * hi,
                    params["wo"].astype(x.dtype))
    ye = shard(ye, "batch", None, None, None)

    yflat = ye.reshape(b, e * cap, d)
    contrib = jnp.where(keep[..., None],
                        jnp.take_along_axis(yflat, dest[..., None], axis=1)
                        * sw[..., None].astype(x.dtype),
                        0)
    out = jnp.zeros((b, s, d), x.dtype).at[brow, st].add(contrib)
    return shard(out, "batch", None, None)


def _moe_mesh():
    mesh = active_mesh()
    if mesh is None or "model" not in (mesh.axis_names or ()):
        return None
    return mesh


def apply_moe(params, x, *, top_k: int, capacity_factor: float = 1.25,
              act: str = "silu"):
    """x: (B, S, D) -> (B, S, D).

    Dispatch is BATCH-LOCAL: capacity is per sequence and the
    argsort/scatter never crosses the data-sharded batch axis. (A single
    global token sort forces GSPMD to replicate the dispatch state on every
    device — measured 428 GiB/device on granite train_4k.)

    Under an active mesh, the whole block runs in shard_map with the expert
    F axis manually sharded over 'model' and ONE psum at (B, S, D)
    granularity after the combine — under plain GSPMD the F-contraction
    all-reduce fires at (B, E, cap, D) granularity, 10x the tokens
    (measured 51 s/step collective on granite train_4k; see EXPERIMENTS.md
    §Perf). Works for any expert count (40 or 64), no padding.
    """
    b, s, d = x.shape
    e = params["router"].shape[-1]
    cap = int(max(top_k, round(s * top_k / e * capacity_factor)))
    cap = min(cap, s * top_k)

    mesh = _moe_mesh()
    f_total = params["wi"].shape[-1]
    tp = mesh.shape["model"] if mesh is not None else 1
    bax = tuple(a for a in ("pod", "data")
                if mesh is not None and a in mesh.axis_names)
    dsize = 1
    for a in bax:
        dsize *= mesh.shape[a]
    use_shard_map = (mesh is not None and f_total % tp == 0
                     and b % max(dsize, 1) == 0)
    if not use_shard_map:
        return _moe_compute(params, x, top_k=top_k, cap=cap, act=act,
                            constrain=True)

    from jax.sharding import PartitionSpec as P

    def local_fn(x_l, router, wi, wg, wo, ln_if_any=None):
        p_l = {"router": router, "wi": wi, "wg": wg, "wo": wo}
        partial = _moe_compute(p_l, x_l, top_k=top_k, cap=cap, act=act,
                               constrain=False)
        return jax.lax.psum(partial, "model")

    in_specs = (P(bax if bax else None, None, None),   # x
                P(),                                   # router (replicated)
                P(None, None, "model"),                # wi: F sliced
                P(None, None, "model"),                # wg
                P(None, "model", None))                # wo: F sliced
    out_specs = P(bax if bax else None, None, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, params["router"], params["wi"], params["wg"], params["wo"])
