"""Large-neighbourhood search past the 64-spin die, over a mesh of dies.

The die solves at most ``chip_block`` all-to-all spins. Larger problems are
split into contiguous tiles of ``chip_block - 1`` free spins; a sub-solve
clamps every spin outside one tile and anneals the tile plus ONE boundary
ancilla whose coupling row carries the exact field of the clamped spins
(``h_i = sum_{j not in tile} J_ij s_j``) — exactly one die program. The
bias-free Z2 symmetry makes ancilla pinning unnecessary: candidates are
gauge-fixed after the anneal. This module is the software analogue of
tiling many 64-spin chips into a larger fabric (the scaling move every
multi-chip CMOS Ising paper — BRIM et al., PAPERS.md — treats as the real
question):

* :class:`FabricLayout` blocks the spin index into the tiles of
  :func:`lns_blocks`, colors them (two colors: checkerboard tile parity;
  one color: every tile in one phase) and assigns tiles round-robin to the
  ``K`` dies of the device mesh. Every die in a color class anneals its
  tiles CONCURRENTLY — one batched engine dispatch per color phase, never
  one per tile.

* :class:`FieldExchange` keeps the full coupling matrix resident on the
  mesh, column-tile sharded, and computes the clamped-spin boundary
  fields as sharded ``J_tile @ s`` partial products psummed along the
  tile row axis (``shard_map`` over the ``fabric`` axis) — the halo
  exchange of a chip fabric, replacing host-side ``S @ J[:, blk]``
  gathers. J and sigma are integer valued (DAC levels x +-1), so the
  float32 partial sums are EXACT (|h| <= 15*N << 2^24) and the exchanged
  fields are bit-identical for every mesh size.

* :class:`FabricLNS` runs the sweep: per color phase, fields are
  exchanged once, every (die, tile, restart) sub-instance is written into
  a PREBUILT batch template (the invariant ``J_tile`` blocks are stamped
  once, only the ancilla row/col changes per phase), and the whole color
  class anneals as one engine dispatch sharded die-aligned across the
  mesh. Candidates are then accepted sequentially per tile by EXACT
  float64 delta energy against the current state (an
  incrementally-maintained full-field ledger), so the per-restart
  incumbent is monotonically non-increasing. Crucially the acceptance
  loop runs in CANONICAL ``(problem, tile)`` order, never in the
  die-major slot order of the batch: same-color tiles share no free
  spins but are still coupled through J, so each acceptance shifts the
  field ledger seen by later tiles — iterating in mesh-dependent order
  would make acceptance decisions (and thus results) depend on
  ``n_dies``. With the canonical order the mesh decides only WHERE
  candidates are generated, never what is accepted, and results are
  bit-identical across mesh sizes.

The registry serves two settings of the one search: ``fabric-jax`` (two
colors over every device of the mesh) and ``chip-lns`` (one color on one
die: every tile of a sweep in one dispatch).

Dispatch ledger: ``colors x outer_sweeps`` engine dispatches per solve
(the anneal bursts that occupy dies), plus ``problems x colors x
outer_sweeps`` field exchanges (the halo traffic), reported separately.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tracing import phase

#: the fabric mesh axis name — one entry per virtual die.
FABRIC_AXIS = "fabric"


def fabric_mesh(n_dies: Optional[int] = None) -> Mesh:
    """A 1-D mesh of ``n_dies`` local devices (default: all of them).

    On an accelerator host the dies are its chips. On a CPU host,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` presents K
    devices, so the fabric paths are exercised (and CI-gated) without
    accelerator hardware.
    """
    devs = jax.devices()
    k = len(devs) if n_dies is None else int(n_dies)
    if k < 1:
        raise ValueError(f"fabric mesh needs >= 1 die, got {k}")
    if k > len(devs):
        platform = devs[0].platform
        hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count={k} "
                f"(before jax import) to emulate a {k}-die fabric on the "
                f"host" if platform == "cpu" else
                f"this host has {len(devs)} {devs[0].device_kind} "
                f"device(s); ask for at most {len(devs)} dies")
        raise ValueError(
            f"fabric mesh of {k} dies requested but only {len(devs)} "
            f"{platform} device(s) visible; {hint}")
    return Mesh(np.asarray(devs[:k]), (FABRIC_AXIS,))


def lns_blocks(n: int, free_block: int) -> list[np.ndarray]:
    """Balanced contiguous partition of [0, n) into ceil(n/free_block)
    blocks of at most ``free_block`` spins each."""
    if free_block < 1:
        raise ValueError(f"free_block must be >= 1, got {free_block}")
    n_blocks = max(1, -(-n // free_block))
    return [np.asarray(b) for b in np.array_split(np.arange(n), n_blocks)]


@dataclasses.dataclass(frozen=True)
class FabricLayout:
    """Tile grid of one problem over a ``n_dies``-die fabric.

    Tiles are the contiguous balanced blocks of :func:`lns_blocks` (at
    most ``free_block`` spins each, so tile + boundary ancilla fits one
    die), colored by parity (``colors=2``, the checkerboard) or all in one
    phase (``colors=1``), and assigned round-robin within each color
    class, so every color phase spreads its tiles evenly across all
    ``n_dies`` dies.
    """
    n: int
    n_dies: int
    free_block: int
    tiles: tuple                      # tuple[np.ndarray] spin-index blocks
    colors: int = 2

    @classmethod
    def build(cls, n: int, n_dies: int, free_block: int = 63,
              colors: int = 2) -> "FabricLayout":
        if n_dies < 1:
            raise ValueError(f"n_dies must be >= 1, got {n_dies}")
        if colors not in (1, 2):
            raise ValueError(f"colors must be 1 or 2, got {colors}")
        return cls(n=int(n), n_dies=int(n_dies), free_block=int(free_block),
                   tiles=tuple(lns_blocks(n, free_block)), colors=int(colors))

    @property
    def n_tiles(self) -> int:
        return len(self.tiles)

    @property
    def n_colors(self) -> int:
        """The requested colors, once there are that many tiles."""
        return min(self.colors, self.n_tiles)

    def color_of(self, t: int) -> int:
        return t % self.n_colors

    def die_of(self, t: int) -> int:
        # round-robin by rank WITHIN the color class, not by raw tile
        # index: ``t % n_dies`` would alias with the parity coloring on
        # even meshes and pile a whole color phase onto same-parity dies
        return (t // self.n_colors) % self.n_dies

    def color_tiles(self, color: int) -> list:
        return [t for t in range(self.n_tiles) if self.color_of(t) == color]

    def die_color_tiles(self, color: int) -> list:
        """Per-die tile lists for one color phase: ``[(die, [t, ...])]``
        for every die (possibly empty — an idle die in this phase)."""
        per_die: list = [[] for _ in range(self.n_dies)]
        for t in self.color_tiles(color):
            per_die[self.die_of(t)].append(t)
        return list(enumerate(per_die))

    def occupancy(self, color: int) -> dict:
        """The phase's die-occupancy ledger: how many tiles each die
        anneals, how many dies idle, and the per-die padding the batched
        dispatch needs to stay die-aligned."""
        counts = [len(ts) for _, ts in self.die_color_tiles(color)]
        peak = max(counts) if counts else 0
        return {
            "tiles": int(sum(counts)),
            "dies_busy": int(sum(1 for c in counts if c)),
            "dies_idle": int(sum(1 for c in counts if not c)),
            "max_tiles_per_die": int(peak),
            "pad_tiles": int(sum(peak - c for c in counts)),
        }


class FieldExchange:
    """Device-resident sharded boundary-field computation for one problem.

    The (padded) coupling matrix lives on the mesh column-tile sharded —
    die ``k`` holds ``J[:, cols_k]`` — and ``fields(s)`` returns the full
    local field ``h = s @ J`` by summing each die's partial
    ``s[cols_k] @ J[:, cols_k]^T`` with a ``psum`` along the tile row
    axis. One call = one halo exchange; J never moves again after
    placement.
    """

    def __init__(self, J_levels: np.ndarray, mesh: Mesh):
        J = np.asarray(J_levels, dtype=np.float32)
        if J.ndim != 2 or J.shape[0] != J.shape[1]:
            raise ValueError(f"FieldExchange takes one (N, N) coupling "
                             f"matrix, got {J.shape}")
        self.mesh = mesh
        self.n = J.shape[0]
        k = int(mesh.shape[FABRIC_AXIS])
        self.n_pad = -(-self.n // k) * k
        if self.n_pad != self.n:
            Jp = np.zeros((self.n_pad, self.n_pad), dtype=np.float32)
            Jp[:self.n, :self.n] = J
            J = Jp
        self._J = jax.device_put(
            J, NamedSharding(mesh, P(None, FABRIC_AXIS)))
        self._fn = self._build(mesh)
        self.exchanges = 0

    # jitted exchange fns keyed on (device ids, axis names) — meshes over
    # the same devices compare equal in jax, so fresh Mesh objects from
    # repeated solves reuse one compiled executable instead of pinning a
    # new Mesh + shard_map executable per object for the process lifetime
    _FN_CACHE: dict = {}

    @classmethod
    def _build(cls, mesh: Mesh):
        key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
        fn = cls._FN_CACHE.get(key)
        if fn is None:
            fn = cls._FN_CACHE[key] = jax.jit(cls._make_exchange(mesh))
        return fn

    @staticmethod
    def _make_exchange(mesh: Mesh):
        def partial_fields(J_loc, s_loc):
            # J_loc (N_pad, N_pad/K) column tile, s_loc (R, N_pad/K):
            # this die's contribution to every row's field, then row-sum
            # across the tile row axis.
            h = jnp.einsum("rc,nc->rn", s_loc, J_loc)
            return jax.lax.psum(h, FABRIC_AXIS)

        return jax.shard_map(partial_fields, mesh=mesh,
                             in_specs=(P(None, FABRIC_AXIS),
                                       P(None, FABRIC_AXIS)),
                             out_specs=P(None, None))

    def fields(self, s: np.ndarray) -> np.ndarray:
        """``h = s @ J`` for ±1 states ``s (R, N)`` -> ``(R, N)`` float32.

        Exact: J is integer DAC levels and s is ±1, so every partial sum
        is an integer below 2^24 — float32 arithmetic loses nothing and
        the psum order across dies cannot change the result.
        """
        s = np.asarray(s, dtype=np.float32)
        if s.shape[-1] != self.n:
            raise ValueError(f"state has {s.shape[-1]} spins, expected "
                             f"{self.n}")
        if self.n_pad != self.n:
            s = np.concatenate(
                [s, np.zeros(s.shape[:-1] + (self.n_pad - self.n,),
                             dtype=np.float32)], axis=-1)
        s_dev = jax.device_put(
            s, NamedSharding(self.mesh, P(None, FABRIC_AXIS)))
        h = np.asarray(self._fn(self._J, s_dev))
        self.exchanges += 1
        return h[:, :self.n]


class FabricLNS:
    """Colored large-neighborhood search over a die mesh.

    ``solve`` minimizes level-space ``H = -0.5 s'Js`` and returns
    per-problem ``(energies (R,), sigma (R, N), init_energies (R,))`` plus
    the engine dispatch count. All tiles of a color phase anneal
    concurrently across the mesh, per-sweep dispatches are ``n_colors``
    (never one per tile), and the boundary fields feeding the candidate
    anneals come from the sharded :class:`FieldExchange`. Acceptance is
    sequential, float64-exact, and in canonical (problem, tile) order
    regardless of which die generated each candidate (per-restart
    incumbents are monotone), so the mesh size cannot change the result —
    only where the work runs. ``colors`` is 2 (checkerboard) or 1 (every
    tile of a sweep in one phase, the ``chip-lns`` setting).

    After ``solve``, ``self.ledger`` holds the occupancy/timing record
    the registry surfaces as ``meta['fabric']``.
    """

    def __init__(self, engine, mesh: Optional[Mesh] = None,
                 chip_block: int = 64, inner_runs: int = 8, colors: int = 2):
        self.engine = engine
        self.mesh = mesh if mesh is not None else fabric_mesh()
        self.chip_block = chip_block
        self.inner_runs = inner_runs
        self.colors = colors
        self.n_dies = int(self.mesh.shape[FABRIC_AXIS])
        self.ledger: dict = {}

    # -- hoisted per-solve precompute -------------------------------------
    def _plan(self, Js: Sequence[np.ndarray]):
        """Everything sweep-invariant, computed once: layouts, field
        exchangers, per-tile couplings, and one batch TEMPLATE per color
        with every ``J_tile`` block already stamped (per phase only the
        ancilla row/col is rewritten)."""
        cb = self.chip_block
        layouts = [FabricLayout.build(J.shape[0], self.n_dies, cb - 1,
                                      self.colors) for J in Js]
        exchangers = [FieldExchange(J, self.mesh) for J in Js]
        n_colors = max(l.n_colors for l in layouts)
        colors = []
        for c in range(n_colors):
            # die-aligned row order: die 0's tiles (every problem), then
            # die 1's, ... padded per die to the fabric-wide peak so the
            # batch shards into equal contiguous per-die chunks.
            per_die: list = [[] for _ in range(self.n_dies)]
            for p, lay in enumerate(layouts):
                if c >= lay.n_colors:
                    continue
                for d, ts in lay.die_color_tiles(c):
                    per_die[d].extend((p, t) for t in ts)
            peak = max(len(x) for x in per_die)
            if peak == 0:
                colors.append(None)
                continue
            slots = []                       # (p, t) or None (idle pad)
            for d in range(self.n_dies):
                slots.extend(per_die[d])
                slots.extend([None] * (peak - len(per_die[d])))
            colors.append({"slots": slots, "peak": peak,
                           "occupancy": [
                               lay.occupancy(c) if c < lay.n_colors else None
                               for lay in layouts]})
        tiles = {}
        for p, lay in enumerate(layouts):
            J = Js[p]
            for t, blk in enumerate(lay.tiles):
                lo, hi = int(blk[0]), int(blk[-1]) + 1   # contiguous
                Jbb64 = J[lo:hi, lo:hi]
                tiles[(p, t)] = (lo, hi, Jbb64, Jbb64.astype(np.float32),
                                 np.ascontiguousarray(J[lo:hi, :]))
        return layouts, exchangers, colors, tiles

    def _template(self, color_plan, tiles, restarts):
        """(S, cb, cb) float32 batch with J_tile blocks stamped; rows are
        (die-slot, restart)-major and idle-pad slots stay all-zero.
        ``accept`` is the same spans re-sorted into canonical (problem,
        tile) order — acceptance must NOT follow the die-major batch
        order, which depends on n_dies (see module docstring)."""
        cb = self.chip_block
        S = len(color_plan["slots"]) * restarts
        batch = np.zeros((S, cb, cb), dtype=np.float32)
        spans = []
        for k, slot in enumerate(color_plan["slots"]):
            rows = slice(k * restarts, (k + 1) * restarts)
            if slot is None:
                spans.append((None, rows))
                continue
            lo, hi, _, Jbb32, _ = tiles[slot]
            m = hi - lo
            batch[rows, 1:m + 1, 1:m + 1] = Jbb32
            spans.append((slot, rows))
        accept = sorted((sp for sp in spans if sp[0] is not None),
                        key=lambda sp: sp[0])
        return batch, spans, accept

    # -- the solve loop ----------------------------------------------------
    def solve(self, J_list, restarts: int, outer_sweeps: int, seed: int = 0):
        from ..core.lfsr import lfsr_voltage_inits
        cb = self.chip_block
        rng = np.random.default_rng(seed)
        Js = [np.asarray(J, dtype=np.float64) for J in J_list]
        # one init stream for every color count: seed-equal solves start
        # equal
        states = [rng.choice([-1.0, 1.0], size=(restarts, J.shape[0]))
                  for J in Js]

        def energies(p):
            S = states[p]
            return -0.5 * np.einsum("ri,ij,rj->r", S, Js[p], S)

        init_e = [energies(p) for p in range(len(Js))]

        t_plan0 = time.perf_counter()
        layouts, exchangers, colors, tiles = self._plan(Js)
        templates = [None if cp is None else
                     self._template(cp, tiles, restarts) for cp in colors]
        # exact float64 full-field ledger F = s @ J, maintained
        # incrementally under acceptance (the acceptance-side counterpart
        # of the device-side exchange)
        F = [states[p] @ Js[p] for p in range(len(Js))]
        t_plan = time.perf_counter() - t_plan0

        shard = NamedSharding(self.mesh, P(FABRIC_AXIS, None, None))
        dispatches = 0
        batch_devices = 0
        sweeps_ledger = []
        for sweep in range(outer_sweeps):
            rec = {"t_fields": 0.0, "t_assemble": 0.0, "t_engine": 0.0,
                   "t_accept": 0.0}
            t_sweep0 = time.perf_counter()
            for c, (cplan, tmpl) in enumerate(zip(colors, templates)):
                if cplan is None:
                    continue
                batch, spans, accept = tmpl
                # the ledger's phase times and the trace's spans share one
                # boundary per phase
                where = dict(sweep=sweep, color=c, tiles=len(accept))

                # 1) halo exchange: sharded J_tile @ s row-sums (exact)
                with phase("fabric.fields", rec, "t_fields", **where):
                    h_all = [exchangers[p].fields(states[p])
                             if any(s is not None and s[0] == p
                                    for s, _ in spans) else None
                             for p in range(len(Js))]

                # 2) stamp the ancilla boundary row/col into the template
                with phase("fabric.assemble", rec, "t_assemble", **where):
                    for slot, rows in spans:
                        if slot is None:
                            continue
                        p, t = slot
                        lo, hi, Jbb64, _, _ = tiles[slot]
                        m = hi - lo
                        Sb = states[p][:, lo:hi]
                        h = (h_all[p][:, lo:hi].astype(np.float64)
                             - Sb @ Jbb64)
                        batch[rows, 0, 1:m + 1] = h
                        batch[rows, 1:m + 1, 0] = h
                    # phase LFSR seed: seed + 7919(sweep+1), plus
                    # 104729(c+1) per color phase when there are two
                    # colors; one color keeps the bare sweep stream
                    v0 = lfsr_voltage_inits(
                        cb, self.inner_runs,
                        seed=seed + 7919 * (sweep + 1)
                        + 104729 * (c + 1) * (self.colors - 1))
                    v0b = np.broadcast_to(v0, (batch.shape[0],) + v0.shape)

                # 3) ONE die-aligned engine dispatch for the color class
                with phase("fabric.engine", rec, "t_engine", **where):
                    batch_dev = jax.device_put(batch, shard)
                    v0_dev = jax.device_put(np.ascontiguousarray(v0b), shard)
                    batch_devices = len(batch_dev.sharding.device_set)
                    res = self.engine.run(batch_dev, v0_dev)
                    e = np.asarray(res.energy)             # (S, inner_runs)
                    sig = np.asarray(res.sigma)            # (S, inner, cb)
                dispatches += 1

                # 4) sequential EXACT acceptance (monotone incumbents) in
                # canonical (problem, tile) order — NOT die-major batch
                # order, so results cannot depend on the mesh size
                with phase("fabric.accept", rec, "t_accept", **where):
                    best = e.argmin(axis=1)
                    cand_all = np.take_along_axis(
                        sig, best[:, None, None], axis=1)[:, 0]
                    for slot, rows in accept:
                        p, t = slot
                        lo, hi, Jbb64, _, Jrows64 = tiles[slot]
                        m = hi - lo
                        cand = cand_all[rows]
                        # gauge-fix the boundary ancilla to +1, trim to tile
                        cand = (cand[:, 1:m + 1] *
                                cand[:, :1]).astype(np.float64)
                        cur = states[p][:, lo:hi]
                        # exact current field
                        h = F[p][:, lo:hi] - cur @ Jbb64
                        e_new = -np.einsum("rm,rm->r", h, cand) - 0.5 * \
                            np.einsum("rm,mk,rk->r", cand, Jbb64, cand)
                        e_old = -np.einsum("rm,rm->r", h, cur) - 0.5 * \
                            np.einsum("rm,mk,rk->r", cur, Jbb64, cur)
                        acc = np.flatnonzero(e_new < e_old - 1e-9)
                        if len(acc):
                            F[p][acc] += (cand[acc] - cur[acc]) @ Jrows64
                            states[p][np.ix_(acc, np.arange(lo, hi))] = \
                                cand[acc]
            rec["t_total"] = time.perf_counter() - t_sweep0
            sweeps_ledger.append(rec)

        self.ledger = {
            "mesh_devices": self.n_dies,
            # devices the dispatched batches actually spanned
            "batch_devices": batch_devices,
            "n_colors": max(l.n_colors for l in layouts),
            "n_tiles": [l.n_tiles for l in layouts],
            # fabric-wide tiles-per-die peak of each color phase — the
            # quantity a die-occupancy model multiplies (idle pads ride
            # along but anneal zero-J tiles)
            "color_peaks": [cp["peak"] for cp in colors if cp],
            "restarts": restarts,
            "inner_runs": self.inner_runs,
            "occupancy": [
                {"color": c, **{f"p{p}": o for p, o in
                                enumerate(cp["occupancy"]) if o}}
                for c, cp in enumerate(colors) if cp],
            "field_exchanges": int(sum(x.exchanges for x in exchangers)),
            "plan_s": t_plan,
            "per_sweep": sweeps_ledger,
            "dispatches": dispatches,
        }
        out = []
        for p in range(len(Js)):
            out.append((energies(p), states[p].astype(np.int8), init_e[p]))
        return out, dispatches
