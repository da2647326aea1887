"""AnnealEngine — the single dispatching front-end for every anneal path.

The repo has three ways to integrate the chip dynamics:

  'scan'   — ``core.annealer.anneal``: pure-JAX lax.scan. Runs anywhere,
             supports noise and energy-trajectory recording, and is what
             the sharded multi-device layouts (launch/dryrun.py) partition.
  'fused'  — ``kernels.ising_anneal.fused_anneal_kernel``: whole-anneal
             Pallas VMEM kernel, schedule derived in-kernel (interpret
             mode on CPU; compiled on TPU).
  (the sharded multi-device path is 'scan' under a mesh — the engine keeps
  the spin-axis constraint intact, so `jax.set_mesh(...)` around
  ``run``/``solve`` shards exactly as before.)

``AnnealEngine`` owns the choice: callers hand it (J, v0) and get an
``AnnealResult`` back. Dispatch rules (see ENGINE.md):

  1. Features first: noise or trajectory recording forces 'scan' (the fused
     kernel integrates in VMEM and never materializes intermediates).
  2. Explicit ``path=`` wins otherwise.
  3. 'auto': 'fused' on TPU, 'scan' elsewhere (Pallas interpret mode is a
     correctness harness, not a fast path).
  4. j_dtype auto-selection: 'int8' when the schedule is identically one
     (``unit_scales``) and J is integer-levels (bit-exact MXU fast path);
     otherwise the device's compute preference.
  5. block_r: autotune-cache hit, else a size heuristic.

The block_r/path autotuner times real (shortened) anneals for each
candidate and persists winners to a small JSON cache keyed on
(backend, N, R, P, j_dtype, schedule-kind) so repeat workloads skip the
search — set ``autotune=True`` or call ``autotune()`` directly.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..tracing import span
from ..utils import load_json_cache, store_json_cache
from .annealer import anneal, AnnealResult
from .device_model import DeviceModel
from .perturbation import (PerturbationConfig, DEFAULT_PERTURBATION,
                           unit_scales)

_BLOCK_R_CANDIDATES = (64, 128, 256)
_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "annealengine.json")


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """A fully-resolved dispatch decision for one (P, R, N) workload."""
    path: str                    # 'scan' | 'fused'
    block_r: int                 # fused-kernel run-block (ignored by scan)
    j_dtype: str                 # 'float32' | 'bfloat16' | 'int8'
    interpret: bool              # Pallas interpret mode (True off-TPU)
    reason: str = ""             # human-readable provenance ('auto', 'cache',
                                 # 'autotuned', 'explicit', 'feature:…')


def _next_pow2(x: int) -> int:
    p = 8
    while p < x:
        p *= 2
    return p


def _cache_path() -> str:
    return os.environ.get(_CACHE_ENV, _DEFAULT_CACHE)


# shared atomic best-effort JSON cache (also backs the oracle cache)
_load_cache = load_json_cache
_store_cache = store_json_cache


class AnnealEngine:
    """Unified batched-solve hot path. One instance per (device, schedule).

    >>> eng = AnnealEngine()
    >>> res = eng.run(Jq, v0)            # AnnealResult
    """

    def __init__(self,
                 device: DeviceModel | None = None,
                 perturbation: PerturbationConfig | None = None,
                 path: str = "auto",
                 autotune: bool = False,
                 cache_path: Optional[str] = None):
        if path not in ("auto", "scan", "fused"):
            raise ValueError(f"unknown path {path!r}")
        self.device = device or DeviceModel()
        self.perturbation = (perturbation if perturbation is not None
                             else DEFAULT_PERTURBATION)
        self.path = path
        self.autotune_enabled = autotune
        self.cache_path = cache_path or _cache_path()
        self._cache = _load_cache(self.cache_path)

    # -- planning ----------------------------------------------------------
    def _key(self, P: int, R: int, N: int, j_dtype: str) -> str:
        # schedule kind from the shared predicates (DeviceModel.has_leakage
        # + PerturbationConfig.enabled) — "unit" is exactly their conjunction
        # being false/false, so the cache key can never disagree with the
        # unit_scales() fast-path gate.
        if unit_scales(self.device, self.perturbation):
            sched = "unit"
        elif self.perturbation.enabled:
            sched = "pert"
        else:
            assert self.device.has_leakage
            sched = "leak"
        return (f"{jax.default_backend()}|N={N}|R={R}|P={P}"
                f"|j={j_dtype}|sched={sched}")

    def _auto_j_dtype(self, J=None) -> str:
        # int8 is bit-exact vs float32 only when (a) the schedule is unit,
        # (b) J is integer levels, AND (c) drive_dt is a power of two (the
        # int path scales AFTER the sum: sum(±J)*dd vs sum(±J*dd) — equal
        # only under an exact exponent shift).
        if unit_scales(self.device, self.perturbation) and \
                _integer_levels(J) and \
                _is_pow2(self.device.drive_eff * self.device.dt):
            return "int8"
        dt = str(self.device.compute_dtype)
        return dt if dt in ("float32", "bfloat16") else "float32"

    def plan(self, P: int, R: int, N: int, J=None,
             needs_scan: bool = False) -> EnginePlan:
        """Resolve the dispatch for a (P problems, R runs, N spins) solve.

        ``needs_scan``: noise / trajectory recording — features only the
        scan path implements.
        """
        on_tpu = _on_tpu()
        j_dtype = self._auto_j_dtype(J)
        block_r = min(_next_pow2(R), 256)
        if needs_scan:
            return EnginePlan("scan", block_r, j_dtype, not on_tpu,
                              reason="feature:noise/record")
        path = self.path
        reason = "explicit"
        if path == "auto":
            cached = self._cache.get(self._key(P, R, N, j_dtype))
            if cached:
                return EnginePlan(cached["path"], int(cached["block_r"]),
                                  j_dtype, not on_tpu,
                                  reason=_tuned_reason("cache", cached))
            path = "fused" if on_tpu else "scan"
            reason = "auto"
        elif path == "fused":
            cached = self._cache.get(self._key(P, R, N, j_dtype))
            if cached and cached["path"] == "fused":
                block_r = int(cached["block_r"])
                reason = _tuned_reason("cache", cached)
        return EnginePlan(path, block_r, j_dtype, not on_tpu, reason=reason)

    # -- autotuner ---------------------------------------------------------
    def autotune(self, P: int, R: int, N: int, seed: int = 0,
                 candidates=_BLOCK_R_CANDIDATES, probe_sweeps: float = 0.25,
                 include_scan: bool = True,
                 j_dtype: Optional[str] = None) -> EnginePlan:
        """Time shortened anneals for each (path, block_r) candidate; persist
        the winner under the workload key. Returns the winning plan.

        The probe uses a truncated schedule (``probe_sweeps``) — per-step
        cost is schedule-independent, so the ranking transfers to the full
        anneal while the search stays cheap. ``j_dtype``: tune (and key the
        cache) for this dtype; pass the real workload's dtype so the cache
        entry matches ``run()``'s lookup — default derives it from the
        synthetic integer-level probe J.
        """
        from ..kernels import ops as kops
        from .lfsr import lfsr_voltage_inits
        rng = np.random.default_rng(seed)
        J = self.device.quantize(
            _random_symmetric(rng, P, N).astype(np.float32))
        v0 = np.stack([lfsr_voltage_inits(N, R, seed=seed + i)
                       for i in range(P)])
        probe_dev = dataclasses.replace(self.device, n_spins=N,
                                        anneal_sweeps=probe_sweeps)
        if j_dtype is None:
            j_dtype = self._auto_j_dtype(np.asarray(J))
        on_tpu = _on_tpu()

        results: list[tuple[float, str, int]] = []
        if include_scan:
            t = time_call(lambda: anneal(jnp.asarray(J), jnp.asarray(v0),
                                          probe_dev, self.perturbation))
            results.append((t, "scan", min(_next_pow2(R), 256)))
        # Fused candidates only where the kernel actually compiles (TPU):
        # off-TPU it runs in interpret mode — a Python-speed correctness
        # harness whose timings must never be persisted as a winner (a tiny
        # workload could pin 'auto' dispatch to interpret mode via cache).
        # A candidate that overflows the kernel's fast memory is skipped
        # and recorded; any other failure is a fault and propagates — a
        # fused kernel that never compiles must not quietly tune to scan.
        skipped: list[int] = []
        if on_tpu:
            # Clamp oversized candidates to the padded run count instead of
            # skipping them, so small workloads still get >= 1 fused probe.
            for br in sorted({min(br, _next_pow2(R)) for br in candidates}):
                try:
                    t = time_call(lambda br=br: kops.fused_anneal(
                        J, v0, probe_dev, self.perturbation, block_r=br,
                        j_dtype=j_dtype, interpret=False))
                except Exception as e:
                    if not _is_fast_memory_overflow(e):
                        raise
                    skipped.append(br)
                    continue
                results.append((t, "fused", br))
        if not results:
            raise ValueError(
                "autotune found no viable candidate (scan excluded and "
                "no compilable fused candidate on this backend) — "
                f"backend={jax.default_backend()}, N={N}, block_r "
                f"candidates {tuple(candidates)}")
        results.sort()
        best_t, best_path, best_br = results[0]
        key = self._key(P, R, N, j_dtype)
        entry = {"path": best_path, "block_r": best_br, "probe_s": best_t,
                 "tuned_at": time.strftime("%Y-%m-%d %H:%M:%S")}
        if skipped:
            entry["skipped_block_r"] = skipped
        self._cache[key] = entry
        _store_cache(self.cache_path, self._cache)
        return EnginePlan(best_path, best_br, j_dtype, not on_tpu,
                          reason=_tuned_reason("autotuned", entry))

    # -- execution ---------------------------------------------------------
    def run(self, J, v0, key: Optional[jax.Array] = None,
            record_every: int = 0) -> AnnealResult:
        """Anneal quantized couplings J (P,N,N) from voltages v0 (P,R,N)."""
        with span("engine.run") as sp:
            J = jnp.asarray(J, jnp.float32)
            v0 = jnp.asarray(v0, jnp.float32)
            P, N, _ = J.shape
            R = v0.shape[1]
            dev = self.device
            if N != dev.n_spins:
                dev = dataclasses.replace(dev, n_spins=N)
            needs_scan = bool(record_every) or (
                key is not None and dev.noise_sigma > 0)
            run_j_dtype = self._auto_j_dtype(J)
            # No point tuning when the path is pinned to 'scan': plan() never
            # consults the cache on that branch, so the search would be wasted.
            if self.autotune_enabled and not needs_scan and \
                    self.path != "scan" and \
                    self._key(P, R, N, run_j_dtype) not in self._cache:
                # Tune under the REAL workload's j_dtype so the cache entry
                # matches this lookup (the probe J is always integer levels).
                self.autotune(P, R, N, j_dtype=run_j_dtype)
            plan = self.plan(P, R, N, J=J, needs_scan=needs_scan)
            from ..kernels import ops as kops
            sp.set_metadata(path=plan.path, block_r=plan.block_r,
                            j_dtype=plan.j_dtype,
                            dies_per_tile=(kops.tile_dies(J)
                                           if plan.path == "fused" else 1))

            if plan.path == "scan":
                return anneal(J, v0, dev, self.perturbation, key=key,
                              record_every=record_every)

            v, sigma, energy = kops.fused_anneal(
                J, v0, dev, self.perturbation, interpret=plan.interpret,
                block_r=plan.block_r, j_dtype=plan.j_dtype)
            return AnnealResult(v_final=v, sigma=sigma, energy=energy,
                                energy_traj=None)


def _on_tpu() -> bool:
    """Fused kernels compile here (the Pallas interpret rule, inverted)."""
    from ..kernels.ising_anneal import default_interpret
    return not default_interpret()


def _is_fast_memory_overflow(e: Exception) -> bool:
    """True for the compiler's VMEM-exhaustion refusal of a kernel — the
    one failure that rules a block_r candidate out rather than the run."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg and "vmem" in msg.lower()


def _tuned_reason(base: str, entry: dict) -> str:
    """Plan provenance, naming the fused candidates the tuner skipped."""
    skipped = entry.get("skipped_block_r")
    if not skipped:
        return base
    return (f"{base}; skipped fused block_r="
            f"{','.join(str(b) for b in skipped)} (VMEM overflow)")


def _is_pow2(x: float) -> bool:
    """True when x is an exact power of two (mantissa 0.5 after frexp)."""
    import math
    if not (x > 0 and math.isfinite(x)):
        return False
    return math.frexp(x)[0] == 0.5


def _integer_levels(J) -> bool:
    """True when J is concrete and already integer DAC levels in [-127, 127]
    (the int8 fast path's validity domain). Traced/unknown J -> False."""
    if J is None:
        return False
    try:
        Jn = np.asarray(J)
    except Exception:
        return False
    if not np.issubdtype(Jn.dtype, np.floating) and \
            not np.issubdtype(Jn.dtype, np.integer):
        return False
    return bool(np.all(Jn == np.round(Jn)) and np.all(np.abs(Jn) <= 127))


def _random_symmetric(rng, P, N):
    A = rng.standard_normal((P, N, N))
    A = 0.5 * (A + A.transpose(0, 2, 1))
    for p in range(P):
        np.fill_diagonal(A[p], 0.0)
    return A


def time_call(fn, iters: int = 2) -> float:
    """Warmup once (compile), then average ``iters`` timed calls. Shared by
    the autotuner and benchmarks/kernel_throughput.py."""
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters
