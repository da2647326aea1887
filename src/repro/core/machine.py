"""IsingMachine — the public solve() API of the digital twin.

Usage:
    m = IsingMachine()                          # paper chip: 64 spins
    out = m.solve(J, num_runs=1000, seed=7)     # J: (N,N) or (P,N,N)
    out.best_energy, out.success_rate(best_known)

Backends (legacy spelling of AnnealEngine paths — solve() dispatches through
``core.engine.AnnealEngine``; ``backend="auto"`` + ``autotune=True`` are the
new knobs):
    'jnp'    — scan path (lax.scan reference; runs anywhere; the dry-run path)
    'pallas' — fused VMEM anneal kernel (TPU target; interpret=True on CPU)
    'auto'   — let the engine pick (fused on TPU, scan elsewhere, cache-aware)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..tracing import span
from .device_model import DeviceModel
from .engine import AnnealEngine
from .lfsr import expand_voltage_inits, lfsr_state_words, voltage_levels
from .perturbation import PerturbationConfig, DEFAULT_PERTURBATION, NOMINAL

_BACKEND_TO_PATH = {"jnp": "scan", "pallas": "fused", "auto": "auto"}


@dataclasses.dataclass
class SolveOutput:
    sigma: np.ndarray           # (P, R, N)
    energy: np.ndarray          # (P, R)
    v_final: np.ndarray         # (P, R, N)
    energy_traj: Optional[np.ndarray] = None

    @property
    def best_energy(self) -> np.ndarray:          # (P,)
        return self.energy.min(axis=-1)

    @property
    def best_sigma(self) -> np.ndarray:           # (P, N)
        idx = self.energy.argmin(axis=-1)
        return np.take_along_axis(self.sigma, idx[:, None, None], axis=1)[:, 0]

    def success_rate(self, best_known, frac: float = 0.99) -> np.ndarray:
        """Fraction of runs reaching >= frac of best-known energy (paper's
        99%-of-best rule; energies are negative, so success is
        E <= best + (1-frac)*|best|)."""
        best_known = np.asarray(best_known, dtype=np.float64).reshape(-1, 1)
        thresh = best_known + (1.0 - frac) * np.abs(best_known)
        return (self.energy <= thresh + 1e-9).mean(axis=-1)


class IsingMachine:
    def __init__(self,
                 device: DeviceModel | None = None,
                 perturbation: PerturbationConfig | None = None,
                 backend: str = "jnp",
                 autotune: bool = False):
        self.device = device or DeviceModel()
        self.perturbation = perturbation if perturbation is not None else DEFAULT_PERTURBATION
        if backend not in _BACKEND_TO_PATH:
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.engine = AnnealEngine(device=self.device,
                                   perturbation=self.perturbation,
                                   path=_BACKEND_TO_PATH[backend],
                                   autotune=autotune)

    # ------------------------------------------------------------------
    def solve(self, J, num_runs: int = 100, seed: int = 0,
              record_every: int = 0, key: Optional[jax.Array] = None,
              quantize: bool = True) -> SolveOutput:
        """Anneal ``num_runs`` LFSR-seeded runs per problem.

        J: (N, N) or (P, N, N) float couplings (symmetric, zero diag).
        quantize: apply the 31-level DAC model (identity for integer J in
            [-15, 15], which is the paper's problem distribution).
        """
        J = np.asarray(J, dtype=np.float32)
        single = J.ndim == 2
        if single:
            J = J[None]
        P, N, _ = J.shape
        dev = self.device
        if N != dev.n_spins:
            dev = dataclasses.replace(dev, n_spins=N)

        Jq = dev.quantize(J) if quantize else jnp.asarray(J)
        # problem p's runs are lfsr_voltage_inits(N, R, seed + 7919 p), bit
        # for bit: the states go to the device, and it expands the voltages
        with span("machine.lfsr_init", problems=P, runs=num_runs) as sp:
            words = lfsr_state_words([seed + 7919 * p for p in range(P)],
                                     N, num_runs)
            levels = voltage_levels(dev.vdd, dev.init_swing)
            v0 = expand_voltage_inits(words, levels.astype(np.float32), N)
            sp.set_metadata(bytes=words.nbytes)

        # All paths dispatch through the AnnealEngine; it falls back to the
        # scan path automatically when noise/trajectory recording is asked
        # for (features the fused kernel doesn't materialize).
        res = self.engine.run(Jq, v0, key=key, record_every=record_every)

        # the copies below would wait anyway; waiting first keeps the
        # device's time out of the readback span
        with span("machine.wait"):
            jax.block_until_ready((res.v_final, res.sigma, res.energy))
        with span("machine.readback") as sp:
            out = SolveOutput(
                sigma=np.asarray(res.sigma), energy=np.asarray(res.energy),
                v_final=np.asarray(res.v_final),
                energy_traj=(None if res.energy_traj is None
                             else np.asarray(res.energy_traj)))
            sp.set_metadata(bytes=sum(
                a.nbytes for a in (out.sigma, out.energy, out.v_final,
                                   out.energy_traj) if a is not None))
        return out

    # ------------------------------------------------------------------
    def gradient_descent_baseline(self) -> "IsingMachine":
        """The paper's no-perturbation baseline: same chip, rails always on,
        leakage disabled (ideal refresh), no noise."""
        dev = dataclasses.replace(self.device, tau_leak_sweeps=float("inf"),
                                  noise_sigma=0.0)
        return IsingMachine(device=dev, perturbation=NOMINAL,
                            backend=self.backend,
                            autotune=self.engine.autotune_enabled)

    def inherent_noise_baseline(self, sigma: float = 2.0) -> "IsingMachine":
        """Measured-chip baseline of Fig. 4: no deterministic perturbation,
        only circuit noise."""
        dev = dataclasses.replace(self.device, noise_sigma=sigma)
        return IsingMachine(device=dev, perturbation=NOMINAL,
                            backend=self.backend,
                            autotune=self.engine.autotune_enabled)
