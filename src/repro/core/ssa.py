"""SSA and HA-SSA annealers (paper Sec. II-B and Sec. III) in JAX.

Every spin is a p-bit updated *simultaneously* each cycle (the FPGA spin-gate
array) by integral stochastic computing:

    I_i(t+1)     = h_i + Σ_j J_ij m_j(t) + n_rnd · r_i(t) + Itanh_i(t)   (2a)
    Itanh_i(t+1) = clamp(I_i(t+1), -I0(t), I0(t)-1)                       (2b)
    m_i(t+1)     = +1 if Itanh_i(t+1) >= 0 else -1                        (2c)

The *only* difference between SSA and HA-SSA is outside this update path:

* temperature control — Eq. (3) float-β division (SSA) vs Eq. (4) integer
  barrel shift (HA-SSA); identical sequences when β_ssa = 2^{-β_hassa};
* storage policy — SSA stores the spin bitplane every cycle; HA-SSA stores
  only while I0 == I0max (the FPGA's BRAM write-enable), shrinking trajectory
  memory by (steps = log2(I0max/I0min)+1)× — Eq. (5) vs Eq. (6);
* duration control — HA-SSA counts iterations (complete I0min→I0max sweeps),
  never truncating the final sweep.

TPU adaptation (see DESIGN.md §2): :func:`anneal` is a thin driver over the
plateau-structured engine in :mod:`repro.core.engine`.  The schedule is
grouped into constant-I0 plateaus — HA-SSA's unit of execution and storage —
and the problem runs as a one-lane batch on the engine's batched backends
(:func:`~repro.core.engine.single_problem_backend`), the same programs the
serving layer runs:

* ``backend='sparse'`` — padded-adjacency gather field, `lax.scan` per plateau;
* ``backend='dense'``  — (T,N)·(N,N) MXU matmul field, `lax.scan` per plateau
  (``j_mode='tiled'`` streams (tile_n, N) J slabs for G77/G81-class N;
  ``field_mode='popcount'`` contracts XNOR-popcount bitplanes);
* ``backend='pallas'`` — the resident plateau kernels with J pinned in VMEM
  (DESIGN.md §2.3).  With ``xorshift`` noise per-cycle noise is generated
  inside the kernel from the carried xorshift lanes and the HBM-facing spin
  refs are uint32 bitplanes — no (C, R, N) noise buffer is ever allocated.
  (``threefry`` keeps the per-plateau pregen reference path; it cannot be
  reproduced in-kernel.)

``storage_layout='packed'`` additionally keeps the engine state *between*
launches as uint32 bitplanes (DESIGN.md §4) — bit-identical results, 8–32×
smaller resident spin storage.

All three advance the field contraction **once per cycle** (the field used
for the Eq. 2a update of m(t) is reused for H(m(t))) and produce bit-identical
spin trajectories from the same noise stream — property-tested.

The HA-SSA storage policy is *structural*: it is per-plateau eligibility (the
FPGA's I0 == I0max write-enable), so in ``record='traj'`` mode the XLA output
buffer itself is `steps×` smaller — the BRAM-depth saving, as HBM-buffer
shape (DESIGN.md §4).

Two recording modes:

* ``record='traj'`` — materialize the stored bitplanes (tests, small runs;
  this is what the FPGA ships over UART).
* ``record='best'`` — running arg-best *restricted to storage-eligible
  plateaus*, so HA-SSA's reported solution is computed only from states it
  would have stored.  On TPU, evaluating the cut on the fly is nearly free
  next to the field matmul (compute >> memory), which is exactly the
  opposite trade the FPGA makes — noted in DESIGN.md §8.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .engine import (
    BaseResult,
    finalize_cut,
    normalize_problem,
    pack_spins,
    packed_words,
    schedule_plateaus,
    single_problem_backend,
    ssa_cycle_update,
    tile_plateaus,
    unpack_spins,
)
from .ising import IsingModel, MaxCutProblem, ising_energy
from .schedule import Schedule, hassa_schedule, n_temp_steps, ssa_schedule

__all__ = [
    "SSAHyperParams",
    "AnnealResult",
    "ssa_cycle_update",
    "anneal",
    "solve_maxcut",
    "pack_spins",
    "unpack_spins",
]


@dataclasses.dataclass(frozen=True)
class SSAHyperParams:
    """Table II defaults: trial=100, m_shot=150, n_rnd=2, I0: 1→32, τ=100, β=1."""

    n_trials: int = 100
    m_shot: int = 150
    n_rnd: int = 2
    i0_min: int = 1
    i0_max: int = 32
    tau: int = 100
    beta_shift: int = 1  # HA-SSA Eq.(4) β; equivalent SSA Eq.(3) β = 2^-beta_shift

    @property
    def steps(self) -> int:
        return n_temp_steps(self.i0_min, self.i0_max, self.beta_shift)

    @property
    def cycles_per_iter(self) -> int:
        return self.steps * self.tau

    @property
    def total_cycles(self) -> int:
        return self.m_shot * self.cycles_per_iter

    def schedule(self, kind: str = "hassa") -> Schedule:
        if kind == "hassa":
            return hassa_schedule(self.i0_min, self.i0_max, self.tau, self.beta_shift)
        if kind == "ssa":
            return ssa_schedule(self.i0_min, self.i0_max, self.tau, 2.0 ** (-self.beta_shift))
        raise ValueError(kind)


@dataclasses.dataclass
class AnnealResult(BaseResult):
    """Outcome of one annealing run over a batch of trials.

    Field conventions are shared with SAResult/PTResult via
    :class:`repro.core.engine.BaseResult`.
    """

    traj: Optional[np.ndarray]    # (m_shot, stored_cycles, T, Nw) uint32 bitplanes
    stored_bits_per_iter: int     # N × stored_cycles — the Eq.(5)/(6) witness
    hp: SSAHyperParams


# ---------------------------------------------------------------------------
# Main annealer: a thin driver over the plateau engine at B=1
# ---------------------------------------------------------------------------
def _traced_chain(bk, problem, plateaus, state, *, record, track_energy):
    """One pass of a plateau chain with per-cycle outputs, lane 0's planes.

    record='best': eligible plateaus fold their states into the running
    arg-best; ``track_energy`` adds the (mean_H, min_H) trace of every cycle.
    record='traj': eligible plateaus emit bit-packed spin planes instead
    (the FPGA's UART-shipped trajectory); best-tracking is left to the
    caller's post-scan over the planes.  Plateaus that want no output run
    the backend's own plateau program (the resident kernel on pallas).
    """
    tr_mean, tr_min, planes = [], [], []
    for p in plateaus:
        if record == "traj":
            state, _, pl = bk.run_plateau_traced(
                problem, state, dataclasses.replace(p, eligible=False),
                emit=p.eligible,
            )
            if pl is not None:
                planes.append(pl[:, 0])
        else:
            state, tr, _ = bk.run_plateau_traced(
                problem, state, p, track_energy=track_energy
            )
            if tr is not None:
                tr_mean.append(tr[0])
                tr_min.append(tr[1])
    trace = (
        (jnp.concatenate(tr_mean), jnp.concatenate(tr_min)) if tr_mean else None
    )
    return state, trace, jnp.concatenate(planes) if planes else None


def anneal(
    problem: Union[MaxCutProblem, IsingModel],
    hp: Union[SSAHyperParams, str] = SSAHyperParams(),
    seed: int = 0,
    *,
    storage: str = "i0max",        # 'i0max' (HA-SSA) | 'all' (conventional SSA)
    record: str = "best",          # 'best' | 'traj'
    backend: Optional[str] = None,  # legacy: 'sparse'|'dense'|'pallas'|'auto'
    noise: Optional[str] = None,   # legacy: 'threefry' | 'xorshift'
    track_energy: bool = True,
    schedule_kind: str = "hassa",  # 'hassa' Eq.(4) | 'ssa' Eq.(3)
    total_cycles: Optional[int] = None,  # cycle-count duration (Fig. 12 mode)
    storage_layout: Optional[str] = None,  # legacy: 'dense' | 'packed'
    backend_opts: Optional[dict] = None,   # legacy extra backend kwargs
    auto_base: Optional[SSAHyperParams] = None,  # budget knobs for hp='auto'
    config=None,                   # SolverConfig — the typed option surface
) -> AnnealResult:
    """Run SSA/HA-SSA on a MAX-CUT, raw Ising, or encoded problem instance.

    ``storage='i0max'`` + ``schedule_kind='hassa'`` is the paper's HA-SSA;
    ``storage='all'`` + ``schedule_kind='ssa'`` is conventional SSA.  The
    update path is shared, so with equal hyperparameters and the same noise
    stream the two produce bit-identical spin sequences (Sec. III-A, V-A) —
    property-tested.

    ``hp='auto'`` derives the energy-scale hyperparameters (n_rnd, I0
    clamp, per-plateau τ) from the instance's local-field distribution
    (:mod:`repro.core.autotune`), taking the budget knobs from
    ``auto_base`` (default: Table II).

    Execution-surface options come in one typed object:
    ``config=SolverConfig(backend=..., storage_layout=..., ...)``
    (DESIGN.md §13).  The loose ``backend``/``noise``/``storage_layout``/
    ``backend_opts`` kwargs keep working as a deprecated shim (one
    ``DeprecationWarning`` per process) with their historical defaults
    (sparse backend, threefry noise, dense layout).  ``backend`` is a
    name — 'sparse', 'dense', 'pallas' or 'auto' — never an instance.

    An :class:`~repro.core.ssqa.SSQAHyperParams` ``hp`` switches the run to
    SSQA (DESIGN.md §13): the schedule carries the J⊥ ramp and the backend
    is built with the hp's Trotter-replica count.

    The problem runs as lane 0 of a one-problem batch on the engine's
    batched backend.  A ``record='best'`` run without ``track_energy`` is
    the service's own chunk program (``run_shots``): on ``backend='pallas'``
    each iteration is resident kernel launches.  Per-cycle energy traces
    (``track_energy``) and trajectory planes (``record='traj'``) need
    per-cycle outputs, which the resident kernel does not produce — those
    plateaus run the bit-identical scan path instead.
    """
    from .config import legacy_kwargs_to_config

    maxcut, model = normalize_problem(problem)
    if isinstance(hp, str):
        # Lazy import: autotune imports SSAHyperParams from this module.
        from .autotune import resolve_hyperparams

        hp, _ = resolve_hyperparams(hp, model, base=auto_base)
    if record not in ("best", "traj"):
        raise ValueError(f"unknown record {record!r}")
    cfg = legacy_kwargs_to_config(
        "repro.core.ssa.anneal", config,
        backend=backend, noise=noise, storage_layout=storage_layout,
        backend_opts=dict(backend_opts) if backend_opts else None,
    )
    if config is None and noise is None:
        # anneal()'s historical noise default is threefry, not the
        # SolverConfig default (xorshift) — preserved for the legacy path.
        cfg = cfg.replace(noise="threefry")
    sched = hp.schedule(schedule_kind)
    opts = cfg.engine_opts()
    # SSQA hyper-params carry the Trotter-replica count; duck-typed so this
    # module needs no import of core.ssqa (which imports us).
    nr = int(getattr(hp, "n_replicas", 0) or 0)
    if nr:
        opts.setdefault("n_replicas", nr)
    bk, stacked = single_problem_backend(
        model, cfg.backend, n_trials=hp.n_trials, n_rnd=hp.n_rnd,
        noise=cfg.noise, partition=cfg.partition, mesh=cfg.mesh, **opts,
    )
    noise0 = bk.init_noise([seed], [model.n])
    plateaus = schedule_plateaus(sched, storage)
    stored_per_iter = sum(p.length for p in plateaus if p.eligible)
    n = model.n

    if record == "traj":
        # Iteration-structured: heat plateaus emit nothing; eligible plateaus
        # emit bit-packed planes → the output buffer is structurally
        # (stored/cpi)× smaller, mirroring the BRAM depth saving.
        hh, nbr_idx, nbr_w = model.device_arrays()

        def run(prob, ns0):
            state = bk.init_state(prob, ns0)

            def iteration(st, _):
                st, _, planes = _traced_chain(
                    bk, prob, plateaus, st, record="traj", track_energy=False
                )
                return st, planes

            state, traj = jax.lax.scan(iteration, state, None, length=hp.m_shot)
            # Solution = best stored state, scanned outside the hot loop.
            flat = traj.reshape(-1, hp.n_trials, packed_words(n))
            spins = unpack_spins(flat, n)  # (S, T, N)
            H = ising_energy(spins.astype(jnp.int32), hh, nbr_idx, nbr_w)  # (S, T)
            if maxcut is not None:
                idx = jnp.argmax((maxcut.w_total - H) // 2, axis=0)
            else:
                idx = jnp.argmin(H, axis=0)
            tt = jnp.arange(hp.n_trials)
            best_m = spins[idx, tt]
            best_H = H[idx, tt]
            return best_H, best_m, traj

        best_H, best_m, traj = jax.jit(run)(stacked, noise0)
        e_mean = e_min = None
    else:
        if total_cycles is None:
            # Iteration-aligned: the per-iteration plateau chain m_shot×.
            full_iters, tail = hp.m_shot, ()
        else:
            # Cycle-count duration control: the full iterations, then the
            # truncated tail's plateaus (keeps the compiled program one
            # iteration body + tail, not total_cycles/τ unrolled scans).
            full_iters, rem = divmod(int(total_cycles), sched.cycles_per_iter)
            tail = tile_plateaus(plateaus, rem) if rem else ()

        def advance(prob, st, chain, n_iters):
            """``n_iters`` passes of ``chain``: (state, flat trace or None)."""
            if not track_energy:
                return bk.run_shots(prob, st, chain, n_iters), None

            def iteration(s, _):
                s, trace, _ = _traced_chain(
                    bk, prob, chain, s, record="best", track_energy=True
                )
                return s, trace

            st, tr = jax.lax.scan(iteration, st, None, length=n_iters)
            return st, (tr[0].reshape(-1), tr[1].reshape(-1))

        def run(prob, ns0):
            state = bk.init_state(prob, ns0)
            traces = []
            for chain, n_iters in ((plateaus, full_iters), (tail, 1)):
                if chain and n_iters:
                    state, tr = advance(prob, state, chain, n_iters)
                    traces.append(tr)
            best_H, best_m = bk.finalize(state)
            trace = (
                tuple(jnp.concatenate([t[i] for t in traces]) for i in (0, 1))
                if track_energy
                else None
            )
            return best_H[0], best_m[0, :, :n], trace

        best_H, best_m, trace = jax.jit(run)(stacked, noise0)
        traj = None
        if track_energy:
            e_mean = np.asarray(trace[0]).reshape(-1)
            e_min = np.asarray(trace[1]).reshape(-1)
        else:
            e_mean = e_min = None

    best_H = np.asarray(best_H)
    best_cut = np.asarray(finalize_cut(best_H, maxcut))
    return AnnealResult(
        best_cut=best_cut,
        best_energy=best_H,
        best_m=np.asarray(best_m),
        energy_mean=e_mean,
        energy_min=e_min,
        traj=None if traj is None else np.asarray(traj),
        stored_bits_per_iter=model.n * stored_per_iter,
        hp=hp,
    )


def solve_maxcut(problem: MaxCutProblem, hp: SSAHyperParams = SSAHyperParams(), **kw) -> AnnealResult:
    """Convenience wrapper with HA-SSA defaults (the paper's configuration)."""
    return anneal(problem, hp, **kw)
