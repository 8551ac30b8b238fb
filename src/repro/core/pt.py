"""Parallel-tempering baseline (paper Sec. V-C, Table VII; Gyoten et al. [11])
and PT-SSA — parallel tempering expressed on the plateau engine.

R replicas run Metropolis sweeps at a fixed ladder of temperatures; every
``swap_interval`` cycles adjacent replicas attempt a configuration exchange
with probability min(1, exp((1/T_a - 1/T_b)(H_a - H_b))).  This is standard
PT [27]; IPAPT [11] is a hardware approximation of it — the algorithmic
baseline is what the paper compares solution-quality/time against.

**PT-SSA** (:func:`anneal_pt_ssa`) maps the replica ladder onto the plateau
engine's *trial axis*: R replicas run the Eq. (2a–2c) p-bit update
simultaneously at a fixed per-replica pseudo-inverse temperature I0 (the
ladder replaces the annealing schedule), and a swap phase between plateaus
exchanges configurations between adjacent rungs using an effective inverse
temperature β_k = beta_scale · I0_k.  Because it runs on
:func:`repro.core.engine.run_plateau_scan`, PT-SSA shares the batched
serving path: the service vmaps :func:`pt_ssa_rounds` over a stacked
problem axis exactly as it does the SSA plateau program.

The drivers share the engine's problem/result plumbing
(:func:`repro.core.engine.normalize_problem`,
:class:`repro.core.engine.BaseResult`) so PT results are interchangeable
with HA-SSA's and SA's in the benchmarks and the serving layer.
"""
from __future__ import annotations

import dataclasses
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from .engine import (
    BaseResult,
    EngineState,
    energy_from_field,
    finalize_cut,
    normalize_problem,
    run_plateau_scan,
    single_problem_backend,
)
from .ising import IsingModel, MaxCutProblem

__all__ = [
    "PTHyperParams",
    "PTResult",
    "anneal_pt",
    "PTSSAHyperParams",
    "PTSSAResult",
    "anneal_pt_ssa",
    "pt_ssa_rounds",
]


def _swap_perm(do_swap: jnp.ndarray, R: int) -> jnp.ndarray:
    """Permutation exchanging rungs (k, k+1) where do_swap[k] (k = 0..R-2).

    Accepted pairs all share one parity, so an index belongs to at most one
    accepted swap — as the lower member (takes from above) or the upper
    member (takes from below); the nested where resolves exactly one.
    """
    idx = jnp.arange(R)
    take_above = jnp.zeros(R, bool).at[:-1].set(do_swap)   # idx k   ← k+1
    take_below = jnp.zeros(R, bool).at[1:].set(do_swap)    # idx k+1 ← k
    return jnp.where(take_above, idx + 1, jnp.where(take_below, idx - 1, idx))


@dataclasses.dataclass(frozen=True)
class PTHyperParams:
    n_replicas: int = 8
    n_cycles: int = 90_000
    swap_interval: int = 100
    t_min: float = 0.2
    t_max: float = 10.0


@dataclasses.dataclass
class PTResult(BaseResult):
    """PT reports one chain-best; scalars, but the BaseResult contract holds."""

    hp: PTHyperParams


def anneal_pt(
    problem: Union[MaxCutProblem, IsingModel],
    hp: PTHyperParams = PTHyperParams(),
    seed: int = 0,
    *,
    track_energy: bool = True,
) -> PTResult:
    maxcut, model = normalize_problem(problem)

    h, nbr_idx, nbr_w = model.device_arrays()
    n, R = model.n, hp.n_replicas
    # Geometric temperature ladder (hot→cold across replicas).
    temps = jnp.asarray(
        hp.t_max * (hp.t_min / hp.t_max) ** (np.arange(R) / max(R - 1, 1)),
        jnp.float32,
    )
    inv_t = 1.0 / temps

    def energy(m):
        neigh = jnp.take(m, nbr_idx, axis=-1)
        fields = jnp.sum(nbr_w * neigh, axis=-1)
        return -(jnp.sum(h * m, axis=-1) + jnp.sum(m * fields, axis=-1) // 2)

    def metro_cycle(carry, key):
        m, H = carry
        k_site, k_acc = jax.random.split(key)
        i = jax.random.randint(k_site, (R,), 0, n)
        mi = jnp.take_along_axis(m, i[:, None], axis=1)[:, 0]
        neigh = jnp.take_along_axis(jnp.broadcast_to(m, (R, n)), nbr_idx[i], axis=1)
        local = h[i] + jnp.sum(nbr_w[i] * neigh, axis=-1)
        dH = 2 * mi * local
        u = jax.random.uniform(k_acc, (R,), minval=1e-12)
        accept = (dH <= 0) | (jnp.log(u) < -dH.astype(jnp.float32) * inv_t)
        m = m.at[jnp.arange(R), i].set(jnp.where(accept, -mi, mi))
        H = H + jnp.where(accept, dH, 0)
        return (m, H), None

    def swap_phase(m, H, key, parity):
        # attempt swaps between (k, k+1) pairs of one parity
        a = jnp.arange(0, R - 1)
        pair_mask = (a % 2) == parity
        dB = inv_t[a] - inv_t[a + 1]
        dE = (H[a] - H[a + 1]).astype(jnp.float32)
        u = jax.random.uniform(key, (R - 1,), minval=1e-12)
        do_swap = pair_mask & (jnp.log(u) < dB * dE)
        perm = _swap_perm(do_swap, R)
        return m[perm], H[perm]

    rounds = hp.n_cycles // hp.swap_interval

    def one_round(carry, xs):
        m, H, best_H, best_m = carry
        key, parity = xs
        keys = jax.random.split(key, hp.swap_interval + 1)
        (m, H), _ = jax.lax.scan(metro_cycle, (m, H), keys[:-1])
        m, H = swap_phase(m, H, keys[-1], parity)
        rb = jnp.argmin(H)
        better = H[rb] < best_H
        best_H = jnp.where(better, H[rb], best_H)
        best_m = jnp.where(better, m[rb], best_m)
        trace = best_H if track_energy else 0
        return (m, H, best_H, best_m), trace

    @jax.jit
    def run():
        key = jax.random.PRNGKey(seed)
        key, k0 = jax.random.split(key)
        m0 = jnp.where(jax.random.bernoulli(k0, 0.5, (R, n)), 1, -1).astype(jnp.int32)
        H0 = energy(m0)
        keys = jax.random.split(key, rounds)
        parities = jnp.arange(rounds, dtype=jnp.int32) % 2
        b0 = jnp.argmin(H0)
        carry0 = (m0, H0, H0[b0], m0[b0])
        (_, _, best_H, best_m), mins = jax.lax.scan(one_round, carry0, (keys, parities))
        return best_m, best_H, mins

    best_m, best_H, mins = run()
    best_H = int(best_H)
    return PTResult(
        best_cut=int(finalize_cut(best_H, maxcut)),
        best_energy=best_H,
        best_m=np.asarray(best_m),
        energy_mean=None,
        energy_min=None if not track_energy else np.asarray(mins),
        hp=hp,
    )


# ---------------------------------------------------------------------------
# PT-SSA: the replica ladder on the plateau engine's trial axis
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PTSSAHyperParams:
    """PT expressed in the engine's terms: replicas = trials, rungs = I0.

    ``n_rounds`` plateau+swap rounds of ``tau`` cycles each; the I0 ladder is
    geometric from i0_min (hot) to i0_max (cold) across ``n_replicas``.
    ``beta_scale`` maps a rung's I0 to the effective inverse temperature used
    in the swap acceptance test (the p-bit dynamics' sharpness is monotone in
    I0, so any positive scale gives a valid PT exchange rule).
    """

    n_replicas: int = 8
    n_rounds: int = 60
    tau: int = 100
    i0_min: int = 1
    i0_max: int = 32
    n_rnd: int = 2
    beta_scale: float = 0.25

    def ladder(self) -> np.ndarray:
        """(R,) int32 I0 per replica, geometric hot→cold."""
        R = self.n_replicas
        ratio = (self.i0_max / self.i0_min) ** (1.0 / max(R - 1, 1))
        lad = np.round(self.i0_min * ratio ** np.arange(R))
        return np.clip(lad, self.i0_min, self.i0_max).astype(np.int32)

    @property
    def total_cycles(self) -> int:
        return self.n_rounds * self.tau


@dataclasses.dataclass
class PTSSAResult(BaseResult):
    """Per-replica best (arrays over the replica axis), BaseResult contract."""

    hp: PTSSAHyperParams


def pt_ssa_rounds(
    field_fn,
    noise_step,
    h: jnp.ndarray,
    hp: PTSSAHyperParams,
    state: EngineState,
    keys: jnp.ndarray,      # (k, 2) swap keys — one round per key
    parities: jnp.ndarray,  # (k,) int32 alternating swap parity
) -> EngineState:
    """Advance k plateau+swap rounds (traceable, single problem).

    Each round: one constant-ladder plateau of ``tau`` cycles via
    :func:`run_plateau_scan` with a **per-replica I0 column** (the engine's
    Eq. 2b clamp broadcasts over the trial axis), always storage-eligible
    (PT tracks its best continuously); then one adjacent-pair configuration
    swap at alternating parity.  Swaps permute (m, itanh); the running best
    stays attached to the rung that observed it — the final result reduces
    over rungs anyway.
    """
    ladder = jnp.asarray(hp.ladder(), jnp.int32)
    i0_col = ladder[:, None]
    betas = hp.beta_scale * ladder.astype(jnp.float32)
    R = hp.n_replicas
    a = jnp.arange(0, R - 1)

    def one_round(st, xs):
        key, parity = xs
        st, _, _ = run_plateau_scan(
            field_fn, noise_step, h, hp.n_rnd, st, i0_col,
            length=hp.tau, eligible=True,
        )
        field = field_fn(st.m)
        H = energy_from_field(st.m, field, h)
        pair_mask = (a % 2) == parity
        dB = betas[a] - betas[a + 1]
        dE = (H[a] - H[a + 1]).astype(jnp.float32)
        u = jax.random.uniform(key, (R - 1,), minval=1e-12)
        do_swap = pair_mask & (jnp.log(u) < dB * dE)
        perm = _swap_perm(do_swap, R)
        st = EngineState(
            st.noise_state, st.m[perm], st.itanh[perm], st.best_H, st.best_m
        )
        return st, None

    st, _ = jax.lax.scan(one_round, state, (keys, parities))
    return st


def anneal_pt_ssa(
    problem: Union[MaxCutProblem, IsingModel],
    hp: PTSSAHyperParams = PTSSAHyperParams(),
    seed: int = 0,
    *,
    backend: str = "sparse",
    noise: str = "xorshift",
) -> PTSSAResult:
    """PT on the plateau engine (replicas = trials, per-replica I0 clamp).

    ``backend`` must be 'sparse' or 'dense': the resident Pallas kernel takes
    a scalar plateau I0 (per-replica I0 columns are a kernel extension left
    to a later PR), so PT-SSA runs the scan path.  The problem runs as lane
    0 of a one-problem batch, through the rounds the service vmaps.
    """
    if backend == "pallas":
        raise ValueError(
            "pt-ssa needs a per-replica I0 column; the resident pallas "
            "kernel is scalar-I0 — use backend='sparse' or 'dense'"
        )
    maxcut, model = normalize_problem(problem)
    bk, stacked = single_problem_backend(
        model, backend, n_trials=hp.n_replicas, n_rnd=hp.n_rnd, noise=noise
    )
    noise0 = bk.init_noise([seed], [model.n])

    @jax.jit
    def run(prob, ns0):
        state = bk.init_state(prob, ns0)
        keys = jax.random.split(jax.random.PRNGKey(seed ^ 0x5CA1AB1E), hp.n_rounds)
        parities = jnp.arange(hp.n_rounds, dtype=jnp.int32) % 2

        def one(pr, st):
            field_fn = lambda m: bk._field_one(pr, m)  # noqa: E731
            return pt_ssa_rounds(
                field_fn, bk._noise_step_one, pr["h"], hp, st, keys, parities
            )

        best_H, best_m = bk.finalize(jax.vmap(one)(prob, state))
        return best_H[0], best_m[0]

    best_H, best_m = run(stacked, noise0)
    best_H = np.asarray(best_H)
    return PTSSAResult(
        best_cut=np.asarray(finalize_cut(best_H, maxcut)),
        best_energy=best_H,
        best_m=np.asarray(best_m),
        energy_mean=None,
        energy_min=None,
        hp=hp,
    )
