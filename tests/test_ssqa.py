"""SSQA — stochastic simulated quantum annealing (DESIGN.md §13).

Contracts under test:

* the Trotter-replica ring coupling is backend-invariant: sparse, dense,
  pallas (streamed and pregen noise), pallas XNOR-popcount and
  packed-storage runs produce bit-identical best states, single-problem
  and batched (including spin-sharded);
* classical runs are untouched: a backend built with ``n_replicas`` set
  executes jperp-free schedules bit-identically to a classical backend;
* the J⊥ ramp rides the schedule and is visible to ``Schedule.signature()``
  (executable-cache soundness);
* the autotuner derives the Trotter dimension and J⊥ ceiling from the
  local-field distribution and rounds ``n_trials`` up to whole rings.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import SSAHyperParams, anneal, gset
from repro.core.autotune import resolve_hyperparams
from repro.core.engine import (
    bucket_n,
    make_batched_backend,
    replica_coupling,
    schedule_plateaus,
    single_problem_backend,
)
from repro.core.schedule import hassa_schedule, ssqa_schedule
from repro.core.ssqa import SSQAHyperParams, anneal_ssqa

T, R = 8, 4
TORUS = gset.toroidal_grid(50, seed=17)
MODEL = TORUS.to_ising()
SCHED = ssqa_schedule(1, 8, tau=4, jperp_max=3)
PLATEAUS = schedule_plateaus(SCHED, "i0max")

def _single(backend, **opts):
    """One problem as lane 0 of a B=1 batch (what ``anneal`` runs)."""
    return lambda: single_problem_backend(
        MODEL, backend, n_trials=T, n_rnd=2, noise="xorshift", **opts)


SINGLE_BACKENDS = {
    "sparse": _single("sparse", n_replicas=R),
    "dense": _single("dense", n_replicas=R),
    "pallas": _single("pallas", noise_mode="streamed", n_replicas=R),
    "pallas-popcount": _single("pallas", noise_mode="streamed",
                               field_mode="popcount", n_replicas=R),
    # the pregen kernel has no ring: coupled plateaus run the scan path
    "pallas-pregen": _single("pallas", noise_mode="pregen", n_replicas=R),
    "sparse-packed": _single("sparse", storage_layout="packed",
                             n_replicas=R),
}


def _run(bk, problem, plateaus, seed=7, iters=3):
    st = bk.init_state(problem, bk.init_noise([seed], [MODEL.n]))
    st = bk.run_shots(problem, st, plateaus, iters)
    bh, bm = bk.finalize(st)
    return np.asarray(bh[0]), np.asarray(bm[0])


def _run_single(mk):
    return _run(*mk(), PLATEAUS)


# ---------------------------------------------------------------------------
# Bit-identity across backends and field modes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", [k for k in SINGLE_BACKENDS if k != "sparse"])
def test_single_problem_backends_bit_identical(name):
    ref_h, ref_m = _run_single(SINGLE_BACKENDS["sparse"])
    bh, bm = _run_single(SINGLE_BACKENDS[name])
    np.testing.assert_array_equal(ref_h, bh)
    np.testing.assert_array_equal(ref_m, bm)


@pytest.mark.parametrize("name,kw", [
    ("b-dense", dict(backend="dense")),
    ("b-pallas", dict(backend="pallas", noise_mode="streamed")),
    ("b-pallas-pc", dict(backend="pallas", noise_mode="streamed",
                         field_mode="popcount", j_bits=2)),
    ("b-sparse-packed", dict(backend="sparse", storage_layout="packed")),
    ("b-spin", dict(backend="dense", partition="spin")),
])
def test_batched_backends_bit_identical(name, kw):
    """Batched SSQA (the service's execution shape): the replica axis rides
    the trial axis through stacking and padding untouched."""
    models = [MODEL, gset.king_graph(49, seed=3).to_ising()]
    nb = max(bucket_n(m.n) for m in models)

    def run(backend, **opts):
        bk = make_batched_backend(
            backend, n_bucket=nb, n_trials=T, n_rnd=2,
            noise="xorshift", n_replicas=R, **opts)
        problem = bk.stack(models)
        st = bk.init_state(problem, bk.init_noise([7, 9], [m.n for m in models]))
        st = bk.run_shots(problem, st, PLATEAUS, 3)
        bh, bm = bk.finalize(st)
        return np.asarray(bh), np.asarray(bm)

    ref_h, ref_m = run("sparse")
    kw = dict(kw)
    bh, bm = run(kw.pop("backend"), **kw)
    np.testing.assert_array_equal(ref_h, bh)
    np.testing.assert_array_equal(ref_m, bm)


def test_classical_schedule_unchanged_by_replica_backend():
    """jperp=0 disables the coupling entirely: a backend carrying
    n_replicas runs classical plateau programs bit-identically."""
    cplat = schedule_plateaus(hassa_schedule(1, 8, tau=4), "i0max")
    h0, m0 = _run(*_single("sparse")(), cplat, iters=1)
    hr, mr = _run(*_single("sparse", n_replicas=R)(), cplat, iters=1)
    np.testing.assert_array_equal(h0, hr)
    np.testing.assert_array_equal(m0, mr)


def test_coupling_changes_the_dynamics():
    """Sanity: on the coupled schedule SSQA is not SSA in disguise."""
    bh_q, _ = _run_single(SINGLE_BACKENDS["sparse"])
    bh_c, _ = _run(*_single("sparse")(), PLATEAUS)
    assert not np.array_equal(bh_q, bh_c)


def test_replica_coupling_ring_topology():
    """m[k-1] + m[k+1] over G independent rings of R consecutive trials."""
    rng = np.random.default_rng(0)
    m = rng.choice(np.asarray([-1, 1], np.int8), size=(8, 5))
    nb = np.asarray(replica_coupling(m, 4))
    for g in range(2):
        ring = m[4 * g:4 * (g + 1)].astype(np.int32)
        for k in range(4):
            np.testing.assert_array_equal(
                nb[4 * g + k], ring[(k - 1) % 4] + ring[(k + 1) % 4])


# ---------------------------------------------------------------------------
# Schedule: the J⊥ ramp and its signature
# ---------------------------------------------------------------------------
def test_ssqa_schedule_ramp_shape():
    s = ssqa_schedule(1, 8, tau=4, jperp_max=3)
    jp = np.asarray(s.jperp_per_cycle)
    assert jp.shape == s.i0_per_cycle.shape
    assert jp[0] == 0                       # hottest plateau: free replicas
    assert jp[-1] == 3                      # coldest plateau: J⊥ = jperp_max
    assert (np.diff(jp) >= 0).all()         # monotone ramp
    # per-plateau constant (held over each tau-cycle plateau)
    assert (jp.reshape(s.steps, s.tau) == jp.reshape(s.steps, s.tau)[:, :1]).all()


def test_ssqa_schedule_signature_distinct():
    base = hassa_schedule(1, 8, 4)
    q = ssqa_schedule(1, 8, 4, jperp_max=3)
    np.testing.assert_array_equal(base.i0_per_cycle, q.i0_per_cycle)
    assert q.signature() != base.signature()           # J⊥ ramp is visible
    assert (ssqa_schedule(1, 8, 4, jperp_max=4).signature()
            != q.signature())                          # and so is its height
    # a jperp-free Schedule hashes to the historical v1 payload
    stripped = dataclasses.replace(q, jperp_per_cycle=None)
    assert stripped.signature() == base.signature()


def test_plateaus_carry_jperp():
    by_i0 = {p.i0: p.jperp for p in PLATEAUS}
    assert by_i0[1] == 0 and by_i0[8] == 3
    assert all(p.jperp == 0 for p in schedule_plateaus(hassa_schedule(1, 8, 4)))


# ---------------------------------------------------------------------------
# Hyper-parameters, driver entry point, autotune
# ---------------------------------------------------------------------------
def test_hp_validation():
    with pytest.raises(ValueError, match="n_replicas"):
        SSQAHyperParams(n_trials=8, n_replicas=1)
    with pytest.raises(ValueError, match="divisible"):
        SSQAHyperParams(n_trials=10, n_replicas=4)
    with pytest.raises(ValueError, match="jperp_max"):
        SSQAHyperParams(n_trials=8, n_replicas=4, jperp_max=-1)
    with pytest.raises(ValueError, match="schedule_kind"):
        SSQAHyperParams(n_trials=8, n_replicas=4).schedule("ssa")


def test_anneal_ssqa_matches_anneal_with_ssqa_hp():
    hp = SSQAHyperParams(n_trials=T, n_replicas=R, m_shot=2, tau=4, i0_max=8)
    r1 = anneal_ssqa(TORUS, hp, seed=5, track_energy=False)
    r2 = anneal(TORUS, hp, seed=5, track_energy=False)
    np.testing.assert_array_equal(r1.best_energy, r2.best_energy)
    np.testing.assert_array_equal(r1.best_m, r2.best_m)
    assert r1.best_m.shape == (T, TORUS.n)  # every replica is a candidate


def test_autotune_derives_trotter_knobs():
    """torus σ≈2 → R = next_pow2(4σ) = 8, J⊥max = 2σ = 4 (the defaults),
    and n_trials rounds up to whole rings."""
    hp, report = resolve_hyperparams(
        "auto", TORUS, base=SSQAHyperParams(n_trials=10, n_replicas=2),
        algo="ssqa")
    assert isinstance(hp, SSQAHyperParams)
    assert hp.n_replicas == 8 and hp.jperp_max == 4
    assert hp.n_trials == 16                 # 10 → next multiple of 8
    assert report.n_replicas == 8 and report.jperp_max == 4


def test_autotune_algo_ssqa_defaults_base():
    hp, _ = resolve_hyperparams("auto", TORUS, algo="ssqa")
    assert isinstance(hp, SSQAHyperParams)
    assert hp.n_trials % hp.n_replicas == 0


def test_autotune_classical_base_untouched():
    hp, report = resolve_hyperparams(
        "auto", TORUS, base=SSAHyperParams(n_trials=10))
    assert not isinstance(hp, SSQAHyperParams)
    assert hp.n_trials == 10
    assert report.n_replicas is None and report.jperp_max is None
