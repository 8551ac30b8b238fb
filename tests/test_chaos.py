"""Fault-injection tests for the annealing service's resilience layer
(DESIGN.md §10).

Every fault class in the failure model is injected at its hook point and
the recovery contract is asserted:

* kill between chunks → resume from chunk checkpoints, bit-identical
  (all three backends, noise='xorshift');
* compile failure → backend fallback chain, status/events record the
  downgrade, results bit-identical;
* dense-J OOM → tiled-J downgrade on the same backend;
* NaN burst → offender quarantined (solo retry, re-autotuned I0max),
  batchmates bit-exact; exhausted retries → status='failed', no raise;
* deadline expiry → best-so-far with status='deadline', no raise;
* admission validation → typed AdmissionError before any device work;
* seeded chaos schedules → the service survives arbitrary fault mixes.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import IsingModel, SSAHyperParams, gset
from repro.core.rng import xorshift_lanes_ok
from repro.ft.faults import (
    FaultInjector,
    InjectedCompileFailure,
    InjectedKill,
    chaos_schedule,
)
from repro.serve import (
    AdmissionError,
    AnnealRequest,
    AnnealService,
    ResiliencePolicy,
)

HP = SSAHyperParams(n_trials=3, m_shot=6, tau=4, i0_min=1, i0_max=8)
BACKEND_NAMES = ("sparse", "dense", "pallas")


def _problems():
    return (gset.toroidal_grid(36, seed=0, name="t36"),
            gset.king_graph(36, seed=3, name="k36"))


def _requests(**kw):
    return [AnnealRequest(problem=p, hp=HP, seed=i + 1, **kw)
            for i, p in enumerate(_problems())]


def _assert_bit_identical(a, b):
    np.testing.assert_array_equal(a.result.best_energy, b.result.best_energy)
    np.testing.assert_array_equal(a.result.best_m, b.result.best_m)
    np.testing.assert_array_equal(a.chunk_best_cut, b.chunk_best_cut)


@pytest.fixture(scope="module")
def baselines():
    return {b: AnnealService(backend=b, min_bucket=16).solve(_requests())
            for b in BACKEND_NAMES}


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_kill_mid_solve_resumes_bit_identical(backend, baselines, tmp_path):
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path))
    inj = FaultInjector()
    inj.arm("kill", chunk=2)
    svc = AnnealService(backend=backend, min_bucket=16, resilience=pol,
                        faults=inj)
    with pytest.raises(InjectedKill):  # the kill escapes like a real death
        svc.solve(_requests())
    assert os.listdir(tmp_path)  # checkpoints survived the "crash"

    # "new process": fresh service, same policy, no faults
    svc2 = AnnealService(backend=backend, min_bucket=16, resilience=pol)
    resumed = svc2.solve(_requests())
    for base, r in zip(baselines[backend], resumed):
        _assert_bit_identical(base, r)
    resumes = [e for e in resumed[0].events if e.kind == "resume"]
    assert resumes and resumes[0].detail["chunk"] == 3  # killed after chunk 2
    assert os.listdir(tmp_path) == []  # purged after success


def test_corrupted_checkpoint_rejected_and_rerun(baselines, tmp_path):
    """Zeroed xorshift lanes in a restored checkpoint (the absorbing state)
    are detected; the service starts the group fresh instead of resuming."""
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path))
    inj = FaultInjector()
    inj.arm("kill", chunk=2)
    with pytest.raises(InjectedKill):
        AnnealService(backend="sparse", min_bucket=16, resilience=pol,
                      faults=inj).solve(_requests())
    # corrupt every checkpoint: zero the carried noise lanes
    for root, _dirs, files in os.walk(tmp_path):
        for fn in files:
            if not fn.endswith(".npz"):
                continue
            path = os.path.join(root, fn)
            with np.load(path) as z:
                flat = {k: z[k] for k in z.files}
            for k in flat:
                if "noise_state" in k:
                    flat[k] = np.zeros_like(flat[k])
                    assert not xorshift_lanes_ok(flat[k], axis=1)
            with open(path, "wb") as f:
                np.savez(f, **flat)
    resumed = AnnealService(backend="sparse", min_bucket=16,
                            resilience=pol).solve(_requests())
    kinds = [e.kind for e in resumed[0].events]
    assert "checkpoint_rejected" in kinds and "resume" not in kinds
    for base, r in zip(baselines["sparse"], resumed):
        _assert_bit_identical(base, r)  # fresh run, still correct


# ---------------------------------------------------------------------------
# Backend fallback chain
# ---------------------------------------------------------------------------
def test_pallas_compile_failure_falls_back(baselines):
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    svc = AnnealService(backend="pallas", min_bucket=16, faults=inj)
    resp = svc.solve(_requests())
    for base, r in zip(baselines["pallas"], resp):
        assert r.status == "fallback"
        _assert_bit_identical(base, r)
    hops = [(e.detail["from"], e.detail["to"])
            for e in resp[0].events if e.kind == "fallback"]
    assert hops == [("pallas", "dense")]
    assert svc.stats["fallback_compile"] == 1


def test_full_chain_pallas_dense_sparse(baselines):
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    inj.arm("compile", backend="dense")
    svc = AnnealService(backend="pallas", min_bucket=16, faults=inj)
    resp = svc.solve(_requests())
    hops = [(e.detail["from"], e.detail["to"])
            for e in resp[0].events if e.kind == "fallback"]
    assert hops == [("pallas", "dense"), ("dense", "sparse")]
    for base, r in zip(baselines["pallas"], resp):
        assert r.status == "fallback"
        _assert_bit_identical(base, r)


def test_terminal_backend_failure_propagates():
    """A fault on the chain's terminal backend has nowhere to go: surface."""
    inj = FaultInjector()
    inj.arm("compile", backend="sparse")
    svc = AnnealService(backend="sparse", min_bucket=16, faults=inj)
    with pytest.raises(InjectedCompileFailure):
        svc.solve(_requests())


def test_fallback_disabled_propagates():
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    svc = AnnealService(backend="pallas", min_bucket=16, faults=inj,
                        resilience=ResiliencePolicy(fallback=False))
    with pytest.raises(InjectedCompileFailure):
        svc.solve(_requests())


def test_dense_oom_downgrades_to_tiled(baselines):
    inj = FaultInjector()
    inj.arm("oom", backend="dense", j_mode="dense")
    svc = AnnealService(backend="dense", min_bucket=16, faults=inj)
    resp = svc.solve(_requests())
    ev = [e for e in resp[0].events if e.kind == "fallback"]
    assert ev[0].detail["fault"] == "oom"
    assert ev[0].detail["to"] == "dense"
    assert ev[0].detail["to_opts"]["j_mode"] == "tiled"
    for base, r in zip(baselines["dense"], resp):
        assert r.status == "fallback"
        _assert_bit_identical(base, r)  # tiled J is bit-identical


def test_fallback_drops_incompatible_backend_opts(baselines):
    """pallas-only opts (block_r) must not leak into the dense fallback."""
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    svc = AnnealService(backend="pallas", min_bucket=16, faults=inj,
                        backend_opts={"block_r": 8})
    resp = svc.solve(_requests())
    assert all(r.status == "fallback" for r in resp)
    ev = [e for e in resp[0].events if e.kind == "fallback"][0]
    assert "block_r" not in ev.detail["to_opts"]


# ---------------------------------------------------------------------------
# Watchdogs: NaN quarantine, deadline, admission
# ---------------------------------------------------------------------------
def test_nan_burst_quarantines_without_poisoning_batchmates(baselines):
    inj = FaultInjector()
    inj.arm("nan", chunk=1, slots=(1,))
    svc = AnnealService(backend="sparse", min_bucket=16, faults=inj)
    resp = svc.solve(_requests())
    assert resp[0].status == "ok"
    _assert_bit_identical(baselines["sparse"][0], resp[0])  # batchmate exact
    assert resp[1].status == "quarantined"
    assert resp[1].result is not None
    kinds = [e.kind for e in resp[1].events]
    assert kinds[:2] == ["quarantine", "retry"]
    retry = [e for e in resp[1].events if e.kind == "retry"][0]
    assert "i0_max" in retry.detail  # retried with a re-autotuned clamp
    assert svc.stats["nonfinite_detected"] == 1
    assert svc.stats["quarantine_recoveries"] == 1


def test_quarantine_retries_exhausted_returns_failed():
    """A request whose NaN never clears (armed for every chunk of every
    retry) comes back status='failed' — the solve never raises."""
    inj = FaultInjector()
    inj.arm("nan", count=100)  # every slot, every chunk, every retry
    pol = ResiliencePolicy(max_retries=2, backoff_base_s=0.0)
    svc = AnnealService(backend="sparse", min_bucket=16, faults=inj,
                        resilience=pol)
    resp = svc.solve([_requests()[0]])
    assert resp[0].status == "failed" and resp[0].result is None
    assert [e.kind for e in resp[0].events].count("retry") == 2
    assert svc.stats["quarantine_failures"] == 1


def test_deadline_returns_best_so_far(baselines):
    resp = AnnealService(backend="sparse", min_bucket=16).solve(
        _requests(deadline_s=1e-9))
    for r in resp:
        assert r.status == "deadline"
        assert r.result is not None
        assert r.chunks_run < r.chunks_total  # stopped at a chunk boundary
        assert any(e.kind == "deadline" for e in r.events)
    # best-so-far is a prefix of the uninterrupted run's streamed trace
    for base, r in zip(baselines["sparse"], resp):
        n = len(r.chunk_best_cut)
        np.testing.assert_array_equal(r.chunk_best_cut,
                                      base.chunk_best_cut[:n])


def test_deadline_only_affects_expired_requests(baselines):
    """One expired request must not stop its batchmate's continuation."""
    reqs = _requests()
    reqs[1] = dataclasses.replace(reqs[1], deadline_s=1e-9)
    resp = AnnealService(backend="sparse", min_bucket=16).solve(reqs)
    assert resp[0].status == "ok"
    assert resp[0].chunks_run == resp[0].chunks_total
    _assert_bit_identical(baselines["sparse"][0], resp[0])
    assert resp[1].status == "deadline"
    assert len(resp[1].chunk_best_cut) < resp[1].chunks_total


def test_admission_rejects_bad_requests():
    svc = AnnealService(backend="sparse", min_bucket=16)
    good = _requests()[0]
    # non-finite couplings (constructed directly — from_edges rejects them)
    nan_model = IsingModel(
        n=3, h=np.zeros(3, np.int32),
        nbr_idx=np.zeros((3, 1), np.int32),
        nbr_w=np.full((3, 1), np.nan),
    )
    with pytest.raises(AdmissionError, match="finite"):
        svc.solve([good, AnnealRequest(problem=nan_model, hp=HP)])
    # absurd shape
    empty = IsingModel(n=0, h=np.zeros(0, np.int32),
                       nbr_idx=np.zeros((0, 1), np.int32),
                       nbr_w=np.zeros((0, 1), np.int32))
    with pytest.raises(AdmissionError, match="n"):
        svc.solve([AnnealRequest(problem=empty, hp=HP)])
    # bad deadline
    with pytest.raises(AdmissionError, match="deadline"):
        svc.solve([dataclasses.replace(good, deadline_s=-1.0)])
    # nothing was solved, nothing compiled
    assert len(svc._programs) == 0


# ---------------------------------------------------------------------------
# Seeded chaos schedules
# ---------------------------------------------------------------------------
def test_chaos_schedule_deterministic():
    a = chaos_schedule(17)
    b = chaos_schedule(17)
    assert [(s.point, s.match, s.slots) for s in a.specs] == \
           [(s.point, s.match, s.slots) for s in b.specs]
    assert [(s.point, s.match) for s in chaos_schedule(18).specs] != \
           [(s.point, s.match) for s in a.specs]


@pytest.mark.parametrize("seed", range(4))
def test_chaos_schedule_survival(seed, baselines, tmp_path):
    """Arbitrary seeded fault mixes: the service must serve every request
    (modulo one resume after a kill), and every non-quarantined result must
    be bit-identical to the fault-free run."""
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path))
    svc = AnnealService(backend="pallas", min_bucket=16, resilience=pol,
                        faults=chaos_schedule(seed))
    try:
        resp = svc.solve(_requests())
    except InjectedKill:
        resp = AnnealService(backend="pallas", min_bucket=16,
                             resilience=pol).solve(_requests())
    assert len(resp) == 2
    for base, r in zip(baselines["pallas"], resp):
        if r.status == "quarantined":
            assert r.result is not None  # re-autotuned: different valid run
        else:
            _assert_bit_identical(base, r)
