"""SSQA through the serving layer (DESIGN.md §13).

Contracts under test:

* ``AnnealRequest(algo='ssqa')`` solves through :class:`AnnealService` on
  all three backends with bit-identical results, and the streaming front
  door returns exactly the one-shot service's answer (the slot splice /
  extract machinery carries the replica axis untouched);
* the registry resolves families by hp type, rejects algo/hp mismatches
  and unknown algos at admission, and keeps the family admission rules
  (PT-SSA×pallas, SSQA×pallas noise) active even with validation off;
* per-request :class:`SolverConfig` redirects a group to another execution
  surface (bit-identity preserved) but may not disagree with the service
  on noise/storage_layout (they key checkpoint fingerprints);
* checkpoint ``group_fingerprint``s distinguish algo and config.
"""
import numpy as np
import pytest

from repro.core import SolverConfig, SSAHyperParams, gset
from repro.core.ssqa import SSQAHyperParams, anneal_ssqa
from repro.serve import (
    AdmissionError,
    AnnealRequest,
    AnnealService,
    family_for,
    registered_algos,
)
from repro.serve.resilience import group_fingerprint

HP = SSQAHyperParams(n_trials=8, n_replicas=4, m_shot=3, tau=4,
                     i0_min=1, i0_max=8)
BACKEND_NAMES = ["sparse", "dense", "pallas"]


def _problems():
    return [gset.toroidal_grid(50, seed=17, name="t50"),
            gset.king_graph(49, seed=3, name="k49")]


def _requests(**kw):
    return [AnnealRequest(problem=p, hp=HP, seed=7 + 2 * i, algo="ssqa", **kw)
            for i, p in enumerate(_problems())]


# ---------------------------------------------------------------------------
# Service: backend-invariant, matches the single-problem driver
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_service_matches_driver(backend):
    svc = AnnealService(backend=backend, min_bucket=16)
    responses = svc.solve(_requests())
    for i, (p, resp) in enumerate(zip(_problems(), responses)):
        ref = anneal_ssqa(p, HP, seed=7 + 2 * i, track_energy=False,
                          config=SolverConfig())
        np.testing.assert_array_equal(ref.best_energy,
                                      resp.result.best_energy)
        np.testing.assert_array_equal(ref.best_m, resp.result.best_m)
        assert resp.result.best_m.shape == (HP.n_trials, p.n)


def test_mixed_ssa_ssqa_batch_does_not_share_groups():
    """Same bucket, same budget knobs — different families must not share a
    compiled program (their plateau programs differ by the J⊥ ramp)."""
    p = _problems()[0]
    hp_ssa = SSAHyperParams(n_trials=8, m_shot=3, tau=4, i0_min=1, i0_max=8)
    svc = AnnealService(backend="sparse", min_bucket=16)
    k_ssa = svc._group_key(AnnealRequest(problem=p, hp=hp_ssa, seed=7), 64)
    k_ssqa = svc._group_key(AnnealRequest(problem=p, hp=HP, seed=7), 64)
    assert k_ssa[0] == "ssa" and k_ssqa[0] == "ssqa"
    assert k_ssa != k_ssqa
    # and the mixed batch still solves both
    rs = svc.solve([AnnealRequest(problem=p, hp=hp_ssa, seed=7),
                    AnnealRequest(problem=p, hp=HP, seed=7, algo="ssqa")])
    assert all(r.status == "ok" and r.result is not None for r in rs)


def test_per_request_config_redirects_backend():
    """A sparse service can serve an SSQA group on the pallas popcount
    surface via the request's SolverConfig — bit-identically."""
    svc = AnnealService(backend="sparse", min_bucket=16)
    ref = svc.solve(_requests())
    cfg = SolverConfig(backend="pallas", field_mode="popcount",
                       noise_mode="streamed", backend_opts={"j_bits": 2})
    got = svc.solve(_requests(config=cfg))
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.result.best_energy,
                                      b.result.best_energy)
        np.testing.assert_array_equal(a.result.best_m, b.result.best_m)


def test_per_request_config_noise_and_layout_must_match_service():
    svc = AnnealService(backend="sparse", min_bucket=16)
    with pytest.raises(AdmissionError, match="noise"):
        svc.solve(_requests(config=SolverConfig(noise="threefry")))
    with pytest.raises(AdmissionError, match="storage_layout"):
        svc.solve(_requests(config=SolverConfig(storage_layout="packed")))


# ---------------------------------------------------------------------------
# Registry: resolution + admission rules
# ---------------------------------------------------------------------------
def test_registry_families():
    algos = registered_algos()
    assert set(algos) >= {"ssa", "sa", "ptssa", "ssqa"}
    # most-specific-type-first: an SSQA hp is also an SSA instance
    assert family_for(HP).name == "ssqa"
    assert family_for(SSAHyperParams(n_trials=4)).name == "ssa"
    assert family_for(HP, algo="ssqa").name == "ssqa"


def test_registry_rejects_mismatch_and_unknown():
    with pytest.raises(AdmissionError, match="does not match"):
        family_for(HP, algo="ssa")
    with pytest.raises(AdmissionError, match="does not match"):
        family_for(SSAHyperParams(n_trials=4), algo="ssqa")
    with pytest.raises(AdmissionError, match="unknown algo"):
        family_for(HP, algo="quantum")
    svc = AnnealService(backend="sparse", min_bucket=16)
    p = _problems()[0]
    with pytest.raises(AdmissionError, match="does not match"):
        svc.solve([AnnealRequest(problem=p, hp=HP, seed=7, algo="ssa")])


def test_ssqa_pallas_noise_rules_fire_even_with_validation_off():
    """Family admission rules are correctness, not hygiene: they apply with
    validate_admission=False too (like the historical PT-SSA×pallas one)."""
    from repro.serve import ResiliencePolicy

    p = _problems()[0]
    svc = AnnealService(
        backend="pallas", noise="threefry", min_bucket=16,
        resilience=ResiliencePolicy(validate_admission=False))
    with pytest.raises(AdmissionError, match="xorshift"):
        svc.solve([AnnealRequest(problem=p, hp=HP, seed=7)])
    svc2 = AnnealService(
        backend="pallas", min_bucket=16,
        backend_opts={"noise_mode": "pregen"},
        resilience=ResiliencePolicy(validate_admission=False))
    with pytest.raises(AdmissionError, match="streamed"):
        svc2.solve([AnnealRequest(problem=p, hp=HP, seed=7)])


# ---------------------------------------------------------------------------
# Streaming front door
# ---------------------------------------------------------------------------
def test_stream_ssqa_matches_one_shot():
    from repro.serve import StreamingAnnealService, StreamPolicy

    one_shot = AnnealService(backend="sparse", min_bucket=16)
    ref = one_shot.solve(_requests())

    ss = StreamingAnnealService(
        backend="sparse", min_bucket=16,
        policy=StreamPolicy(slots_per_table=2))
    ss.start()
    try:
        tickets = [ss.submit(r) for r in _requests()]
        got = [t.result(timeout=None) for t in tickets]
    finally:
        ss.stop()
    for a, b in zip(ref, got):
        assert b.status == "ok"
        np.testing.assert_array_equal(a.result.best_energy,
                                      b.result.best_energy)
        np.testing.assert_array_equal(a.result.best_m, b.result.best_m)


def test_stream_rejects_non_plateau_families():
    from repro.core.sa import SAHyperParams
    from repro.serve import StreamingAnnealService

    ss = StreamingAnnealService(backend="sparse", min_bucket=16)
    ss.start()
    try:
        with pytest.raises(AdmissionError, match="plateau-family"):
            ss.submit(AnnealRequest(
                problem=_problems()[0],
                hp=SAHyperParams(n_trials=4, n_cycles=64), seed=7))
    finally:
        ss.stop()


# ---------------------------------------------------------------------------
# Checkpoint fingerprints
# ---------------------------------------------------------------------------
def test_group_fingerprint_distinguishes_algo_and_config():
    p = _problems()[0]
    model = p.to_ising()

    def fp(req):
        return group_fingerprint("ssqa", 64, "sparse", "dense", "xorshift",
                                 1, [(0, req, p, model)])

    base = AnnealRequest(problem=p, hp=HP, seed=7, algo="ssqa")
    with_cfg = AnnealRequest(problem=p, hp=HP, seed=7, algo="ssqa",
                             config=SolverConfig(backend="dense"))
    no_algo = AnnealRequest(problem=p, hp=HP, seed=7)
    assert fp(base) != fp(with_cfg)
    assert fp(base) != fp(no_algo)
    assert fp(base) == fp(AnnealRequest(problem=p, hp=HP, seed=7,
                                        algo="ssqa"))
