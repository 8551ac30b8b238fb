"""Resident kernels are sized against the chip's VMEM before dispatch.

An explicit ``backend='pallas'`` whose kernel cannot fit raises a
:class:`VmemBudgetError` naming the budget, before any array is built, and
the service never hides it behind its fallback chain; ``backend='auto'``
sends that size to XLA dense and says so on the response.  Interpret mode
is decided when a kernel is called, from the platform, not at import.
"""
import jax
import numpy as np
import pytest

from repro.core import SSAHyperParams, gset
from repro.core.engine import (
    make_batched_backend,
    pallas_vmem_shortfall,
    resolve_backend,
    single_problem_backend,
)
from repro.kernels import ssa_update
from repro.kernels.ssa_update import (
    VmemBudgetError,
    plateau_vmem_bytes,
    vmem_budget_bytes,
)
from repro.serve import AnnealRequest, AnnealService

HP = SSAHyperParams(n_trials=8, m_shot=1, n_rnd=2, i0_min=1, i0_max=4, tau=4)


@pytest.fixture
def small_vmem(monkeypatch):
    """A 2 MiB target chip: the dense kernels stop fitting at a few hundred
    spins, so the refusal paths run at test sizes."""
    monkeypatch.setattr(ssa_update, "TARGET_VMEM_BYTES", 2 << 20)


def test_k2000_bucket_fits_the_v5e_budget():
    budget = vmem_budget_bytes()
    for kernel in ("streamed", "popcount"):
        assert plateau_vmem_bytes(kernel, 2048) < budget
    assert plateau_vmem_bytes("pregen", 2048, n_cycles=100) < budget
    # A dense f32 J double-buffered at 8192 spins is 512 MiB: never fits.
    assert plateau_vmem_bytes("streamed", 8192) > budget


@pytest.mark.parametrize("kernel", ["pregen", "streamed", "popcount"])
def test_vmem_estimate_grows_with_the_bucket(kernel):
    sizes = [plateau_vmem_bytes(kernel, n, n_cycles=50)
             for n in (256, 1024, 4096)]
    assert sizes == sorted(sizes) and sizes[0] < sizes[-1]


def test_auto_routes_by_the_budget():
    assert resolve_backend("auto", 2048, noise="xorshift") == "pallas"
    assert resolve_backend("auto", 8192, noise="xorshift") == "dense"
    # Bitplanes are ~32x smaller than f32 J: popcount still fits at 8192.
    assert resolve_backend("auto", 8192, noise="xorshift",
                           field_mode="popcount") == "pallas"
    assert resolve_backend("auto", 1 << 15, noise="xorshift",
                           field_mode="popcount") == "dense"
    why = pallas_vmem_shortfall(8192, noise="xorshift")
    assert "MiB" in why and "budget" in why


def test_explicit_pallas_over_budget_raises_before_building():
    with pytest.raises(VmemBudgetError, match="budget"):
        make_batched_backend("pallas", n_bucket=8192, n_trials=16,
                             noise="xorshift")


def test_explicit_single_problem_pallas_over_budget_raises(small_vmem):
    model = gset.toroidal_grid(512, seed=3).to_ising()
    with pytest.raises(VmemBudgetError, match="budget"):
        single_problem_backend(model, "pallas", n_trials=8,
                               noise="xorshift")
    bk, _ = single_problem_backend(model, "auto", n_trials=8,
                                   noise="xorshift")
    assert bk.name == "dense"


def test_service_pallas_over_budget_is_not_a_fallback(small_vmem):
    svc = AnnealService(backend="pallas")  # default policy: fallback on
    req = AnnealRequest(problem=gset.toroidal_grid(512, seed=3), hp=HP)
    with pytest.raises(VmemBudgetError, match="budget"):
        svc.solve([req])
    assert not any(k.startswith("fallback") for k in svc.stats)


def test_service_auto_reports_the_route(small_vmem):
    svc = AnnealService(backend="auto")
    small = AnnealRequest(problem=gset.toroidal_grid(256, seed=1), hp=HP)
    big = AnnealRequest(problem=gset.toroidal_grid(512, seed=3), hp=HP)
    r_small, r_big = svc.solve([small, big])
    assert r_small.backend == "pallas" and r_small.status == "ok"
    assert r_big.backend == "dense" and r_big.status == "ok"
    route = [e for e in r_big.events if e.kind == "route"]
    assert len(route) == 1 and "budget" in route[0].detail["reason"]
    assert not any(e.kind == "route" for e in r_small.events)
    ref = AnnealService(backend="sparse").solve([big])[0]
    np.testing.assert_array_equal(r_big.result.best_cut, ref.result.best_cut)


def test_interpret_is_decided_at_call_time(monkeypatch):
    assert not hasattr(ssa_update, "DEFAULT_INTERPRET")
    assert ssa_update.default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssa_update.default_interpret() is False
