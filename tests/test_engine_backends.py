"""Plateau-engine backend equivalence (DESIGN.md §2).

The engine's contract: `sparse`, `dense` and `pallas` (interpret mode on
CPU) backends driven by the same xorshift noise stream produce
**bit-identical** spin trajectories and best-cut results.  The update math
is integer-valued throughout and the dense/Pallas float32 accumulations are
exact below 2^24, so equality is exact, not approximate.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    SSAHyperParams,
    anneal,
    fig4_example,
    gset,
    make_batched_backend,
)
from repro.core.engine import (
    BATCHED_BACKENDS,
    BatchedDenseBackend,
    Plateau,
    schedule_plateaus,
    single_problem_backend,
    tile_plateaus,
)

BACKEND_NAMES = ["sparse", "dense", "pallas"]


def _gset_twin():
    """A small structure-faithful G-set twin (4-regular torus, ±1 weights)."""
    return gset.toroidal_grid(64, seed=17)


# ---------------------------------------------------------------------------
# Plateau grouping: the schedule's structural view
# ---------------------------------------------------------------------------
def test_schedule_plateaus_grouping():
    hp = SSAHyperParams(i0_min=1, i0_max=8, tau=5)
    ps = schedule_plateaus(hp.schedule("hassa"), "i0max")
    assert [p.i0 for p in ps] == [1, 2, 4, 8]
    assert all(p.length == 5 for p in ps)
    # HA-SSA's write-enable: only the I0max plateau is storage-eligible
    assert [p.eligible for p in ps] == [False, False, False, True]
    ps_all = schedule_plateaus(hp.schedule("hassa"), "all")
    assert all(p.eligible for p in ps_all)


def test_tile_plateaus_truncates():
    ps = (Plateau(1, 5, False), Plateau(2, 5, True))
    seq = tile_plateaus(ps, 23)
    assert sum(p.length for p in seq) == 23
    # 2 full iterations (10+10) + 3 cycles into the third
    assert [p.length for p in seq] == [5, 5, 5, 5, 3]
    assert seq[-1] == Plateau(1, 3, False)


# ---------------------------------------------------------------------------
# The acceptance property: bit-identical trajectories and best cuts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("problem_fn", [fig4_example, _gset_twin])
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_traj_bitwise_equal_across_backends(problem_fn, backend):
    p = problem_fn()
    hp = SSAHyperParams(n_trials=3, m_shot=2, tau=4, i0_min=1, i0_max=8)
    ref = anneal(p, hp, seed=5, record="traj", noise="xorshift", backend="sparse")
    out = anneal(p, hp, seed=5, record="traj", noise="xorshift", backend=backend)
    np.testing.assert_array_equal(ref.traj, out.traj)
    np.testing.assert_array_equal(ref.best_cut, out.best_cut)
    np.testing.assert_array_equal(ref.best_m, out.best_m)


@pytest.mark.parametrize("problem_fn", [fig4_example, _gset_twin])
@pytest.mark.parametrize("storage", ["i0max", "all"])
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_best_bitwise_equal_across_backends(problem_fn, storage, backend):
    """record='best' (the production path; pallas runs the resident kernel)."""
    p = problem_fn()
    hp = SSAHyperParams(n_trials=3, m_shot=2, tau=4, i0_min=1, i0_max=8)
    kw = dict(seed=3, record="best", noise="xorshift", storage=storage,
              track_energy=False)
    ref = anneal(p, hp, backend="sparse", **kw)
    out = anneal(p, hp, backend=backend, **kw)
    np.testing.assert_array_equal(ref.best_energy, out.best_energy)
    np.testing.assert_array_equal(ref.best_cut, out.best_cut)
    np.testing.assert_array_equal(ref.best_m, out.best_m)


@given(st.integers(0, 10_000))
@settings(max_examples=5, deadline=None)
def test_backend_equivalence_property(seed):
    """Property form over random seeds: all three backends, same stream."""
    p = _gset_twin()
    hp = SSAHyperParams(n_trials=2, m_shot=2, tau=3, i0_min=1, i0_max=4)
    runs = [
        anneal(p, hp, seed=seed, record="traj", noise="xorshift", backend=b)
        for b in BACKEND_NAMES
    ]
    for other in runs[1:]:
        np.testing.assert_array_equal(runs[0].traj, other.traj)
        np.testing.assert_array_equal(runs[0].best_cut, other.best_cut)


def test_energy_trace_equal_across_jnp_backends():
    """Per-cycle energy traces (one field contraction per cycle) agree."""
    p = _gset_twin()
    hp = SSAHyperParams(n_trials=3, m_shot=2, tau=4, i0_min=1, i0_max=8)
    rs = anneal(p, hp, seed=1, noise="xorshift", backend="sparse")
    rd = anneal(p, hp, seed=1, noise="xorshift", backend="dense")
    assert rs.energy_mean.shape == (hp.total_cycles,)
    np.testing.assert_array_equal(rs.energy_mean, rd.energy_mean)
    np.testing.assert_array_equal(rs.energy_min, rd.energy_min)


# ---------------------------------------------------------------------------
# The pallas backend is *resident*: one pallas_call per plateau, not per cycle
# ---------------------------------------------------------------------------
def _count_primitive(jaxpr, name: str) -> int:
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            count += 1
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                count += _count_primitive(sub, name)
            elif isinstance(v, (list, tuple)):
                for vv in v:
                    sub = getattr(vv, "jaxpr", None)
                    if sub is not None:
                        count += _count_primitive(sub, name)
    return count


def test_pallas_backend_one_call_per_plateau():
    p = _gset_twin()
    model = p.to_ising()
    hp = SSAHyperParams(n_trials=2, m_shot=3, tau=4, i0_min=1, i0_max=8)
    bk, problem = single_problem_backend(
        model, "pallas", n_trials=hp.n_trials, n_rnd=hp.n_rnd,
        noise="xorshift",
    )
    state = bk.init_state(problem, bk.init_noise([0], [model.n]))

    jaxpr = jax.make_jaxpr(
        lambda st: bk.run_plateau(problem, st, 8, length=hp.tau, eligible=True)
    )(state)
    assert _count_primitive(jaxpr.jaxpr, "pallas_call") == 1

    plateaus = schedule_plateaus(hp.schedule("hassa"), "i0max")
    jaxpr = jax.make_jaxpr(
        lambda st: bk.run_shots(problem, st, plateaus, 1)
    )(state)
    assert _count_primitive(jaxpr.jaxpr, "pallas_call") == len(plateaus) == hp.steps


def test_backend_factory_accepts_instances_and_classes():
    """Every backend name resolves to its class; an unknown name raises."""
    for name, cls in BATCHED_BACKENDS.items():
        bk = make_batched_backend(name, n_bucket=8, n_trials=2)
        assert type(bk) is cls and bk.name == name
    model = fig4_example().to_ising()
    bk, _ = single_problem_backend(model, "dense", n_trials=2)
    assert isinstance(bk, BatchedDenseBackend) and bk.n_bucket == model.n
    with pytest.raises(ValueError):
        make_batched_backend("no-such-backend", n_bucket=8, n_trials=2)
    with pytest.raises(ValueError):  # a name, never an instance
        anneal(fig4_example(), SSAHyperParams(n_trials=2, m_shot=1),
               backend=bk)
