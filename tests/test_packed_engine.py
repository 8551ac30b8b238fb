"""Packed memory subsystem (DESIGN.md §4): bit-identity of the packed
storage layout, the streamed-noise kernel, and the tiled-J path.

The refactor's gate: every memory-saving representation — uint32 spin
bitplanes between launches, in-kernel xorshift noise instead of the
(C, R, N) pregen buffer, (tile_n, N) J slabs instead of dense (N, N) — must
be bit-identical on live lanes to the dense reference, for all three
backends and both storage policies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import SSAHyperParams, anneal, gset
from repro.core.engine import (
    PackedEngineState,
    make_batched_backend,
    resolve_j_mode,
    single_problem_backend,
)
from repro.core.ising import (
    local_fields_dense,
    local_fields_sparse,
    local_fields_tiled,
)

HP = SSAHyperParams(n_trials=3, m_shot=2, tau=4, i0_min=1, i0_max=8)
BACKEND_NAMES = ["sparse", "dense", "pallas"]


def _problem():
    # 50 spins: exercises the non-multiple-of-32 bitplane tail in every layer
    return gset.toroidal_grid(50, seed=17)


def _one(model, backend, seed=0, **kw):
    """``(bk, problem, state)``: ``model`` as lane 0 of a B=1 batch."""
    bk, problem = single_problem_backend(model, backend, **kw)
    return bk, problem, bk.init_state(problem, bk.init_noise([seed], [model.n]))


# ---------------------------------------------------------------------------
# The acceptance property: packed ≡ dense, all backends × storage policies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
@pytest.mark.parametrize("storage", ["i0max", "all"])
def test_packed_bitwise_equal_to_dense(backend, storage):
    p = _problem()
    kw = dict(seed=3, record="best", noise="xorshift", storage=storage,
              track_energy=False)
    ref = anneal(p, HP, backend="sparse", **kw)
    out = anneal(p, HP, backend=backend, storage_layout="packed", **kw)
    np.testing.assert_array_equal(ref.best_energy, out.best_energy)
    np.testing.assert_array_equal(ref.best_cut, out.best_cut)
    np.testing.assert_array_equal(ref.best_m, out.best_m)


@given(st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_packed_equivalence_property(seed):
    p = _problem()
    hp = SSAHyperParams(n_trials=2, m_shot=2, tau=3, i0_min=1, i0_max=4)
    runs = [
        anneal(p, hp, seed=seed, record="best", noise="xorshift",
               backend=b, storage_layout=layout, track_energy=False)
        for b in BACKEND_NAMES
        for layout in ("dense", "packed")
    ]
    for other in runs[1:]:
        np.testing.assert_array_equal(runs[0].best_energy, other.best_energy)
        np.testing.assert_array_equal(runs[0].best_m, other.best_m)


def test_packed_state_is_the_engine_carry():
    """storage_layout='packed' really stores bitplanes: the state between
    plateaus is a PackedEngineState with uint32 spin words."""
    model = _problem().to_ising()
    bk, problem, st = _one(model, "sparse", n_trials=3, noise="xorshift",
                           storage_layout="packed")
    assert isinstance(st, PackedEngineState)
    assert st.m_packed.dtype == jnp.uint32
    assert st.m_packed.shape == (1, 3, (model.n + 31) // 32)
    st2 = bk.run_plateau(problem, st, 4, length=3, eligible=True)
    assert isinstance(st2, PackedEngineState)
    bh, bm = bk.finalize(st2)
    assert bm.shape == (1, 3, model.n) and bm.dtype == jnp.int8


# ---------------------------------------------------------------------------
# The streamed-noise resident kernel: no (C, R, N) noise buffer anywhere
# ---------------------------------------------------------------------------
def _collect_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(v.aval)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                _collect_avals(sub, out)
            elif isinstance(v, (list, tuple)):
                for vv in v:
                    sub = getattr(vv, "jaxpr", None)
                    if sub is not None:
                        _collect_avals(sub, out)
    return out


def _is_noise_buffer(aval, length, n_trials, n):
    """A per-plateau int8 noise buffer of one problem lane: (C, 1, T, N) as
    drawn, or (1, C, T, N) as the pregen kernel takes it."""
    return (getattr(aval, "dtype", None) == jnp.int8
            and getattr(aval, "shape", None) in (
                (length, 1, n_trials, n), (1, length, n_trials, n)))


def test_xorshift_pallas_plateau_has_no_noise_buffer():
    """The legacy datapath pregenerated (C, T, N) int8 noise per plateau;
    the streamed kernel must not materialize it at any nesting level."""
    model = _problem().to_ising()
    length = 7
    bk, problem, state = _one(model, "pallas", n_trials=3, noise="xorshift")
    jaxpr = jax.make_jaxpr(
        lambda st: bk.run_plateau(problem, st, 8, length=length, eligible=True)
    )(state)
    avals = _collect_avals(jaxpr.jaxpr, [])
    assert not any(_is_noise_buffer(a, length, bk.n_trials, model.n)
                   for a in avals), (
        "found a (C, T, N) int8 noise buffer in the streamed plateau program")


def test_threefry_pallas_still_pregenerates():
    """The reference path is unchanged: threefry noise cannot be generated
    in-kernel, so its plateau program still carries the (C, T, N) buffer."""
    model = _problem().to_ising()
    length = 7
    bk, problem, state = _one(model, "pallas", n_trials=3, noise="threefry")
    jaxpr = jax.make_jaxpr(
        lambda st: bk.run_plateau(problem, st, 8, length=length, eligible=True)
    )(state)
    avals = _collect_avals(jaxpr.jaxpr, [])
    assert any(_is_noise_buffer(a, length, bk.n_trials, model.n)
               for a in avals)


def test_pregen_noise_mode_is_bit_identical_and_materializes_buffer():
    """noise_mode='pregen' (the measured dense baseline of
    benchmarks/timing.py --memory) really runs the legacy datapath: its
    plateau program carries the (C, T, N) buffer, and its results equal
    the streamed kernel's bit-for-bit."""
    p = _problem()
    model = p.to_ising()
    length = 7
    bk, problem, state = _one(model, "pallas", n_trials=3, noise="xorshift",
                              noise_mode="pregen")
    assert bk.noise_mode == "pregen"
    jaxpr = jax.make_jaxpr(
        lambda st: bk.run_plateau(problem, st, 8, length=length, eligible=True)
    )(state)
    avals = _collect_avals(jaxpr.jaxpr, [])
    assert any(_is_noise_buffer(a, length, bk.n_trials, model.n)
               for a in avals)
    kw = dict(seed=3, record="best", noise="xorshift", track_energy=False)
    ref = anneal(p, HP, backend="pallas", **kw)
    out = anneal(p, HP, backend="pallas",
                 backend_opts={"noise_mode": "pregen"}, **kw)
    np.testing.assert_array_equal(ref.best_energy, out.best_energy)
    np.testing.assert_array_equal(ref.best_m, out.best_m)
    with pytest.raises(ValueError, match="streamed"):
        single_problem_backend(model, "pallas", n_trials=3, noise="threefry",
                               noise_mode="streamed")


def test_streamed_kernel_advances_the_same_rng_stream():
    """After a plateau, the kernel's carried xorshift lanes equal the host
    stream advanced by `length` draws — chunk/plateau chaining stays exact."""
    from repro.core.rng import xorshift_init, xorshift_next_bits

    model = _problem().to_ising()
    length = 5
    bk, problem, state = _one(model, "pallas", n_trials=2, noise="xorshift")
    st2 = bk.run_plateau(problem, state, 4, length=length, eligible=True)
    ns = state.noise_state[0]
    for _ in range(length):
        ns, _ = xorshift_next_bits(ns)
    np.testing.assert_array_equal(np.asarray(st2.noise_state[0]),
                                  np.asarray(ns))


# ---------------------------------------------------------------------------
# Tiled J: (tile_n, N) slabs ≡ dense (N, N), no dense buffer above threshold
# ---------------------------------------------------------------------------
def test_local_fields_tiled_matches_dense_and_sparse():
    model = _problem().to_ising()
    h = jnp.asarray(model.h, jnp.int32)
    J = jnp.asarray(model.dense_J(), jnp.float32)
    _, idx, w = model.device_arrays()
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.choice([-1, 1], size=(4, model.n)), jnp.int32)
    ref_d = local_fields_dense(m, h, J)
    ref_s = local_fields_sparse(m, h, idx, w)
    np.testing.assert_array_equal(np.asarray(ref_d), np.asarray(ref_s))
    for tile_n in (8, 16, 50, 64):  # incl. non-dividing and full-N tiles
        out = local_fields_tiled(m, h, idx, w, tile_n=tile_n)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_d))


def test_tiled_anneal_bitwise_equal_to_dense():
    p = _problem()
    kw = dict(seed=3, record="best", noise="xorshift", track_energy=False)
    ref = anneal(p, HP, backend="dense", **kw)
    out = anneal(p, HP, backend="dense",
                 backend_opts={"j_mode": "tiled", "tile_n": 16}, **kw)
    np.testing.assert_array_equal(ref.best_energy, out.best_energy)
    np.testing.assert_array_equal(ref.best_m, out.best_m)


def test_j_mode_auto_threshold():
    from repro.core.engine import TILED_J_THRESHOLD

    assert resolve_j_mode("auto", TILED_J_THRESHOLD) == "dense"
    assert resolve_j_mode("auto", TILED_J_THRESHOLD + 1) == "tiled"
    assert resolve_j_mode("dense", 10**6) == "dense"
    with pytest.raises(ValueError):
        resolve_j_mode("bogus", 16)


def test_tiled_backend_never_materializes_dense_J():
    """Above the threshold the dense backend holds adjacency, not (N, N)."""
    model = _problem().to_ising()
    bk, problem = single_problem_backend(model, "dense", n_trials=2,
                                         noise="xorshift", j_mode="tiled")
    assert bk.j_mode == "tiled" and "J" not in problem
    assert problem["nbr_idx"].shape == (1, model.n, model.max_degree)
    bkb = make_batched_backend("dense", n_bucket=64, n_trials=2,
                               noise="xorshift", j_mode="tiled")
    stacked = bkb.stack([model])
    assert "J" not in stacked and "nbr_idx" in stacked


# ---------------------------------------------------------------------------
# The service: packed layout + tiled J end-to-end (the G77 path, scaled down)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_service_packed_bit_identical_to_unpadded_runs(backend):
    from repro.serve import AnnealRequest, AnnealService

    problems = [
        gset.toroidal_grid(36, seed=1, name="t36"),
        gset.king_graph(49, seed=2, name="k49"),
        gset.toroidal_grid(100, seed=4, name="t100"),
    ]
    svc = AnnealService(backend=backend, min_bucket=16, storage_layout="packed")
    responses = svc.solve(
        [AnnealRequest(problem=p, hp=HP, seed=10 + i)
         for i, p in enumerate(problems)]
    )
    for i, (p, resp) in enumerate(zip(problems, responses)):
        ref = anneal(p, HP, seed=10 + i, record="best", noise="xorshift",
                     backend="sparse", track_energy=False)
        np.testing.assert_array_equal(ref.best_energy, resp.result.best_energy)
        np.testing.assert_array_equal(ref.best_cut, resp.result.best_cut)
        np.testing.assert_array_equal(ref.best_m, resp.result.best_m)


def test_service_tiled_j_group(monkeypatch):
    """A bucket above TILED_J_THRESHOLD serves through slabs with no dense J
    — the G77 scenario property-checked at reduced N."""
    import repro.core.engine as engine_mod

    from repro.serve import AnnealRequest, AnnealService

    monkeypatch.setattr(engine_mod, "TILED_J_THRESHOLD", 64)
    p = gset.toroidal_grid(100, seed=4, name="t100")
    ref = anneal(p, HP, seed=0, record="best", noise="xorshift",
                 backend="sparse", track_energy=False)
    svc = AnnealService(backend="dense", min_bucket=16,
                        storage_layout="packed",
                        backend_opts={"tile_n": 32})
    resp = svc.solve([AnnealRequest(problem=p, hp=HP, seed=0)])[0]
    np.testing.assert_array_equal(ref.best_energy, resp.result.best_energy)
    np.testing.assert_array_equal(ref.best_m, resp.result.best_m)
    (ent,) = svc._programs.values()
    assert ent[0].j_mode == "tiled"


def test_service_layouts_share_no_programs_but_agree():
    from repro.serve import AnnealRequest, AnnealService

    p = gset.toroidal_grid(36, seed=1)
    outs = {}
    for layout in ("dense", "packed"):
        svc = AnnealService(backend="pallas", min_bucket=16,
                            storage_layout=layout)
        outs[layout] = svc.solve([AnnealRequest(problem=p, hp=HP, seed=0)])[0]
        assert all(layout in k for k in svc._programs)
    np.testing.assert_array_equal(
        outs["dense"].result.best_energy, outs["packed"].result.best_energy
    )


# ---------------------------------------------------------------------------
# Distributed: the batched step carries packed layout and tiled J
# ---------------------------------------------------------------------------
def _init_batched(models, hp, seeds):
    from repro.core.rng import xorshift_init, xorshift_next_bits

    T = hp.n_trials
    rngs, ms, its = [], [], []
    for seed, mo in zip(seeds, models):
        r = xorshift_init(seed, (T, mo.n))
        r, r0 = xorshift_next_bits(r)
        rngs.append(r)
        ms.append(r0.astype(jnp.int8))
        its.append(jnp.where(r0 > 0, 0, -1).astype(jnp.int32))
    bH = jnp.full((len(models), T), 2**30, jnp.int32)
    return (
        jnp.stack(rngs, axis=1),
        jnp.stack(ms),
        jnp.stack(its),
        bH,
        jnp.stack(ms),
    )


def test_batched_step_packed_and_tiled_match_dense():
    from repro.core.distributed import make_batched_iteration_step
    from repro.core.engine import pack_spins, unpack_spins

    # equal max_degree (4-regular tori) so the adjacency arrays stack
    problems = [gset.toroidal_grid(36, seed=5), gset.toroidal_grid(36, seed=7)]
    models = [p.to_ising() for p in problems]
    hp = SSAHyperParams(n_trials=4, m_shot=1, tau=5, i0_min=1, i0_max=8)
    rng, m8, it, bH, bm = _init_batched(models, hp, seeds=(20, 21))
    J = jnp.stack([jnp.asarray(mo.dense_J(), jnp.float32) for mo in models])
    h = jnp.stack([jnp.asarray(mo.h, jnp.int32) for mo in models])
    idx = jnp.stack([jnp.asarray(mo.nbr_idx, jnp.int32) for mo in models])
    w = jnp.stack([jnp.asarray(mo.nbr_w, jnp.int32) for mo in models])

    ref_step = jax.jit(make_batched_iteration_step(hp, mesh=None))
    ref = ref_step(rng, m8.astype(jnp.float32), it, bH, bm, J, h)

    pk_step = jax.jit(
        make_batched_iteration_step(hp, mesh=None, storage_layout="packed")
    )
    pk = pk_step(rng, pack_spins(m8), it, bH, pack_spins(bm), J, h)
    np.testing.assert_array_equal(
        np.asarray(unpack_spins(pk[1], 36)),
        np.asarray(ref[1]).astype(np.int8),
    )
    np.testing.assert_array_equal(np.asarray(pk[3]), np.asarray(ref[3]))
    np.testing.assert_array_equal(
        np.asarray(unpack_spins(pk[4], 36)), np.asarray(ref[4])
    )

    td_step = jax.jit(
        make_batched_iteration_step(hp, mesh=None, j_mode="tiled", tile_n=16)
    )
    td = td_step(rng, m8.astype(jnp.float32), it, bH, bm, idx, w, h)
    np.testing.assert_array_equal(np.asarray(td[3]), np.asarray(ref[3]))
    np.testing.assert_array_equal(np.asarray(td[4]), np.asarray(ref[4]))
