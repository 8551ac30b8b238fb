"""XNOR-popcount bit-parallel compute path (DESIGN.md §8).

The tentpole's gate: the packed bitplanes are the *arithmetic* format, not
just the storage format.  Every property here is exact, not approximate —
the popcount contraction computes the same integers the f32 matmul does,
so 'popcount' vs 'dense' field_mode must be bit-identical end to end:
field values, kernel plateau chains, service best-cuts, distributed steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import SSAHyperParams, anneal, gset
from repro.core.engine import (
    MIN_RESIDENT_N,
    POPCOUNT_AUTO_MAX_BITS,
    make_batched_backend,
    model_weight_bits,
    resolve_backend,
    resolve_field_mode,
    schedule_plateaus,
    single_problem_backend,
)
from repro.core.ising import local_fields_popcount
from repro.kernels.bitplane import (
    PackedJ,
    _resolve_n_bits,
    adjacency_planes,
    adjacency_weight_bits,
    coalesce_adjacency,
    coalesced_planes,
    pack_couplings,
    pack_couplings_from_adjacency,
    pack_spins,
    packed_j_nbytes,
    packed_words,
    popcount_u32,
)

HP = SSAHyperParams(n_trials=3, m_shot=2, tau=4, i0_min=1, i0_max=8)


def _torus():
    # 50 spins: non-multiple-of-32 bitplane tail, ±1 weights (1 plane)
    return gset.toroidal_grid(50, seed=17)


def _king():
    # 49 spins, king's-graph topology re-weighted to ±1..±3: integer
    # multi-bit couplings → 2 magnitude bitplanes, deterministically
    p = gset.king_graph(49, seed=3)
    rng = np.random.default_rng(11)
    w = rng.integers(1, 4, len(p.edges)) * np.sign(p.weights)
    return type(p)(n=p.n, edges=p.edges, weights=w.astype(np.int64),
                   name="King49w3")


# ---------------------------------------------------------------------------
# popcount_u32 and the packed-J codec
# ---------------------------------------------------------------------------
def test_popcount_u32_counts_bits():
    x = jnp.asarray([0, 1, 0xFFFFFFFF, 0x80000001, 0xDEADBEEF], jnp.uint32)
    got = popcount_u32(x)
    assert got.dtype == jnp.int32
    want = [bin(int(v)).count("1") for v in np.asarray(x)]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_popcount_u32_rejects_non_uint32():
    with pytest.raises(TypeError):
        popcount_u32(jnp.asarray([1, 2], jnp.int32))


def test_pack_couplings_rejects_non_integer():
    J = np.asarray([[0.0, 0.5], [0.5, 0.0]], np.float32)
    with pytest.raises(ValueError, match="integer"):
        pack_couplings(J)


def test_pack_couplings_forced_bits_too_small():
    J = np.asarray([[0, 5], [5, 0]], np.float32)  # |w|=5 needs 3 planes
    with pytest.raises(ValueError, match="bitplanes"):
        pack_couplings(J, n_bits=1)


def test_packed_j_nbytes_matches_arrays():
    m = _king().to_ising()
    jb = adjacency_weight_bits(m.n, m.nbr_idx, m.nbr_w)
    pj = pack_couplings_from_adjacency(m.n, m.nbr_idx, m.nbr_w)
    assert pj.n_bits == jb == 2
    got = pj.sign.nbytes + pj.mags.nbytes + pj.base.nbytes
    assert got == packed_j_nbytes(m.n, jb)


# ---------------------------------------------------------------------------
# Coalescing and plane packing against a scatter oracle
# ---------------------------------------------------------------------------
def _oracle_coalesce(n, nbr_idx, nbr_w):
    """``np.unique`` + ``np.add.at``: the plain coalescing."""
    idx = np.asarray(nbr_idx, dtype=np.int64)
    w = np.asarray(nbr_w, dtype=np.int64)
    rows = np.broadcast_to(np.arange(n, dtype=np.int64)[:, None], idx.shape)
    live = w != 0
    keys = rows[live] * n + idx[live]
    uniq, inv = np.unique(keys, return_inverse=True)
    wsum = np.zeros(uniq.shape[0], dtype=np.int64)
    np.add.at(wsum, inv, w[live])
    nz = wsum != 0
    uniq, wsum = uniq[nz], wsum[nz]
    return uniq // n, uniq % n, wsum


def _oracle_planes(n, nbr_idx, nbr_w, n_bits=None):
    """``np.bitwise_or.at`` / ``np.add.at`` scatters of the oracle triple."""
    nw = packed_words(n)
    r, c, wsum = _oracle_coalesce(n, nbr_idx, nbr_w)
    word, bit = c // 32, (c % 32).astype(np.uint32)
    mag = np.abs(wsum)
    n_bits = _resolve_n_bits(mag.max(initial=0), n_bits)
    sign = np.zeros((n, nw), np.uint32)
    pos = wsum > 0
    np.bitwise_or.at(sign, (r[pos], word[pos]), np.uint32(1) << bit[pos])
    mags = np.zeros((n_bits, n, nw), np.uint32)
    base = np.zeros(n, np.int64)
    for b in range(n_bits):
        sel = ((mag >> b) & 1) == 1
        np.bitwise_or.at(mags[b], (r[sel], word[sel]),
                         np.uint32(1) << bit[sel])
        np.add.at(base, r[sel], -(np.int64(1) << b))
    return sign, mags, base.astype(np.int32)


def _entries(c):
    """A coalescing's nonzero entries ``(rows, cols, wsum)``, row-major,
    read from whichever form its route filled."""
    if c.dense is None:
        return c.rows, c.cols, c.wsum
    keys = np.flatnonzero(c.dense)
    return keys // c.n, keys % c.n, c.dense.reshape(-1)[keys]


def _adjacency(n, deg, w_max, seed, pad_rows=0):
    """Random padded adjacency with duplicate slots, duplicates that cancel
    to 0, zero-weight slots and ``pad_rows`` trailing padding rows."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, (n, deg)).astype(np.int32)
    w = rng.integers(-w_max, w_max + 1, (n, deg)).astype(np.int32)
    w[rng.random((n, deg)) < 0.2] = 0
    idx[:, 1] = idx[:, 0]                          # a duplicate slot...
    w[::2, 1] = -w[::2, 0]                         # ...cancelling in half
    idx[:, 2], w[:, 2] = idx[:, 3], w[:, 3]        # ...summing in all
    if pad_rows:
        idx[-pad_rows:] = np.arange(n - pad_rows, n)[:, None]
        w[-pad_rows:] = 0
    return idx, w


@pytest.mark.parametrize("n,deg,w_max,pad_rows,dense", [
    (40, 24, 1, 0, True),     # ±1, duplicates, n % 32 != 0
    (70, 40, 15, 5, True),    # 4-bit weights of both signs, padded rows
    (64, 16, 7, 0, True),     # n² exactly 4x the slots: still dense
    (65, 16, 7, 3, False),    # just past the dense rule
    (300, 6, 15, 7, False),   # sparse: 4-bit weights, padded rows
    (97, 4, 1, 0, False),     # sparse ±1, n % 32 != 0
], ids=["dense-pm1", "dense-4bit-pad", "dense-edge", "sorted-edge",
        "sorted-4bit-pad", "sorted-pm1"])
def test_coalescing_and_planes_equal_scatter_oracle(n, deg, w_max, pad_rows,
                                                    dense):
    """The bincount/sort coalescing and the packbits/reduceat planes equal
    the np.unique/np.add.at/np.bitwise_or.at scatters bit for bit, on both
    routes, at the model's own size, padded to a bucket, and with forced
    extra magnitude planes."""
    idx, w = _adjacency(n, deg, w_max, seed=n * 1000 + deg, pad_rows=pad_rows)
    c = coalesce_adjacency(n, idx, w)
    assert (c.dense is not None) == dense
    want = _oracle_coalesce(n, idx, w)
    got = _entries(c)
    assert np.any(want[2] != 0) and len(want[2]) < n * deg  # duplicates
    for g, x in zip(got, want):
        np.testing.assert_array_equal(g, x)
    need = max(1, int(np.abs(want[2]).max()).bit_length())
    assert adjacency_weight_bits(n, idx, w) == c.weight_bits == need

    nb = -(-n // 64) * 64 + 32  # a bucket with whole extra words
    pad_i = np.concatenate([idx, np.tile(np.arange(n, nb)[:, None],
                                         (1, deg))]).astype(np.int32)
    pad_w = np.concatenate([w, np.zeros((nb - n, deg), np.int32)])
    for size, ii, ww, bits in [(n, idx, w, None), (nb, pad_i, pad_w, None),
                               (nb, pad_i, pad_w, need + 2)]:
        want_p = _oracle_planes(size, ii, ww, bits)
        for got_p in (adjacency_planes(size, ii, ww, bits),
                      coalesced_planes(c, size, bits)):
            for g, x in zip(got_p, want_p):
                assert g.dtype == x.dtype and g.shape == x.shape
                np.testing.assert_array_equal(g, x)


@pytest.mark.parametrize("n,deg,dense", [(8, 8, True), (64, 4, False)],
                         ids=["dense", "sorted"])
def test_coalescing_exact_at_large_weights(n, deg, dense):
    """Weights whose total passes 2^53: the float64 bincount of the dense
    route refuses them, the int64 sums of the sorted route stay exact."""
    idx = np.tile((np.arange(n) + 1)[:, None] % n, (1, deg))
    w = np.zeros((n, deg), np.int64)
    w[:, 0] = w[:, 1] = (1 << 52) + 1
    if dense:
        with pytest.raises(ValueError, match="exactly"):
            coalesce_adjacency(n, idx, w)
        return
    c = coalesce_adjacency(n, idx, w)
    assert c.dense is None and c.max_abs == (1 << 53) + 2
    np.testing.assert_array_equal(c.wsum, np.full(n, (1 << 53) + 2))
    for g, x in zip(_entries(c), _oracle_coalesce(n, idx, w)):
        np.testing.assert_array_equal(g, x)


# ---------------------------------------------------------------------------
# Exact-integer field equivalence (the tentpole's arithmetic claim)
# ---------------------------------------------------------------------------
def _dense_int_fields(spins, h, J):
    return h.astype(np.int64) + spins.astype(np.int64) @ J.T.astype(np.int64)


@given(
    n=st.integers(1, 70),
    w_max=st.integers(1, 7),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_popcount_fields_exact_integer(n, w_max, seed):
    """Random symmetric integer-weight graphs, every tail width (n spans
    1..70 → 1-3 uint32 words with all pad widths), ±1..±7 weights (1-3
    magnitude planes): popcount fields == int64 matmul fields, exactly."""
    rng = np.random.default_rng(seed)
    J = rng.integers(-w_max, w_max + 1, (n, n)).astype(np.float32)
    J = np.triu(J, 1)
    J = J + J.T
    h = rng.integers(-3, 4, n).astype(np.int32)
    spins = (rng.integers(0, 2, (2, n)) * 2 - 1).astype(np.int8)

    pj = pack_couplings(J)
    mw = pack_spins(jnp.asarray(spins))
    got = np.asarray(local_fields_popcount(mw, jnp.asarray(h), pj))
    want = _dense_int_fields(spins, h, J)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_pack_from_adjacency_equals_pack_from_dense():
    m = _king().to_ising()
    pj_a = pack_couplings_from_adjacency(m.n, m.nbr_idx, m.nbr_w)
    pj_d = pack_couplings(np.asarray(m.dense_J()))
    np.testing.assert_array_equal(np.asarray(pj_a.sign), np.asarray(pj_d.sign))
    np.testing.assert_array_equal(np.asarray(pj_a.mags), np.asarray(pj_d.mags))
    np.testing.assert_array_equal(np.asarray(pj_a.base), np.asarray(pj_d.base))


def test_popcount_fields_tiled_equals_untiled():
    m = _king().to_ising()
    pj = pack_couplings_from_adjacency(m.n, m.nbr_idx, m.nbr_w)
    rng = np.random.default_rng(0)
    spins = (rng.integers(0, 2, (3, m.n)) * 2 - 1).astype(np.int8)
    mw = pack_spins(jnp.asarray(spins))
    h = jnp.asarray(m.h, jnp.int32)
    a = np.asarray(local_fields_popcount(mw, h, pj))
    b = np.asarray(local_fields_popcount(mw, h, pj, tile_n=16))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# No f32 unpack in the hot loop (structural)
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


def test_popcount_field_path_has_no_float_values():
    """The packed field contraction never unpacks to f32: every value in
    its jaxpr is integer/bool — the arithmetic really is bit-parallel."""
    m = _torus().to_ising()
    pj = pack_couplings_from_adjacency(m.n, m.nbr_idx, m.nbr_w)
    h = jnp.asarray(m.h, jnp.int32)
    mw = pack_spins(jnp.asarray(np.ones((3, m.n), np.int8)))
    jaxpr = jax.make_jaxpr(lambda w: local_fields_popcount(w, h, pj))(mw)
    avals = _collect_avals(jaxpr.jaxpr, [])
    floats = [a for a in avals
              if jnp.issubdtype(getattr(a, "dtype", jnp.int32), jnp.floating)]
    assert not floats, f"f32 values in the popcount field path: {floats[:5]}"


def test_dense_backend_popcount_materializes_no_J():
    m = _torus().to_ising()
    bk, problem = single_problem_backend(m, "dense", n_trials=2,
                                         noise="xorshift",
                                         field_mode="popcount")
    assert bk.field_mode == "popcount"
    assert "J" not in problem
    pj = PackedJ(problem["sign"][0], problem["mags"][0], problem["base"][0])
    assert pj.sign.dtype == jnp.uint32 and pj.mags.shape[0] == bk.j_bits


# ---------------------------------------------------------------------------
# End-to-end bit-identity: anneal() on every backend × layout × weight depth
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("problem_fn", [_torus, _king])
@pytest.mark.parametrize("backend,layout", [
    ("dense", "dense"),
    ("dense", "packed"),
    ("pallas", "dense"),
    ("pallas", "packed"),
])
def test_popcount_anneal_bitwise_equal_to_sparse(problem_fn, backend, layout):
    p = problem_fn()
    kw = dict(seed=3, record="best", noise="xorshift", track_energy=False)
    ref = anneal(p, HP, backend="sparse", **kw)
    out = anneal(p, HP, backend=backend, storage_layout=layout,
                 backend_opts={"field_mode": "popcount"}, **kw)
    np.testing.assert_array_equal(ref.best_energy, out.best_energy)
    np.testing.assert_array_equal(ref.best_cut, out.best_cut)
    np.testing.assert_array_equal(ref.best_m, out.best_m)


@given(st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_popcount_equivalence_property(seed):
    p = _king()
    hp = SSAHyperParams(n_trials=2, m_shot=2, tau=3, i0_min=1, i0_max=4)
    kw = dict(seed=seed, record="best", noise="xorshift", track_energy=False)
    ref = anneal(p, hp, backend="sparse", **kw)
    for backend in ("dense", "pallas"):
        for fm in ("popcount", "auto"):
            out = anneal(p, hp, backend=backend,
                         backend_opts={"field_mode": fm}, **kw)
            np.testing.assert_array_equal(ref.best_energy, out.best_energy)
            np.testing.assert_array_equal(ref.best_m, out.best_m)


# ---------------------------------------------------------------------------
# Multi-plateau residency: the whole chain is ONE pallas_call
# ---------------------------------------------------------------------------
def _count_primitive(jaxpr, name):
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


def test_popcount_chain_is_one_resident_launch():
    m = _torus().to_ising()
    bk, problem = single_problem_backend(m, "pallas", n_trials=2,
                                         n_rnd=HP.n_rnd, noise="xorshift",
                                         field_mode="popcount")
    plateaus = schedule_plateaus(HP.schedule("hassa"), "i0max")
    assert len(plateaus) > 1
    state = bk.init_state(problem, bk.init_noise([0], [m.n]))
    jaxpr = jax.make_jaxpr(
        lambda s: bk.run_shots(problem, s, plateaus, 1)
    )(state)
    assert _count_primitive(jaxpr.jaxpr, "pallas_call") == 1


def test_popcount_run_plateaus_equals_chained_run_plateau():
    m = _king().to_ising()
    bk, problem = single_problem_backend(m, "pallas", n_trials=2,
                                         n_rnd=HP.n_rnd, noise="xorshift",
                                         field_mode="popcount")
    plateaus = schedule_plateaus(HP.schedule("hassa"), "i0max")
    st0 = bk.init_state(problem, bk.init_noise([0], [m.n]))
    whole = bk.run_shots(problem, st0, plateaus, 1)
    chained = st0
    for p in plateaus:
        chained = bk.run_plateau(problem, chained, p.i0, length=p.length,
                                 eligible=p.eligible)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chained)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pallas_popcount_requires_streamed_noise():
    m = _torus().to_ising()
    with pytest.raises(ValueError, match="streamed"):
        single_problem_backend(m, "pallas", n_trials=2, noise="threefry",
                               field_mode="popcount")


# ---------------------------------------------------------------------------
# Batched engine: popcount group solves are bit-identical
# ---------------------------------------------------------------------------
def test_batched_popcount_bitwise_equal():
    models = [gset.toroidal_grid(36, seed=1).to_ising(), _king().to_ising()]
    nb = 64
    jb = max(adjacency_weight_bits(m.n, m.nbr_idx, m.nbr_w) for m in models)
    plateaus = schedule_plateaus(HP.schedule("hassa"), "i0max")
    seeds, lives = [7, 8], [m.n for m in models]

    def solve(backend, **opts):
        bk = make_batched_backend(backend, n_bucket=nb, n_trials=2, n_rnd=2,
                                  noise="xorshift", **opts)
        prob = bk.stack(models)
        st = bk.init_state(prob, bk.init_noise(seeds, lives))
        st = bk.run_shots(prob, st, plateaus, n_shots=2)
        bh, bm = bk.finalize(st)
        return np.asarray(bh), np.asarray(bm)

    rh, rm = solve("sparse")
    for backend in ("dense", "pallas"):
        bh, bm = solve(backend, field_mode="popcount", j_bits=jb)
        np.testing.assert_array_equal(bh, rh)
        np.testing.assert_array_equal(bm, rm)


def test_batched_popcount_insufficient_j_bits_raises():
    models = [_king().to_ising()]  # needs 2 magnitude planes
    bk = make_batched_backend("dense", n_bucket=64, n_trials=2, n_rnd=2,
                              noise="xorshift", field_mode="popcount",
                              j_bits=1)
    with pytest.raises(ValueError, match="bitplanes"):
        bk.stack(models)


# ---------------------------------------------------------------------------
# Resolvers
# ---------------------------------------------------------------------------
def test_resolve_field_mode_auto_by_weight_depth():
    assert resolve_field_mode("auto", 1) == "popcount"
    assert resolve_field_mode("auto", POPCOUNT_AUTO_MAX_BITS) == "popcount"
    assert resolve_field_mode("auto", POPCOUNT_AUTO_MAX_BITS + 1) == "dense"
    assert resolve_field_mode("dense", 1) == "dense"
    assert resolve_field_mode("popcount", 9) == "popcount"
    with pytest.raises(ValueError):
        resolve_field_mode("xnor", 1)


def test_resolve_backend_min_resident_n():
    assert resolve_backend("auto", 32) == "dense"
    assert resolve_backend("auto", MIN_RESIDENT_N - 1) == "dense"
    assert resolve_backend("auto", MIN_RESIDENT_N) == "pallas"
    assert resolve_backend("sparse", 10**6) == "sparse"
    assert resolve_backend("pallas", 2) == "pallas"  # explicit wins


def test_make_backend_auto_routes_small_n_to_dense():
    m = _torus().to_ising()  # 50 spins < MIN_RESIDENT_N
    bk, _ = single_problem_backend(m, "auto", n_trials=2, noise="xorshift")
    assert bk.name == "dense"


def test_model_weight_bits():
    assert model_weight_bits(_torus().to_ising()) == 1
    assert model_weight_bits(_king().to_ising()) == 2


# ---------------------------------------------------------------------------
# Service: popcount parity through the full serving stack
# ---------------------------------------------------------------------------
def test_service_popcount_best_cut_parity():
    from repro.serve.anneal_service import AnnealRequest, AnnealService

    probs = [gset.toroidal_grid(36, seed=1), _king()]
    hp = SSAHyperParams(n_trials=3, m_shot=2, tau=3, i0_min=1, i0_max=8)
    reqs = [AnnealRequest(problem=p, hp=hp, seed=10 + i)
            for i, p in enumerate(probs)]

    def cuts(svc):
        return [tuple(np.asarray(r.result.best_cut).tolist())
                for r in svc.solve(reqs)]

    ref = cuts(AnnealService(backend="sparse", noise="xorshift"))
    for backend, layout in [("dense", "dense"), ("pallas", "packed"),
                            ("auto", "packed")]:
        svc = AnnealService(backend=backend, noise="xorshift",
                            storage_layout=layout,
                            backend_opts={"field_mode": "auto"})
        assert cuts(svc) == ref, (backend, layout)
        if backend != "auto":
            keys = svc.cache_info()["keys"]
            assert any("field_mode" in repr(k) for k in keys)


# ---------------------------------------------------------------------------
# Distributed lowering parity
# ---------------------------------------------------------------------------
def test_distributed_popcount_step_matches_dense():
    """The batched mesh step under field_mode='popcount' is bit-identical
    to the dense-einsum step — the exact-integer property survives the
    distributed lowering path."""
    from repro.core.distributed import make_batched_iteration_step
    from repro.core.rng import xorshift_init

    models = [gset.king_graph(36, seed=5).to_ising(),
              gset.toroidal_grid(36, seed=7).to_ising()]
    hp = SSAHyperParams(n_trials=3, m_shot=2, tau=3, i0_min=1, i0_max=4)
    T, N, B = hp.n_trials, 36, len(models)
    jb = max(adjacency_weight_bits(m.n, m.nbr_idx, m.nbr_w) for m in models)

    step_d = jax.jit(make_batched_iteration_step(hp, mesh=None))
    step_pc = jax.jit(make_batched_iteration_step(hp, mesh=None,
                                                  field_mode="popcount"))

    rng0 = jnp.stack([xorshift_init(20 + i, (T, N)) for i in range(B)],
                     axis=1)                        # (4, B, T, N)
    m0 = jnp.stack([jnp.asarray(
        (np.random.default_rng(i).integers(0, 2, (T, N)) * 2 - 1), jnp.float32)
        for i in range(B)])
    it0 = jnp.where(m0 > 0, 0, -1).astype(jnp.int32)
    bH0 = jnp.full((B, T), 2**30, jnp.int32)
    bm0 = m0.astype(jnp.int8)

    JB = jnp.stack([jnp.asarray(m.dense_J(), jnp.float32) for m in models])
    hB = jnp.stack([jnp.asarray(m.h, jnp.int32) for m in models])
    pjs = [pack_couplings_from_adjacency(m.n, m.nbr_idx, m.nbr_w, n_bits=jb)
           for m in models]
    sign = jnp.stack([pj.sign for pj in pjs])
    mags = jnp.stack([pj.mags for pj in pjs])
    base = jnp.stack([pj.base for pj in pjs])

    st_d = (rng0, m0, it0, bH0, bm0)
    st_pc = (rng0, m0, it0, bH0, bm0)
    for _ in range(hp.m_shot):
        st_d = step_d(*st_d, JB, hB)
        st_pc = step_pc(*st_pc, sign, mags, base, hB)
    for a, b in zip(jax.tree.leaves(st_d), jax.tree.leaves(st_pc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_lowering_popcount_operands():
    """The dry-run lowering under popcount carries bitplane operands (no
    (B, N, N) f32 J anywhere in the program)."""
    from jax.sharding import Mesh

    from repro.core.distributed import batched_anneal_step_lowering

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    hp = SSAHyperParams(n_trials=2, m_shot=1, tau=2, i0_min=1, i0_max=2)
    B, N = 2, 64
    low = batched_anneal_step_lowering(
        mesh, n_problems=B, n_spins=N, n_trials=hp.n_trials, hp=hp,
        field_mode="popcount", j_bits=2,
    )
    txt = low.as_text()
    assert f"{B}x{N}x{N}xf32" not in txt
    assert "ui32" in txt or "u32" in txt
