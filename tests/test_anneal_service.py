"""Shape-bucketed annealing service (DESIGN.md §7).

The serving contracts under test:

* one compiled plateau program per shape bucket — counted by trace-time
  side effects AND by the jitted functions' cache sizes (jit cache misses);
* batched, padded, chunked runs are bit-identical on the live lanes to the
  unpadded single-problem drivers (padding invariance, all three backends);
* chunked execution streams per-chunk best reports and early-stops on
  target_cut;
* SA and PT-SSA requests ride the same entry.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    SAHyperParams,
    SSAHyperParams,
    anneal,
    anneal_sa,
    bucket_n,
    gset,
    memory,
    pad_model,
)
from repro.core.pt import PTSSAHyperParams, anneal_pt_ssa
from repro.serve import AnnealRequest, AnnealService

HP = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
BACKEND_NAMES = ["sparse", "dense", "pallas"]


def _mixed_problems():
    """Heterogeneous sizes spanning two buckets (min_bucket=16 → 64, 128)."""
    return [
        gset.toroidal_grid(36, seed=1, name="t36"),
        gset.king_graph(49, seed=2, name="k49"),
        gset.toroidal_grid(64, seed=3, name="t64"),
        gset.toroidal_grid(100, seed=4, name="t100"),
    ]


# ---------------------------------------------------------------------------
# The acceptance property: mixed-size batches == per-problem unpadded runs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_mixed_batch_bit_identical_to_unpadded_runs(backend):
    problems = _mixed_problems()
    reqs = [AnnealRequest(problem=p, hp=HP, seed=10 + i)
            for i, p in enumerate(problems)]
    svc = AnnealService(backend=backend, min_bucket=16)
    responses = svc.solve(reqs)
    for i, (p, resp) in enumerate(zip(problems, responses)):
        ref = anneal(p, HP, seed=10 + i, record="best", noise="xorshift",
                     backend="sparse", track_energy=False)
        np.testing.assert_array_equal(ref.best_energy, resp.result.best_energy)
        np.testing.assert_array_equal(ref.best_cut, resp.result.best_cut)
        np.testing.assert_array_equal(ref.best_m, resp.result.best_m)
        assert resp.result.best_m.shape == (HP.n_trials, p.n)  # live lanes only
        assert resp.bucket == bucket_n(p.n, 16)


# ---------------------------------------------------------------------------
# Padding-invariance property: padded-to-next-bucket == unpadded, live lanes
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_padding_invariance_property(seed):
    """A problem zero-padded to its bucket (zero J rows/cols, zero h) yields
    the identical best cut and best spins on the live lanes — all three
    backends."""
    p = gset.king_graph(36, seed=seed % 7)
    model = p.to_ising()
    nb = bucket_n(model.n, 16)
    assert nb > model.n  # the property is about actual padding
    padded = pad_model(model, nb)
    assert padded.n == nb
    assert np.all(np.asarray(padded.h[model.n:]) == 0)
    assert np.all(np.asarray(padded.nbr_w[model.n:]) == 0)

    ref = anneal(p, HP, seed=seed, record="best", noise="xorshift",
                 backend="sparse", track_energy=False)
    for backend in BACKEND_NAMES:
        svc = AnnealService(backend=backend, min_bucket=16)
        resp = svc.solve([AnnealRequest(problem=p, hp=HP, seed=seed)])[0]
        np.testing.assert_array_equal(ref.best_cut, resp.result.best_cut)
        np.testing.assert_array_equal(ref.best_m, resp.result.best_m)


# ---------------------------------------------------------------------------
# One compile per bucket (the retrace/recompile fix), counted two ways
# ---------------------------------------------------------------------------
def test_same_bucket_batch_compiles_plateau_program_once():
    svc = AnnealService(backend="sparse", min_bucket=16)
    reqs = [
        AnnealRequest(problem=gset.toroidal_grid(36, seed=s, name=f"g{s}"),
                      hp=HP, seed=s)
        for s in range(4)
    ]
    svc.solve(reqs)
    # Trace-time side-effect counters: the plateau chunk program traced once.
    assert svc.stats["traces_chunk"] == 1
    assert svc.stats["traces_init"] == 1
    assert svc.stats["program_cache_misses"] == 1
    # jax.jit's own cache agrees: one entry per jitted program — the
    # program's recorded ahead-of-time compile is that entry, not a second.
    (_, init_fn, chunk_fn), = svc._programs.values()
    for prog in (init_fn, chunk_fn):
        assert prog._jit._cache_size() == 1
        assert prog.compiled is not None and prog.compile_s > 0


def test_one_compile_per_bucket_for_mixed_sizes():
    svc = AnnealService(backend="sparse", min_bucket=16)
    reqs = [AnnealRequest(problem=p, hp=HP, seed=i)
            for i, p in enumerate(_mixed_problems())]
    svc.solve(reqs)
    # 36/49/64 → bucket 64; 100 → bucket 128: two buckets, two programs.
    assert svc.stats["traces_chunk"] == 2
    assert len(svc._programs) == 2


def test_executable_reused_across_solve_calls():
    svc = AnnealService(backend="sparse", min_bucket=16)
    mk = lambda s: [AnnealRequest(  # noqa: E731
        problem=gset.toroidal_grid(36, seed=s), hp=HP, seed=s)]
    svc.solve(mk(0))
    svc.solve(mk(1))
    svc.solve(mk(2))
    assert svc.stats["traces_chunk"] == 1  # compiled once, reused twice
    assert svc.stats["program_cache_hits"] == 2


# ---------------------------------------------------------------------------
# Chunked execution: streaming reports + early stop
# ---------------------------------------------------------------------------
def test_chunk_reports_stream_and_early_stop():
    p = gset.toroidal_grid(36, seed=1)
    hp = SSAHyperParams(n_trials=3, m_shot=10, tau=4, i0_min=1, i0_max=8)
    events = []
    svc = AnnealService(backend="sparse", min_bucket=16)
    resp = svc.solve(
        [AnnealRequest(problem=p, hp=hp, seed=0, target_cut=1)],
        progress=events.append,
    )[0]
    assert resp.chunks_run < resp.chunks_total  # early stop fired
    assert resp.result.overall_best_cut >= 1
    assert len(events) == resp.chunks_run
    assert [e.chunk for e in events] == list(range(resp.chunks_run))
    # the streamed trace is monotone (a running best) and matches the result
    trace = resp.chunk_best_cut
    assert len(trace) == resp.chunks_run
    assert all(a <= b for a, b in zip(trace, trace[1:]))
    assert trace[-1] == resp.result.overall_best_cut
    assert svc.stats["early_stops"] == 1


def test_untargeted_requests_run_to_completion():
    p = gset.toroidal_grid(36, seed=1)
    hp = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
    svc = AnnealService(backend="sparse", min_bucket=16)
    resp = svc.solve([AnnealRequest(problem=p, hp=hp, seed=0)])[0]
    assert resp.chunks_run == resp.chunks_total == hp.m_shot


def test_chunked_equals_unchunked():
    p = gset.toroidal_grid(36, seed=5)
    hp = SSAHyperParams(n_trials=3, m_shot=6, tau=4, i0_min=1, i0_max=8)
    r1 = AnnealService(backend="sparse", min_bucket=16, chunk_shots=1).solve(
        [AnnealRequest(problem=p, hp=hp, seed=3)])[0]
    r3 = AnnealService(backend="sparse", min_bucket=16, chunk_shots=3).solve(
        [AnnealRequest(problem=p, hp=hp, seed=3)])[0]
    np.testing.assert_array_equal(r1.result.best_energy, r3.result.best_energy)
    assert r1.chunks_run == 6 and r3.chunks_run == 2


# ---------------------------------------------------------------------------
# SA and PT-SSA ride the same service entry
# ---------------------------------------------------------------------------
def test_sa_requests_via_service():
    problems = [gset.toroidal_grid(36, seed=1), gset.king_graph(49, seed=2)]
    hp = SAHyperParams(n_trials=4, n_cycles=400)
    svc = AnnealService(backend="sparse", min_bucket=16)
    responses = svc.solve(
        [AnnealRequest(problem=p, hp=hp, seed=1) for p in problems]
    )
    for p, r in zip(problems, responses):
        assert r.result.best_m.shape == (hp.n_trials, p.n)
        # padded lanes never proposed → reported spins reproduce the cut
        cuts = p.cut_value(np.asarray(r.result.best_m, np.int32))
        np.testing.assert_array_equal(np.asarray(cuts), r.result.best_cut)
        # sanity vs the single-problem driver's solution quality
        ref = anneal_sa(p, hp, seed=1, track_energy=False)
        assert r.result.overall_best_cut >= 0.7 * max(ref.overall_best_cut, 1)


def test_ptssa_requests_bit_identical_to_driver():
    problems = [gset.toroidal_grid(36, seed=1), gset.king_graph(49, seed=2)]
    hp = PTSSAHyperParams(n_replicas=6, n_rounds=8, tau=10)
    svc = AnnealService(backend="sparse", min_bucket=16, chunk_shots=2)
    responses = svc.solve(
        [AnnealRequest(problem=p, hp=hp, seed=2) for p in problems]
    )
    for p, r in zip(problems, responses):
        ref = anneal_pt_ssa(p, hp, seed=2, backend="sparse", noise="xorshift")
        np.testing.assert_array_equal(ref.best_energy, r.result.best_energy)
        np.testing.assert_array_equal(ref.best_cut, r.result.best_cut)


def test_ptssa_rejects_pallas_backend():
    with pytest.raises(ValueError, match="per-replica I0"):
        AnnealService(backend="pallas", min_bucket=16).solve(
            [AnnealRequest(problem=gset.toroidal_grid(36, seed=1),
                           hp=PTSSAHyperParams(n_replicas=4, n_rounds=2, tau=5))]
        )


# ---------------------------------------------------------------------------
# Bucketing + padding-overhead memory model
# ---------------------------------------------------------------------------
def test_bucket_n_powers_of_two():
    assert bucket_n(800) == 1024
    assert bucket_n(1024) == 1024
    assert bucket_n(1025) == 2048
    assert bucket_n(10, min_bucket=64) == 64


def test_padding_overhead_model():
    hp = SSAHyperParams()  # Table II: tau=100
    # N=800 → bucket 1024: 224 dead lanes × 100 stored cycles per iteration
    assert memory.padding_overhead_bits_per_iteration(800, hp) == 224 * 100
    # conventional SSA stores every plateau → steps× the waste
    assert memory.padding_overhead_bits_per_iteration(
        800, hp, hardware_aware=False
    ) == 224 * 100 * memory.memory_ratio(hp)
    # exactly-bucket-sized problems waste nothing
    assert memory.padding_overhead_bits_per_iteration(1024, hp) == 0
    assert memory.padding_overhead_fraction(800) == pytest.approx(224 / 1024)


# ---------------------------------------------------------------------------
# Request-boundary edge cases (DESIGN.md §10)
# ---------------------------------------------------------------------------
def test_empty_batch_returns_empty():
    svc = AnnealService(backend="sparse", min_bucket=16)
    assert svc.solve([]) == []
    assert svc.stats["requests"] == 0 and len(svc._programs) == 0


def test_duplicate_and_aliased_requests():
    """The same request object repeated in one batch: every occurrence gets
    its own (identical) response; batchmates are unaffected."""
    p = gset.toroidal_grid(36, seed=1)
    hp = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
    req = AnnealRequest(problem=p, hp=hp, seed=7)
    solo = AnnealService(backend="sparse", min_bucket=16).solve([req])[0]
    svc = AnnealService(backend="sparse", min_bucket=16)
    rs = svc.solve([req, req, AnnealRequest(problem=p, hp=hp, seed=8), req])
    assert len(rs) == 4
    for r in (rs[0], rs[1], rs[3]):
        np.testing.assert_array_equal(r.result.best_energy,
                                      solo.result.best_energy)
        np.testing.assert_array_equal(r.result.best_m, solo.result.best_m)
    assert rs[2].result.best_energy.shape == solo.result.best_energy.shape
    assert all(r.status == "ok" for r in rs)


# ---------------------------------------------------------------------------
# Executable-cache bounds and concurrency
# ---------------------------------------------------------------------------
def test_executable_cache_lru_eviction():
    """A capacity-1 cache evicts the cold program, counts the eviction,
    and recompiles (bit-identically) when the evicted bucket returns."""
    p_small = gset.toroidal_grid(36, seed=1)   # bucket 64
    p_large = gset.toroidal_grid(100, seed=2)  # bucket 128
    base = AnnealService(backend="sparse", min_bucket=16).solve(
        [AnnealRequest(problem=p_small, hp=HP, seed=1)])[0]

    svc = AnnealService(backend="sparse", min_bucket=16,
                        max_cached_executables=1)
    svc.solve([AnnealRequest(problem=p_small, hp=HP, seed=1)])
    svc.solve([AnnealRequest(problem=p_large, hp=HP, seed=2)])
    info = svc.cache_info()
    assert info["capacity"] == 1
    assert info["programs"] == 1      # bounded, not growing
    assert info["evictions"] == 1     # small-bucket program was dropped

    # the evicted program recompiles on return — same answer, new trace
    traces_before = svc.stats["traces_chunk"]
    r = svc.solve([AnnealRequest(problem=p_small, hp=HP, seed=1)])[0]
    assert svc.stats["traces_chunk"] == traces_before + 1
    assert svc.cache_info()["evictions"] == 2
    np.testing.assert_array_equal(r.result.best_energy,
                                  base.result.best_energy)
    np.testing.assert_array_equal(r.result.best_m, base.result.best_m)

    with pytest.raises(ValueError):
        AnnealService(backend="sparse", max_cached_executables=0)


def test_concurrent_solves_share_cache_safely(tmp_path):
    """Two threads solving same-bucket requests concurrently: no cache
    corruption, both bit-identical to their sequential runs, and their
    checkpoint trees land under distinct group fingerprints."""
    import threading

    from repro.serve import ResiliencePolicy

    reqs = [AnnealRequest(problem=gset.toroidal_grid(36, seed=s), hp=HP,
                          seed=s) for s in (1, 2)]
    solo = AnnealService(backend="sparse", min_bucket=16)
    base = [solo.solve([r])[0] for r in reqs]

    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path),
                           cleanup_on_success=False)
    svc = AnnealService(backend="sparse", min_bucket=16, resilience=pol)
    svc.solve([reqs[0]])  # warm the executable so both threads race reuse
    results, errors = [None, None], []
    gate = threading.Barrier(2)

    def worker(i):
        try:
            gate.wait(timeout=30)
            results[i] = svc.solve([reqs[i]])[0]
        except Exception as e:  # pragma: no cover - surfaced via assert
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    for r, b in zip(results, base):
        assert r is not None and r.status == "ok"
        np.testing.assert_array_equal(r.result.best_energy,
                                      b.result.best_energy)
        np.testing.assert_array_equal(r.result.best_m, b.result.best_m)
    # distinct problems => distinct checkpoint fingerprints, both present
    assert len(os.listdir(tmp_path)) == 2
    # the cache stayed bounded and coherent: one program, no evictions
    info = svc.cache_info()
    assert info["programs"] == 1 and info["evictions"] == 0
