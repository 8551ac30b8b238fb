"""The plain reference against the program, and its controls, on the CPU.

The reference must equal the service bit for bit (stop chunk, per-chunk
best cuts, best cut per trial, best spins) on every backend and field mode
the configurations use, for seeds beyond 32 bits, with and without early
stop.  The control, noise shared by the trials of a request, must differ
everywhere.
"""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import instances, reference

HP = dict(n_trials=4, m_shot=6, n_rnd=2, i0_min=1, i0_max=8, tau=5,
          beta_shift=1)
SEEDS = [5, 2**31 + 17, 123456789]


def _same(a: reference.Outcome, b: reference.Outcome) -> bool:
    return (a.chunks == b.chunks and a.trace == b.trace
            and np.array_equal(a.best_cut, b.best_cut)
            and np.array_equal(a.best_m, b.best_m))


def _with_targets(inst, hp):
    full = reference.solve(inst, hp, SEEDS, [None] * 3)
    return [full[0].trace[1], None, full[2].trace[3]]


@pytest.mark.parametrize("backend,field_mode", [
    ("sparse", None), ("dense", "popcount"), ("dense", "dense"),
    ("pallas", "popcount"), ("pallas", "dense")])
@pytest.mark.parametrize("kind", ["toroidal", "complete"])
def test_reference_equals_the_service_bit_for_bit(backend, field_mode, kind):
    from repro.core import SSAHyperParams
    from repro.serve import AnnealRequest, AnnealService

    inst = (instances.toroidal("t", 8, 12, 11) if kind == "toroidal"
            else instances.complete("k", 40, 3))
    hp = reference.HyperParams(**HP)
    targets = _with_targets(inst, hp)
    ref = reference.solve(inst, hp, SEEDS, targets)
    opts = {} if field_mode is None else {"field_mode": field_mode}
    svc = AnnealService(backend=backend, noise="xorshift",
                        storage_layout="packed", backend_opts=opts)
    got = svc.solve([AnnealRequest(problem=instances.to_program(inst),
                                   hp=SSAHyperParams(**HP), seed=s,
                                   target_cut=t)
                     for s, t in zip(SEEDS, targets)])
    for r, o in zip(got, ref):
        assert r.chunks_run == o.chunks
        assert [int(v) for v in r.chunk_best_cut] == o.trace
        assert np.array_equal(np.asarray(r.result.best_cut), o.best_cut)
        assert np.array_equal(np.asarray(r.result.best_m), o.best_m)
    # The reported cuts are the edge-list cuts of the returned spins.
    for o in ref:
        assert np.array_equal(inst.cut(o.best_m), o.best_cut)


def test_shared_noise_control_differs_everywhere():
    for inst in (instances.toroidal("t", 8, 12, 11),
                 instances.complete("k", 40, 3)):
        hp = reference.HyperParams(**HP)
        a = reference.solve(inst, hp, SEEDS, [None] * 3)
        b = reference.solve(inst, hp, SEEDS, [None] * 3,
                            variant="shared_noise")
        assert not any(_same(x, y) for x, y in zip(a, b))


def test_lanes_match_the_published_splitmix_seeding():
    st = reference.seed_lanes(0, 1, 2)
    # SplitMix64 of 0x9E3779B97F4A7C15 * 1, low 32 bits.
    z = (0x9E3779B97F4A7C15 * 1) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    z ^= z >> 31
    assert int(st[0, 0, 0]) == z & 0xFFFFFFFF
