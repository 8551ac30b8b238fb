"""Tests for the roofline extraction layer (HLO parsing + term math)."""
import jax
import pytest

from repro.launch.hlo_analysis import (RooflineReport, collective_bytes,
                                       count_hlo_ops, model_flops, shape_bytes)

HLO = """
HloModule jit_step

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[128,4096]{1,0} all-gather(f32[128,256]{1,0} %p0), dimensions={1}
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %p0), to_apply=%add
  %rs = bf16[8,256]{1,0} reduce-scatter(bf16[64,256]{1,0} %x), dimensions={0}
  %a2a = s8[16,64]{1,0} all-to-all(s8[16,64]{1,0} %y), dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4]{1,0} %z), source_target_pairs={{0,1}}
  %ars = f32[128,256]{1,0} all-reduce-start(f32[128,256]{1,0} %p0), to_apply=%add
  %ard = f32[128,256]{1,0} all-reduce-done(f32[128,256]{1,0} %ars)
  %dot = f32[128,128]{1,0} dot(f32[128,256]{1,0} %p0, f32[256,128]{1,0} %w)
}
"""


def test_shape_bytes():
    assert shape_bytes("f32", "128,256") == 128 * 256 * 4
    assert shape_bytes("bf16", "8,256") == 8 * 256 * 2
    assert shape_bytes("s8", "16,64") == 16 * 64
    assert shape_bytes("f32", "") == 4  # scalar


def test_collective_bytes_by_type():
    out = collective_bytes(HLO)
    assert out["all-gather"] == 128 * 4096 * 4
    # plain all-reduce + async all-reduce-start; -done NOT double counted
    assert out["all-reduce"] == 2 * 128 * 256 * 4
    assert out["reduce-scatter"] == 8 * 256 * 2
    assert out["all-to-all"] == 16 * 64
    assert out["collective-permute"] == 4 * 4 * 4
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")
    )


def test_dot_not_counted():
    out = collective_bytes("%d = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)")
    assert out["total"] == 0


def test_roofline_terms_and_dominance():
    r = RooflineReport(
        flops=197e12,        # exactly 1 s of compute
        hbm_bytes=819e9 * 2,  # 2 s of memory
        coll_bytes=50e9 * 0.5,  # 0.5 s of collective
        coll_breakdown={}, n_chips=256, peak_memory_per_device=1e9,
    )
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 0.5) < 1e-9
    assert r.dominant == "memory"
    assert r.bound_time == 2.0


def test_model_flops():
    assert model_flops(1e9, 1e6, "train") == 6e15
    assert model_flops(1e9, 128, "decode") == 2 * 1e9 * 128


def test_count_hlo_ops_both_dialects():
    assert count_hlo_ops("%d = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)", "dot") == 1
    assert count_hlo_ops("%5 = stablehlo.dot_general %a, %b", "dot_general") == 1
    # op-name prefixes don't cross-match
    assert count_hlo_ops("%5 = stablehlo.dot_general %a, %b", "dot") == 0
    assert count_hlo_ops("%g = s32[4]{0} gather(s32[8] %x)", "gather") == 1
    assert count_hlo_ops("%ag = f32[4] all-gather(f32[1] %x)", "gather") == 0


@pytest.mark.parametrize("track_energy", [False, True])
def test_plateau_cycle_has_one_contraction(track_energy):
    """One plateau (C cycles) compiles to exactly TWO field contractions:
    one inside the cycle loop — i.e. one per cycle — plus one epilogue for
    the plateau's final state.  The seed's record='best' scan evaluated the
    field twice per cycle; this pins the fix per backend."""
    from repro.core import gset
    from repro.core.engine import Plateau, single_problem_backend

    model = gset.toroidal_grid(64, seed=17).to_ising()
    counts = {"dense": "dot", "sparse": "gather"}
    for kind, op in counts.items():
        bk, problem = single_problem_backend(model, kind, n_trials=4,
                                             noise="xorshift")
        state = bk.init_state(problem, bk.init_noise([0], [model.n]))
        f = jax.jit(
            lambda pr, st, bk=bk: bk.run_plateau_traced(
                pr, st, Plateau(8, 16, True), track_energy=track_energy
            )[0]
        )
        hlo = f.lower(problem, state).compile().as_text()
        assert count_hlo_ops(hlo, op) == 2, (kind, op)
        # and the dense loop uses no gathers / the sparse loop no dots
        other = "gather" if op == "dot" else "dot"
        assert count_hlo_ops(hlo, other) == 0, (kind, other)
