"""A G81-class bucket through ``AnnealService.solve`` on ``backend='auto'``.

A 100x130 rudy torus (13000 spins, bucket 16384) is past the resident
popcount kernel's VMEM budget, so ``backend='auto'`` routes its group to XLA
dense with a ``route`` event, and ``field_mode='auto'`` gives it the row-tiled
XNOR-popcount field: the path of the benchmark's g81.batch cell, at a size
the CPU runs.  The route is the real one, not forced: no threshold is
patched.  Two requests share one instance object and a third carries a
second instance, so the group is padded to four lanes and stacks two
instances.  Answers must equal the plain reference ``chipbench.reference``
bit for bit.

The service's route counters are checked on the same run and on small
groups of every backend: ``route_lanes.<backend>`` and ``tiled_lanes`` count
live lanes only, ``stack_bytes`` the bytes ``bk.stack`` returned, none of
them moves with the profiler on, and reading them changes no answer.
"""
import numpy as np
import pytest

import jax

from chipbench import instances, reference
from repro.core import SSAHyperParams, engine
from repro.serve import AnnealRequest, AnnealService

HP = dict(n_trials=2, m_shot=1, n_rnd=2, i0_min=1, i0_max=4, tau=5,
          beta_shift=1)
SEEDS = (2**31 + 5, 77, 2**20 + 3)
COUNTERS = ("route_lanes.dense", "route_lanes.pallas", "route_lanes.sparse",
            "tiled_lanes", "stack_bytes")


def _service(backend, opts):
    return AnnealService(backend=backend, noise="xorshift",
                         storage_layout="packed", backend_opts=opts)


def _requests(progs, seeds, hp):
    return [AnnealRequest(problem=p, hp=hp, seed=s)
            for p, s in zip(progs, seeds)]


def _answer(resp):
    return (resp.chunks_run, [int(v) for v in resp.chunk_best_cut],
            np.asarray(resp.result.best_cut), np.asarray(resp.result.best_m))


def _assert_same(a, b):
    assert a[0] == b[0], "stop chunk"
    assert a[1] == b[1], "per-chunk best cuts"
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_array_equal(a[3], b[3])


class _StackSpy:
    """Records the bytes of every stacked problem a backend class returns."""

    def __init__(self, monkeypatch, cls):
        self.nbytes = []
        orig = cls.stack

        def stack(bk, models, **kw):
            out = orig(bk, models, **kw)
            self.nbytes.append(sum(a.nbytes
                                   for a in jax.tree_util.tree_leaves(out)))
            return out

        monkeypatch.setattr(cls, "stack", stack)


def _counters(svc):
    return {k: svc.stats[k] for k in COUNTERS}


@pytest.fixture(scope="module")
def big():
    """The 13000-spin run: instances, the service, its answers, the bytes
    its stacks returned."""
    mp = pytest.MonkeyPatch()
    try:
        spy = _StackSpy(mp, engine.BatchedDenseBackend)
        a = instances.toroidal("A", 100, 130, 81)
        b = instances.toroidal("B", 100, 130, 82)
        pa, pb = instances.to_program(a), instances.to_program(b)
        svc = _service("auto", {"field_mode": "auto"})
        got = svc.solve(_requests([pa, pa, pb], SEEDS, SSAHyperParams(**HP)))
    finally:
        mp.undo()
    return {"insts": [a, a, b], "svc": svc, "got": got,
            "nbytes": spy.nbytes}


def test_g81_class_bucket_routes_to_the_row_tiled_popcount_field(big):
    svc, got = big["svc"], big["got"]
    assert engine.bucket_n(13000) == 16384
    for resp in got:
        assert resp.status == "ok"
        assert resp.backend == "dense"
        assert resp.bucket == 16384
        routes = [ev for ev in resp.events if ev.kind == "route"]
        assert len(routes) == 1
        assert routes[0].detail["backend"] == "dense"
        assert "VMEM" in routes[0].detail["reason"]
    (prog,) = {id(p.backend): p for p in svc.programs()}.values()
    bk = prog.backend
    assert isinstance(bk, engine.BatchedDenseBackend)
    assert bk.field_mode == "popcount"
    assert bk._pc_tile == bk.tile_n < bk.n_bucket
    assert bk.row_tiled


def test_g81_class_bucket_equals_the_plain_reference(big):
    hp = reference.HyperParams(**HP)
    a, _, b = big["insts"]
    ref = (reference.solve(a, hp, list(SEEDS[:2]), [None, None])
           + reference.solve(b, hp, [SEEDS[2]], [None]))
    for resp, o in zip(big["got"], ref):
        _assert_same(_answer(resp), (o.chunks, o.trace, o.best_cut, o.best_m))


def test_g81_class_counters_count_live_lanes_and_stacked_bytes(big):
    c = _counters(big["svc"])
    # Three live lanes in a group padded to four; one stack of four lanes.
    assert big["svc"].stats["slot_chunks"] == 4
    assert c["route_lanes.dense"] == 3 and c["tiled_lanes"] == 3
    assert c["route_lanes.pallas"] == c["route_lanes.sparse"] == 0
    assert big["nbytes"] == [c["stack_bytes"]]
    # h, sign, one magnitude plane, base, for four lanes of 16384 spins.
    assert c["stack_bytes"] == 4 * (2 * 16384 * 4 + 2 * 16384 * 512 * 4)


SMALL = {
    # name: (backend, backend opts, spins, expected route, row-tiled)
    "sparse": ("sparse", {}, 64, "sparse", False),
    "dense-block": ("dense", {"field_mode": "auto"}, 64, "dense", False),
    "dense-tiled-j": ("dense", {"field_mode": "dense", "j_mode": "tiled",
                                "tile_n": 16}, 64, "dense", True),
    "auto-pallas": ("auto", {"field_mode": "auto"}, 256, "pallas", False),
}
SMALL_HP = dict(n_trials=2, m_shot=2, n_rnd=2, i0_min=1, i0_max=4, tau=3,
                beta_shift=1)


def _small_run(name, monkeypatch, profile_dir=None):
    backend, opts, n, _, _ = SMALL[name]
    cls = engine.BATCHED_BACKENDS[SMALL[name][3]]
    spy = _StackSpy(monkeypatch, cls)
    side = int(np.sqrt(n))
    p = instances.to_program(instances.toroidal("s", side, n // side, 5))
    q = instances.to_program(instances.toroidal("q", side, n // side, 6))
    svc = _service(backend, opts)
    reqs = _requests([p, q, p], (11, 2**31 + 9, 13), SSAHyperParams(**SMALL_HP))
    if profile_dir is None:
        got = svc.solve(reqs)
    else:
        with jax.profiler.trace(str(profile_dir)):
            got = svc.solve(reqs)
    monkeypatch.undo()
    return svc, got, spy.nbytes


@pytest.mark.parametrize("name", sorted(SMALL))
def test_route_counters_count_live_lanes_on_every_backend(name, monkeypatch,
                                                          tmp_path):
    _, _, _, route, tiled = SMALL[name]
    svc, got, nbytes = _small_run(name, monkeypatch)
    c = _counters(svc)
    assert {r.backend for r in got} == {route}
    # Three live lanes, padded to a group of four.
    assert svc.stats["slot_chunks"] == 4 * svc.stats["chunks_run"]
    assert c[f"route_lanes.{route}"] == 3
    assert sum(c[f"route_lanes.{b}"] for b in ("dense", "pallas",
                                               "sparse")) == 3
    assert c["tiled_lanes"] == (3 if tiled else 0)
    assert nbytes and c["stack_bytes"] == sum(nbytes)

    # The same run under the profiler: the same counters, the same answers.
    svc_p, got_p, _ = _small_run(name, monkeypatch, tmp_path / "trace")
    assert _counters(svc_p) == c
    for a, b in zip(got, got_p):
        _assert_same(_answer(a), _answer(b))


def test_reading_the_counters_changes_no_answer(monkeypatch):
    """Answers of a run whose counters are read equal a run's whose are not,
    and both equal the reference."""
    svc, got, _ = _small_run("dense-tiled-j", monkeypatch)
    assert _counters(svc)["tiled_lanes"] == 3
    _, again, _ = _small_run("dense-tiled-j", monkeypatch)
    hp = reference.HyperParams(**SMALL_HP)
    p = instances.toroidal("s", 8, 8, 5)
    q = instances.toroidal("q", 8, 8, 6)
    ref_p = reference.solve(p, hp, [11, 13], [None, None])
    ref_q = reference.solve(q, hp, [2**31 + 9], [None])
    for resp, resp2, o in zip(got, again, [ref_p[0], ref_q[0], ref_p[1]]):
        want = (o.chunks, o.trace, o.best_cut, o.best_m)
        _assert_same(_answer(resp), want)
        _assert_same(_answer(resp2), want)


@pytest.mark.parametrize("base,field_mode,n_bucket,tiled", [
    ("sparse", "auto", 8192, False),
    ("dense", "dense", 64, True),
    ("dense", "popcount", 64, False),
    ("dense", "popcount", 8192, True),
])
def test_spin_sharded_backend_says_whether_it_row_tiles(base, field_mode,
                                                        n_bucket, tiled):
    from repro.core.distributed import BatchedSpinShardedBackend

    bk = BatchedSpinShardedBackend(base_backend=base, field_mode=field_mode,
                                   n_bucket=n_bucket, n_trials=2)
    assert bk.row_tiled is tiled
