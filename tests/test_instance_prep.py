"""Per-call instance preparation of the annealing service.

Within one ``solve()`` call each distinct problem *object* is normalized,
validated, scanned for weight bits and packed once; every request that
carries it shares the result.  Sharing must never change an answer, never
cross-wire lanes, and never merge two distinct objects, however equal.
"""
import copy

import numpy as np
import pytest

from repro.core import MaxCutProblem, SSAHyperParams, gset
from repro.core import engine
from repro.core.engine import (
    _stack_dense_models,
    _stack_packed_models,
    _stack_sparse_models,
)
from repro.serve import AnnealRequest, AnnealService
from repro.serve import anneal_service

HP = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
SERVICES = {
    "sparse": dict(backend="sparse"),
    "dense-popcount": dict(backend="dense",
                           backend_opts={"field_mode": "auto"}),
    "pallas-packed": dict(backend="pallas", storage_layout="packed",
                          backend_opts={"field_mode": "auto"}),
}


def _service(name):
    return AnnealService(min_bucket=16, **SERVICES[name])


def _answer(resp):
    """Everything a request's answer is compared on."""
    return (resp.chunks_run, [int(v) for v in resp.chunk_best_cut],
            np.asarray(resp.result.best_cut), np.asarray(resp.result.best_m))


def _assert_same(a, b):
    assert a[0] == b[0], "stop chunk"
    assert a[1] == b[1], "per-chunk best cuts"
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_array_equal(a[3], b[3])


def _sweep(problem, targets):
    return [AnnealRequest(problem=problem, hp=HP, seed=100 + i, target_cut=t)
            for i, t in enumerate(targets)]


@pytest.mark.parametrize("service", sorted(SERVICES))
def test_shared_instance_answers_equal_copied_instances(service):
    """Eight requests on one problem object answer exactly as the same eight
    on deep copies, and prepare one instance instead of eight."""
    p = gset.toroidal_grid(36, seed=1, name="t36")
    # A target some lanes reach early and some never: stop chunks differ.
    first = _service(service).solve(_sweep(p, [None] * 8))
    target = sorted(int(r.chunk_best_cut[1]) for r in first)[4]
    targets = [target, None] * 4

    shared_svc, copied_svc = _service(service), _service(service)
    shared = shared_svc.solve(_sweep(p, targets))
    copied = copied_svc.solve(
        [AnnealRequest(problem=copy.deepcopy(p), hp=HP, seed=r.seed,
                       target_cut=r.target_cut) for r in _sweep(p, targets)])
    assert len({r.chunks_run for r in shared}) > 1
    for a, b in zip(shared, copied):
        _assert_same(_answer(a), _answer(b))
    assert shared_svc.stats["requests"] == copied_svc.stats["requests"] == 8
    assert shared_svc.stats["prep_instances"] == 1
    assert copied_svc.stats["prep_instances"] == 8
    # The spans stay per request: sharing moves time, not counts.
    assert (shared_svc.stats["span_n.normalize"]
            == copied_svc.stats["span_n.normalize"] == 8)


@pytest.mark.parametrize("service", ["sparse", "dense-popcount"])
def test_mixed_list_answers_equal_requests_sent_alone(service):
    """Three instances interleaved over twelve requests, plus a fourth
    problem of the first one's shape with other weights: every answer equals
    that request's answer sent alone, so no lane is cross-wired and no two
    distinct objects are merged."""
    insts = [gset.toroidal_grid(36, seed=1, name="t36"),
             gset.king_graph(49, seed=2, name="k49"),
             gset.toroidal_grid(100, seed=4, name="t100")]
    twin = gset.toroidal_grid(36, seed=9, name="t36")
    assert twin.n == insts[0].n and len(twin.edges) == len(insts[0].edges)
    assert not np.array_equal(twin.weights, insts[0].weights)
    reqs = [AnnealRequest(problem=insts[i % 3], hp=HP, seed=200 + i)
            for i in range(12)]
    reqs[5:5] = [AnnealRequest(problem=twin, hp=HP, seed=200 + k)
                 for k in (0, 3)]  # same seeds as two requests on insts[0]

    svc = _service(service)
    together = svc.solve(reqs)
    assert svc.stats["prep_instances"] == 4
    alone_svc = _service(service)
    for req, resp in zip(reqs, together):
        _assert_same(_answer(resp), _answer(alone_svc.solve([req])[0]))


def test_weight_bits_scanned_once_per_instance(monkeypatch):
    """Routing, the field options and the packing read one coalescing per
    distinct model per call; a second call coalesces again."""
    calls = []

    def counting(model):
        calls.append(model)
        return engine.model_couplings(model)

    monkeypatch.setattr(anneal_service, "model_couplings", counting)
    svc = AnnealService(backend="auto", min_bucket=16,
                        backend_opts={"field_mode": "auto"})
    a = gset.toroidal_grid(36, seed=1)
    b = gset.king_graph(49, seed=2)
    reqs = [AnnealRequest(problem=(a, b)[i % 2], hp=HP, seed=i)
            for i in range(6)]
    svc.solve(reqs)
    assert len(calls) == 2
    assert svc.stats["span_n.weight_bits"] == 2  # route_auto + field opts
    svc.solve(reqs)
    assert len(calls) == 4


def _complete(n, seed):
    """A dense ±1 Max-Cut instance: the complete graph on ``n`` vertices."""
    ii, jj = np.triu_indices(n, k=1)
    w = np.random.default_rng(seed).choice([-1, 1], size=len(ii))
    return MaxCutProblem(n=n, edges=np.stack([ii, jj], 1), weights=w,
                         name=f"K{n}")


@pytest.mark.parametrize("problem,dense", [
    (_complete(40, 5), 1), (gset.toroidal_grid(36, seed=1), 0),
], ids=["complete", "torus"])
def test_one_coalescing_per_instance_per_call(monkeypatch, problem, dense):
    """Eight requests on one instance coalesce its couplings exactly once
    in a call, for routing, field options and packing alike, and the one
    packing reads that coalescing; the dense route is counted in
    ``stats["coalesce_dense"]``."""
    calls, packed = [], []

    def counting(*args):
        calls.append(coalesce(*args))
        return calls[-1]

    def packing(c, *args, **kw):
        packed.append(c)
        return pack(c, *args, **kw)

    coalesce, pack = engine.coalesce_adjacency, engine.coalesced_planes
    monkeypatch.setattr(engine, "coalesce_adjacency", counting)
    monkeypatch.setattr(engine, "coalesced_planes", packing)
    svc = AnnealService(backend="auto", min_bucket=16,
                        backend_opts={"field_mode": "auto"})
    resps = svc.solve(_sweep(problem, [None] * 8))
    assert all(r.status == "ok" for r in resps)
    assert resps[0].backend in ("dense", "pallas")
    assert svc.stats["prep_instances"] == 1
    assert len(calls) == 1
    assert len(packed) == 1 and packed[0] is calls[0]
    assert svc.stats["coalesce_dense"] == dense
    svc.solve(_sweep(problem, [None] * 8))
    assert len(calls) == 2
    assert svc.stats["coalesce_dense"] == 2 * dense


def _per_model(stack, models, *args):
    """The reference: every lane a distinct object, so each is padded and
    packed on its own, as before lanes shared anything."""
    got = stack([copy.deepcopy(m) for m in models], *args)
    return {k: np.asarray(v) for k, v in got.items()}


@pytest.mark.parametrize("stack,args", [
    (_stack_sparse_models, (64,)),
    (_stack_dense_models, (64, np.float32)),
    (_stack_packed_models, (64, 2)),
], ids=["sparse", "dense", "packed"])
def test_stack_of_repeated_models_equals_per_model_stack(stack, args):
    """Lanes that repeat a model object, including ``_pad_group``'s dummy
    slots, get arrays equal to stacking every lane on its own; an equal but
    distinct copy is packed apart."""
    a = gset.toroidal_grid(36, seed=1).to_ising()
    b = gset.king_graph(49, seed=2).to_ising()
    c = gset.toroidal_grid(64, seed=3).to_ising()
    lanes = [a, b, a, c, copy.deepcopy(b)]
    svc = AnnealService(min_bucket=16)
    padded, b_live, b_bucket = svc._pad_group(
        [(i, None, None, m) for i, m in enumerate(lanes)])
    assert (b_live, b_bucket) == (5, 8)
    models = [m for *_, m in padded]
    assert engine._distinct(models)[1] == [0, 1, 0, 2, 3, 0, 0, 0]

    got = stack(models, *args)
    want = _per_model(stack, models, *args)
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]), want[k], err_msg=k)
