"""Streaming-service tests (DESIGN.md §12).

The load-bearing property is *live-lane bit-identity*: a request served
through the continuous-batching slot table — seated mid-stream into a slot
another request just vacated — returns exactly the `best_cut`/spins the
one-shot `AnnealService.solve` path returns for the same request.  That is
what makes the scheduler a pure throughput optimisation rather than a new
numerical code path.  Asserted across all three backends and across slot
backfill boundaries, plus the scheduling semantics around it: priority
ordering, deadline shed/freeze, queue backpressure, target-cut retirement,
and per-slot checkpoint resume (interchangeable with one-shot solo-group
checkpoints).
"""
import os
import time

import numpy as np
import pytest

from repro.core import SSAHyperParams, gset
from repro.ft.faults import FaultInjector, InjectedKill
from repro.serve import (
    AnnealRequest,
    AnnealService,
    QueueFullError,
    ResiliencePolicy,
    StreamingAnnealService,
    StreamPolicy,
)

HP = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
BACKEND_NAMES = ("sparse", "dense", "pallas")


def _requests(k=6, **kw):
    return [AnnealRequest(problem=gset.toroidal_grid(36, seed=s, name=f"t{s}"),
                          hp=HP, seed=s, **kw)
            for s in range(k)]


def _assert_lane_identical(resp, base):
    np.testing.assert_array_equal(resp.result.best_cut, base.result.best_cut)
    np.testing.assert_array_equal(resp.result.best_m, base.result.best_m)
    np.testing.assert_array_equal(resp.chunk_best_cut, base.chunk_best_cut)


@pytest.fixture(scope="module")
def baselines():
    """One-shot solo solves: the bit-identity reference, per backend."""
    out = {}
    for b in BACKEND_NAMES:
        svc = AnnealService(backend=b, min_bucket=16)
        out[b] = [svc.solve([r])[0] for r in _requests()]
    return out


# ---------------------------------------------------------------------------
# Live-lane bit-identity across slot backfill (the acceptance property)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_stream_bit_identical_across_backfill(backend, baselines):
    """6 requests through a 2-slot table = 3 backfill generations; every
    lane must match its one-shot solo solve bit for bit."""
    ss = StreamingAnnealService(backend=backend, min_bucket=16,
                                policy=StreamPolicy(slots_per_table=2))
    tickets = [ss.submit(r) for r in _requests()]
    ss.run_until_idle()
    for t, base in zip(tickets, baselines[backend]):
        resp = t.result(timeout=0)
        assert resp.status == "ok"
        _assert_lane_identical(resp, base)
    st = ss.stream_stats()
    assert st["stream_backfills"] == 6          # every seat is a splice
    assert st["stream_tables_created"] == 1     # one bucket, one table
    assert st["stream_completed"] == 6
    assert 0.0 < st["occupancy"] <= 1.0


def test_target_cut_retires_early_and_backfills(baselines):
    """A target-stopped lane frees its slot at the chunk boundary and the
    next queued request takes it; both report exactly what the one-shot
    path reports (chunks_run, trace prefix, result)."""
    hp_long = SSAHyperParams(n_trials=3, m_shot=10, tau=4, i0_min=1, i0_max=8)
    reqs = [AnnealRequest(problem=gset.toroidal_grid(36, seed=0), hp=hp_long,
                          seed=0, target_cut=1)] + _requests(2)
    solo = AnnealService(backend="sparse", min_bucket=16)
    base = [solo.solve([r])[0] for r in reqs]
    assert base[0].chunks_run < base[0].chunks_total  # target actually fired

    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=1))
    tickets = [ss.submit(r) for r in reqs]
    ss.run_until_idle()
    for t, b in zip(tickets, base):
        resp = t.result(timeout=0)
        assert resp.chunks_run == b.chunks_run
        _assert_lane_identical(resp, b)
    st = ss.stream_stats()
    assert st["stream_retired_target"] == 1
    assert st["stream_retired_budget"] == 2


# ---------------------------------------------------------------------------
# Scheduling semantics
# ---------------------------------------------------------------------------
def test_interactive_preempts_batch_in_queue():
    """With a 1-wide table, the interactive request submitted *last* is
    seated *first* — priority class dominates FIFO order."""
    reqs = _requests(4)
    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=1,
                                                    max_tables=1))
    batch = [ss.submit(r) for r in reqs[:3]]
    inter = ss.submit(reqs[3], priority="interactive")
    ss.run_until_idle()
    assert inter.t_seated < min(t.t_seated for t in batch)
    assert all(t.result(timeout=0).status == "ok" for t in batch + [inter])
    assert inter.result(timeout=0).queued_s is not None


def test_expired_queued_request_is_shed():
    """A queued request whose deadline already passed is dropped before any
    device work: status='shed', no result, counted."""
    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=1))
    ok_t = ss.submit(_requests(1)[0])
    doomed = ss.submit(AnnealRequest(problem=gset.toroidal_grid(36, seed=9),
                                     hp=HP, seed=9, deadline_s=1e-6))
    ss.run_until_idle()
    resp = doomed.result(timeout=0)
    assert resp.status == "shed" and resp.result is None
    assert resp.chunks_run == 0
    assert any(e.kind == "shed" for e in resp.events)
    assert ok_t.result(timeout=0).status == "ok"
    assert ss.stream_stats()["stream_shed"] == 1


def test_seated_deadline_freezes_at_chunk_boundary():
    """With shedding disabled, an already-expired deadline still seats and
    is frozen at its first chunk boundary: best-so-far, status='deadline'."""
    ss = StreamingAnnealService(
        backend="sparse", min_bucket=16,
        policy=StreamPolicy(slots_per_table=1, shed_expired=False))
    t = ss.submit(AnnealRequest(problem=gset.toroidal_grid(36, seed=0),
                                hp=HP, seed=0, deadline_s=1e-6))
    ss.run_until_idle()
    resp = t.result(timeout=0)
    assert resp.status == "deadline"
    assert resp.chunks_run == 1 < resp.chunks_total
    assert resp.result is not None  # best-so-far, not dropped


def test_queue_full_backpressure():
    pol = StreamPolicy(slots_per_table=1, max_queue=1)
    ss = StreamingAnnealService(backend="sparse", min_bucket=16, policy=pol)
    ss.submit(_requests(1)[0])
    with pytest.raises(QueueFullError):
        ss.submit(AnnealRequest(problem=gset.toroidal_grid(36, seed=5),
                                hp=HP, seed=5))
    assert ss.stream_stats()["stream_rejected_queue_full"] == 1


# ---------------------------------------------------------------------------
# Per-slot checkpoints
# ---------------------------------------------------------------------------
def test_stream_kill_resumes_per_slot_bit_identical(baselines, tmp_path):
    """Kill the stream after its second quantum; a fresh stream resumes
    each surviving lane from its own checkpoint, bit-identical."""
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path))
    inj = FaultInjector()
    inj.arm("kill", chunk=1)
    svc = AnnealService(backend="sparse", min_bucket=16, resilience=pol,
                        faults=inj)
    ss = StreamingAnnealService(service=svc,
                                policy=StreamPolicy(slots_per_table=2))
    reqs = _requests(2)
    for r in reqs:
        ss.submit(r)
    with pytest.raises(InjectedKill):
        ss.run_until_idle()
    assert os.listdir(tmp_path)  # per-slot checkpoints survived the "crash"

    svc2 = AnnealService(backend="sparse", min_bucket=16, resilience=pol)
    ss2 = StreamingAnnealService(service=svc2,
                                 policy=StreamPolicy(slots_per_table=2))
    tickets = [ss2.submit(r) for r in reqs]
    ss2.run_until_idle()
    for t, base in zip(tickets, baselines["sparse"][:2]):
        resp = t.result(timeout=0)
        _assert_lane_identical(resp, base)
        resumes = [e for e in resp.events if e.kind == "resume"]
        assert resumes and resumes[0].detail["chunk"] == 2  # killed after 2
    assert ss2.stream_stats()["stream_resumes"] == 2
    assert os.listdir(tmp_path) == []  # purged on success


def test_oneshot_checkpoint_resumes_into_stream(baselines, tmp_path):
    """Slot checkpoints share the solo-group fingerprint: a checkpoint
    written by an interrupted one-shot solve() resumes inside a stream
    slot, and the answer still matches the uninterrupted one-shot run."""
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path))
    inj = FaultInjector()
    inj.arm("kill", chunk=1)
    req = _requests(1)[0]
    svc = AnnealService(backend="sparse", min_bucket=16, resilience=pol,
                        faults=inj)
    with pytest.raises(InjectedKill):
        svc.solve([req])

    ss = StreamingAnnealService(
        service=AnnealService(backend="sparse", min_bucket=16, resilience=pol),
        policy=StreamPolicy(slots_per_table=2))
    t = ss.submit(req)
    ss.run_until_idle()
    resp = t.result(timeout=0)
    assert any(e.kind == "resume" for e in resp.events)
    _assert_lane_identical(resp, baselines["sparse"][0])


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------
def test_stream_stats_counters_consistent():
    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=2))
    tickets = [ss.submit(r) for r in _requests(3)]
    ss.run_until_idle()
    st = ss.stream_stats()
    assert st["queued"] == 0 and st["live_slots"] == 0
    assert st["stream_submitted"] == 3 == st["stream_completed"]
    assert st["stream_seated"] == 3
    assert st["stream_live_lane_chunks"] <= st["stream_slot_chunks"]
    # 3 lanes x 4 chunks of real work, whatever padding ran beside them
    assert st["stream_live_lane_chunks"] == 3 * 4
    for t in tickets:
        r = t.result(timeout=0)
        assert r.lane_wall_s is not None and r.queued_s is not None


def test_background_thread_drives_stream():
    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=2))
    ss.start(poll_s=0.001)
    try:
        tickets = [ss.submit(r) for r in _requests(2)]
        for t in tickets:
            resp = t.result(timeout=120.0)
            assert resp.status == "ok"
    finally:
        ss.stop()
    assert not ss._thread or not ss._thread.is_alive()


def test_non_ssa_requests_rejected():
    from repro.core import SAHyperParams
    from repro.serve import AdmissionError
    ss = StreamingAnnealService(backend="sparse", min_bucket=16)
    with pytest.raises(AdmissionError):
        ss.submit(AnnealRequest(
            problem=gset.toroidal_grid(36, seed=0),
            hp=SAHyperParams(n_trials=2, n_cycles=8), seed=0))
