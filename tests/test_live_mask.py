"""Per-lane live mask of the batched resident kernels.

A lane whose answer is already frozen (stopped at its target or deadline)
or that only pads a group to its batch bucket is marked dead: its grid
steps copy the state through and compute nothing.  Contracts under test:

* kernels (interpret mode), all three batched resident kernels plus their
  SSQA ring variants: live lanes are bit-identical to the mask-free run,
  every output of a dead lane equals its input, an all-ones mask equals no
  mask;
* service: a group whose lanes stop at different chunks, padded to its
  bucket, returns exactly what the mask-free path returns, and
  ``masked_lane_chunks`` counts the skipped lane-chunks only where the
  backend skips them;
* stream: empty and retired slots are masked and every answer still
  equals the one-shot solve.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SSAHyperParams, gset
from repro.core.engine import BatchedPallasBackend
from repro.kernels import ssa_update as k
from repro.serve import (
    AnnealRequest,
    AnnealService,
    StreamingAnnealService,
    StreamPolicy,
)

B, R, N, C = 4, 8, 64, 5
NW = N // 32


def _inputs(kernel):
    """Seeded operands of one batched kernel: ``(args, kwargs, state)``,
    ``state`` being the inputs its outputs return, in output order."""
    rng = np.random.default_rng(5)
    it = jnp.asarray(rng.integers(-4, 4, (B, R, N)), jnp.int32)
    h = jnp.asarray(rng.integers(-2, 3, (B, N)), jnp.int32)
    bh = jnp.asarray(rng.integers(0, 50, (B, R)), jnp.int32)
    words = lambda shape: jnp.asarray(  # noqa: E731
        rng.integers(0, 2**32, shape, dtype=np.uint32))
    if kernel == "pregen":
        j = rng.integers(-1, 2, (B, N, N))
        j = np.triu(j, 1) + np.swapaxes(np.triu(j, 1), 1, 2)
        m = jnp.asarray(rng.choice([-1.0, 1.0], (B, R, N)), jnp.float32)
        bm = jnp.asarray(rng.choice([-1, 1], (B, R, N)), jnp.int8)
        noise = jnp.asarray(rng.choice([-1, 1], (B, C, R, N)), jnp.int8)
        args = (m, it, jnp.asarray(j, jnp.float32), h, noise, jnp.int32(4),
                bh, bm)
        return args, {}, (m, it, bh, bm)
    mp, bmp = words((B, R, NW)), words((B, R, NW))
    lanes = words((B, 4, R, N))
    ssqa = kernel.endswith("-ssqa")
    if kernel.startswith("streamed"):
        j = rng.integers(-1, 2, (B, N, N)).astype(np.float32)
        args = (mp, it, jnp.asarray(j), h, lanes, jnp.int32(4), bh, bmp)
        kw = {"n_cycles": C}
        if ssqa:
            kw.update(jperp=2, n_replicas=4, block_r=4)
        return args, kw, (mp, it, lanes, bh, bmp)
    base = jnp.asarray(rng.integers(-8, 0, (B, N)), jnp.int32)
    i0 = jnp.asarray([1, 2, 2, 4, 4], jnp.int32)
    fold = jnp.asarray([0, 0, 1, 0, 1, 1], jnp.int32)
    args = (mp, it, words((B, N, NW)), words((B, 2, N, NW)), base, h, lanes,
            i0, fold, bh, bmp)
    kw = {}
    if ssqa:
        kw.update(jperp_sched=jnp.asarray([1, 1, 2, 2, 3], jnp.int32),
                  n_replicas=4, block_r=4)
    return args, kw, (mp, it, lanes, bh, bmp)


KERNELS = {
    "pregen": k.ssa_plateau_batched,
    "streamed": k.ssa_plateau_packed_batched,
    "streamed-ssqa": k.ssa_plateau_packed_batched,
    "popcount": k.ssa_plateau_popcount_batched,
    "popcount-ssqa": k.ssa_plateau_popcount_batched,
}


def _random_mask(seed):
    rng = np.random.default_rng(seed)
    while True:
        m = rng.integers(0, 2, B)
        if 0 < m.sum() < B:
            return m.astype(np.int32)


MASKS = {
    "all-live": np.ones(B, np.int32),
    "all-dead": np.zeros(B, np.int32),
    "random-a": _random_mask(1),
    "random-b": _random_mask(2),
}


def _run(kernel, live):
    args, kw, _ = _inputs(kernel)
    out = KERNELS[kernel](*args, **kw, live=live)
    return [np.asarray(o) for o in out]


@functools.lru_cache(maxsize=None)
def _unmasked(kernel):
    return _run(kernel, None)


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_skips_dead_lanes(kernel, mask):
    """Live lanes equal the mask-free run bit for bit; a dead lane's every
    output equals its input (an all-live mask is the mask-free run)."""
    live = MASKS[mask]
    out = _run(kernel, jnp.asarray(live))
    _, _, state = _inputs(kernel)
    for o, ref, inp in zip(out, _unmasked(kernel), state):
        inp = np.asarray(inp)
        assert o.shape == ref.shape == inp.shape
        for b in range(B):
            np.testing.assert_array_equal(o[b], ref[b] if live[b] else inp[b])


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_mask_free_run_moves_every_lane(kernel):
    """The reference the masked runs are held to does advance each lane,
    so "a dead lane equals its input" is not met by a kernel that does
    nothing."""
    _, _, state = _inputs(kernel)
    moved = [not np.array_equal(o[b], np.asarray(i)[b])
             for o, i in zip(_unmasked(kernel), state) for b in range(B)]
    assert sum(moved) >= B


# ---------------------------------------------------------------------------
# Service: lanes that stop at different chunks, padded group
# ---------------------------------------------------------------------------
HP = SSAHyperParams(n_trials=3, m_shot=8, tau=4, i0_min=1, i0_max=8)
PALLAS = {
    "popcount": dict(backend_opts={"field_mode": "popcount"}),
    "streamed": dict(),
    "pregen": dict(noise="threefry"),
}


def _problems():
    return [gset.toroidal_grid(36, seed=s, name=f"t{s}") for s in (1, 2, 3)]


@functools.lru_cache(maxsize=None)
def _targets(noise):
    """Targets that stop request 0 after chunk 1 and request 1 later; request
    2 has none and runs the whole budget, so the group keeps going with
    stopped lanes (and one padding lane: 3 requests in bucket 4)."""
    svc = AnnealService(backend="sparse", noise=noise, min_bucket=16)
    traces = [r.chunk_best_cut for r in svc.solve(
        [AnnealRequest(problem=p, hp=HP, seed=20 + i)
         for i, p in enumerate(_problems())])]
    return (int(traces[0][0]), int(traces[1][-1]), None)


def _requests(noise):
    return [AnnealRequest(problem=p, hp=HP, seed=20 + i, target_cut=t)
            for i, (p, t) in enumerate(zip(_problems(), _targets(noise)))]


def _assert_same(a, b):
    assert a.status == b.status
    assert a.chunks_run == b.chunks_run
    assert a.chunks_total == b.chunks_total
    np.testing.assert_array_equal(a.chunk_best_cut, b.chunk_best_cut)
    np.testing.assert_array_equal(a.result.best_cut, b.result.best_cut)
    np.testing.assert_array_equal(a.result.best_energy, b.result.best_energy)
    np.testing.assert_array_equal(a.result.best_m, b.result.best_m)


@pytest.mark.parametrize("kernel", sorted(PALLAS))
def test_service_masked_group_equals_mask_free(kernel, monkeypatch):
    kw = PALLAS[kernel]
    noise = kw.get("noise", "xorshift")
    reqs = _requests(noise)
    svc = AnnealService(backend="pallas", min_bucket=16, **kw)
    masked = svc.solve(reqs)
    runs = [r.chunks_run for r in masked]
    assert runs[0] == 1 and runs[2] == HP.m_shot and len(set(runs)) > 1
    st = svc.stats
    assert st["slot_chunks"] == 4 * HP.m_shot
    assert st["masked_lane_chunks"] == (st["slot_chunks"]
                                        - st["live_lane_chunks"]) > HP.m_shot

    monkeypatch.setattr(BatchedPallasBackend, "skips_dead_lanes", False)
    free_svc = AnnealService(backend="pallas", min_bucket=16, **kw)
    for a, b in zip(masked, free_svc.solve(reqs)):
        _assert_same(a, b)
    assert free_svc.stats["masked_lane_chunks"] == 0


@pytest.mark.parametrize("noise", ["xorshift", "threefry"])
def test_vmapped_backend_counts_no_masked_lanes(noise):
    """backend='dense' ignores the mask: it masks nothing and its answers
    equal the masked resident kernel's."""
    reqs = _requests(noise)
    dense = AnnealService(backend="dense", min_bucket=16, noise=noise)
    out = dense.solve(reqs)
    assert dense.stats["masked_lane_chunks"] == 0
    assert dense.stats["slot_chunks"] > dense.stats["live_lane_chunks"]
    pallas = AnnealService(backend="pallas", min_bucket=16, noise=noise)
    for a, b in zip(out, pallas.solve(reqs)):
        _assert_same(a, b)


# ---------------------------------------------------------------------------
# Stream: empty and retired slots are dead lanes
# ---------------------------------------------------------------------------
def test_stream_answers_hold_with_retired_slots_masked():
    """Four slots, four requests: one slot retires after its first quantum
    and the others keep running beside it (then beside further empty
    slots); each answer equals its one-shot solo solve."""
    reqs = _requests("xorshift") + [AnnealRequest(
        problem=gset.toroidal_grid(36, seed=4, name="t4"), hp=HP, seed=23)]
    solo = AnnealService(backend="pallas", min_bucket=16)
    base = [solo.solve([r])[0] for r in reqs]
    ss = StreamingAnnealService(backend="pallas", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=4))
    tickets = [ss.submit(r) for r in reqs]
    ss.run_until_idle()
    for t, b in zip(tickets, base):
        resp = t.result(timeout=0)
        assert resp.status == "ok"
        assert resp.chunks_run == b.chunks_run
        np.testing.assert_array_equal(resp.chunk_best_cut, b.chunk_best_cut)
        np.testing.assert_array_equal(resp.result.best_cut, b.result.best_cut)
        np.testing.assert_array_equal(resp.result.best_m, b.result.best_m)
    st = ss.stream_stats()
    assert st["stream_retired_target"] >= 1
    assert st["stream_slot_chunks"] > st["stream_live_lane_chunks"]
