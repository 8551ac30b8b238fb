"""Host spans of the annealing service (``repro.serve.spans``).

Every span the one-shot and the streaming paths open is counted into the
service's ``stats`` (``span_ns.<name>``, ``span_n.<name>``) and, under a
running profiler, lands in the trace as a bare ``repro.<name>`` event.  The
spans must never change a result, the leaves must account for the solve,
and the compiled programs keep the module names the benchmark reads.
"""
import collections
import glob
import sys
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import SAHyperParams, SSAHyperParams, gset
from repro.core.pt import PTSSAHyperParams
from repro.serve import (
    AnnealRequest,
    AnnealService,
    StreamingAnnealService,
    StreamPolicy,
)
from repro.serve import spans as sp

HP = SSAHyperParams(n_trials=3, m_shot=4, tau=4, i0_min=1, i0_max=8)
ONE_SHOT = ("solve", "group", "chunk", "compile") + sp.LEAVES
STREAM = ("quantum", "compile") + sp.QUANTUM


def _ssa_requests(n=36, k=3):
    # Request 0 reaches target at its first chunk while the others run on:
    # its result is frozen by the early-stop snap.
    return [AnnealRequest(problem=gset.toroidal_grid(n, seed=s, name=f"t{s}"),
                          hp=HP, seed=s, target_cut=1 if s == 0 else None)
            for s in range(k)]


def _counted(stats):
    return {k.split(".", 1)[1] for k, v in stats.items()
            if k.startswith("span_n.") and v > 0}


def _run_ssa():
    svc = AnnealService(backend="dense", min_bucket=16,
                        backend_opts={"field_mode": "auto"})
    svc.solve(_ssa_requests())
    svc.solve([AnnealRequest(problem=gset.toroidal_grid(36, seed=9),
                             hp="auto", seed=9)])
    assert "autotuned" not in svc.stats
    return svc.stats, set(ONE_SHOT)


def _run_sa():
    svc = AnnealService(backend="sparse", min_bucket=16)
    hp = SAHyperParams(n_trials=2, n_cycles=32, t_start=5.0, t_end=0.1)
    svc.solve([AnnealRequest(problem=gset.toroidal_grid(36, seed=s), hp=hp,
                             seed=s, target_cut=1 if s == 0 else None)
               for s in range(2)])
    # SA programs are plain jits (no recorded compile), its Metropolis core
    # has no weight-bit field modes, and no request here is autotuned.
    return svc.stats, set(ONE_SHOT) - {"compile", "autotune", "weight_bits"}


def _run_ptssa():
    svc = AnnealService(backend="dense", min_bucket=16)
    hp = PTSSAHyperParams(n_replicas=4, n_rounds=4, tau=4, n_rnd=2)
    svc.solve([AnnealRequest(problem=gset.toroidal_grid(36, seed=s), hp=hp,
                             seed=s, target_cut=1 if s == 0 else None)
               for s in range(2)])
    return svc.stats, set(ONE_SHOT) - {"compile", "autotune"}


def _run_stream():
    ss = StreamingAnnealService(backend="sparse", min_bucket=16,
                                policy=StreamPolicy(slots_per_table=2))
    for r in _ssa_requests():
        ss.submit(r)
    ss.run_until_idle()
    st = ss.stream_stats()
    assert set(st["quantum_host_ms"]) == {"launch", "sync", "retire", "seat"}
    assert all(v > 0 for v in st["quantum_host_ms"].values())
    assert ss.stats["span_n.quantum"] == ss.stats["stream_quanta"]
    return ss.stats, set(STREAM)


PATHS = {"ssa": _run_ssa, "sa": _run_sa, "ptssa": _run_ptssa,
         "stream": _run_stream}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_span_of_a_path_is_counted(path):
    stats, expected = PATHS[path]()
    assert expected <= _counted(stats)
    for name in expected:
        assert stats["span_ns." + name] > 0, name


def test_the_paths_cover_every_documented_span():
    documented = set(sp.LEAVES + sp.QUANTUM + sp.PARENTS) | {"compile"}
    assert set(ONE_SHOT) | set(STREAM) == documented


def test_concurrent_spans_lose_no_update():
    spans = sp.Spans(collections.Counter())
    n_threads, n_spans = 16, 500

    def work():
        for _ in range(n_spans):
            with spans("x"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert spans.stats["span_n.x"] == n_threads * n_spans


def test_leaf_spans_cover_the_solve():
    svc = AnnealService(backend="dense", min_bucket=16,
                        backend_opts={"field_mode": "auto"})
    reqs = _ssa_requests(n=256, k=4)
    svc.solve(reqs)                         # compiles
    before = dict(svc.stats)
    svc.solve(reqs)
    d = {k: v - before.get(k, 0) for k, v in svc.stats.items()}
    leaves = sum(d.get("span_ns." + n, 0) for n in sp.LEAVES)
    assert d["span_n.solve"] == 1
    assert leaves >= 0.9 * d["span_ns.solve"]
    assert leaves <= d["span_ns.solve"]     # leaves never overlap
    # One chunk span per chunk the loop ran.
    assert d["span_n.chunk"] == d["chunks_run"] == d["span_n.chunk.sync"]


def _host_events(trace_dir):
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(sp.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _results(responses):
    return [(np.asarray(r.result.best_cut), np.asarray(r.result.best_m),
             np.asarray(r.chunk_best_cut), r.chunks_run) for r in responses]


def test_profiler_sees_bare_span_names_inside_the_solve(tmp_path):
    svc = AnnealService(backend="dense", min_bucket=16)
    reqs = _ssa_requests()
    off = _results(svc.solve(reqs))              # solve 0, profiler off
    with jax.profiler.trace(str(tmp_path)):
        on = _results(svc.solve(reqs))           # solve 1
    for a, b in zip(off, on):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    events = _host_events(tmp_path)
    names = {name for name, *_ in events}
    assert names <= {sp.PREFIX + n for n in ONE_SHOT}
    assert {sp.PREFIX + n for n in ("solve", "group", "stack", "chunk",
                                    "chunk.sync", "finalize")} <= names
    (solve,) = [e for e in events if e[0] == "repro.solve"]
    assert int(solve[3]["solve"]) == 1
    for name, start, end, _ in events:
        assert solve[1] <= start and end <= solve[2], name
    (group,) = [e for e in events if e[0] == "repro.group"]
    assert group[3]["kind"] == "ssa"
    assert int(group[3]["bucket"]) == 64 and int(group[3]["batch"]) == 3


def test_results_are_identical_with_the_profiler_on_and_off(tmp_path):
    reqs = _ssa_requests(k=4)
    off = _results(AnnealService(backend="pallas", min_bucket=16).solve(reqs))
    with jax.profiler.trace(str(tmp_path)):
        on = _results(
            AnnealService(backend="pallas", min_bucket=16).solve(reqs))
    for a, b in zip(off, on):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_compiled_programs_keep_their_module_names():
    svc = AnnealService(backend="dense", min_bucket=16)
    svc.solve(_ssa_requests())
    heads = {p.key[-1]: p.compiled.as_text().splitlines()[0]
             for p in svc.programs()}
    assert "jit_chunk_fn" in heads["chunk"]
    assert "jit_init_fn" in heads["init"]
