"""CPU tests of the benchmark's yardstick: traffic, score, peaks, work, trace."""
from __future__ import annotations

import copy
import json
from pathlib import Path

import pytest

from chipbench import peaks, score, traffic, work
from chipbench import trace as trace_mod

HERE = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "gset800_trace.json.gz"
TARGETS = [500, 510, 520]


def _lists(mix, seed, k):
    it = traffic.closed_lists(mix, TARGETS, seed)
    return [next(it) for _ in range(k)]


def test_closed_traffic_is_one_pool_in_orders_from_the_seed():
    mix = {"kind": "closed_batch", "list_size": 5, "pool_lists": 4,
           "pool_seed": 1}
    a, b = _lists(mix, 2**31 + 7, 8), _lists(mix, 2**31 + 7, 8)
    c = _lists(mix, 99, 8)
    assert a == b
    assert a != c
    pool = traffic.closed_pool(mix, TARGETS)
    # Each round sends every list of the pool once.
    for lists in (a, c):
        for r in (lists[:4], lists[4:]):
            assert sorted(l[0].index for l in r) == [0, 5, 10, 15]
            assert all(l in pool for l in r)
    flat = [r for l in pool for r in l]
    assert [r.instance for r in flat] == [i % 3 for i in range(20)]
    assert all(r.target == TARGETS[r.instance] for r in flat)
    assert all(traffic.SEED_LO <= r.seed < traffic.SEED_HI for r in flat)


def test_warmup_is_the_same_in_every_run():
    mix = {"kind": "closed_batch", "list_size": 4}
    warm = traffic.warmup_list(mix, TARGETS)
    assert warm == traffic.warmup_list(mix, TARGETS)
    assert all(r.seed < traffic.SEED_LO for r in warm)
    # The cell's list size and instances, stopped by the first chunk.
    assert [r.instance for r in warm] == [0, 1, 2, 0]
    assert all(r.target == traffic.WARMUP_TARGET for r in warm)


def _rec(ok=True, reached=True):
    return {"ok": ok, "reached": reached}


def test_solve_rate_counts_only_solved_requests_over_the_whole_window():
    recs = [_rec(), _rec(), _rec(reached=False), _rec(ok=False, reached=False)]
    assert score.solve_rate(recs, 4.0) == pytest.approx(0.5)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["gset800", "k2000"])
def test_roofline_work_depends_only_on_instance_and_hyperparameters(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    base = work.config_work(cfg)
    assert all(w["ops"] > 0 and w["bytes"] > 0 for w in base)
    for backend in ("pallas", "dense", "sparse", "auto"):
        for field_mode in ("dense", "popcount", "auto"):
            for partition in ("problem", "spin"):
                alt = copy.deepcopy(cfg)
                alt["service"].update(backend=backend, field_mode=field_mode,
                                      partition=partition)
                assert work.config_work(alt) == base


@pytest.mark.parametrize("name", ["gset800", "k2000"])
def test_both_configurations_are_compute_bound(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    for w in work.config_work(cfg):
        _, bound = work.least_seconds(w["ops"], w["bytes"],
                                      peaks.peaks_for("TPU v5 lite"), 1)
        assert bound == "compute"


def test_roofline_work_counts_couplings_trials_and_cycles():
    from chipbench import instances, reference

    inst = instances.toroidal("t", 4, 5, 1)          # 40 edges
    hp = reference.HyperParams(n_trials=2, m_shot=3, n_rnd=2, i0_min=1,
                               i0_max=4, tau=10, beta_shift=1)
    w = work.lane_chunk_work(inst, hp)
    assert w["ops"] == 2 * 80 * 2 * 30
    # 80 one-bit couplings; 2 trials x 20 spins x (2 spin bits + 3 Itanh
    # bits), read and written.
    assert w["bytes"] == 80 / 8 + 2 * 2 * 20 * 5 / 8
    t, bound = work.least_seconds(w["ops"], w["bytes"],
                                  peaks.peaks_for("TPU v5 lite"), 1)
    # So few couplings per spin that the state bytes bound it.
    assert bound == "memory" and t == w["bytes"] / 819e9


def _synthetic_trace():
    ops = [["fusion.1", 100, 50], ["fusion.2", 120, 60],   # overlap: 100..180
           ["copy.3", 200, 40], ["fusion.1", 220, 10],
           ["fusion.4", 300, 20]]
    mods = [["jit_chunk_fn(7)", 100, 140], ["jit_other(2)", 300, 20]]
    host = [["chipbench.window", 50, 400], ["chipbench.solve", 60, 300],
            ["chipbench.requests", 240, 50], ["other", 0, 10]]
    return {"planes": {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
        "/device:TPU:1": {"XLA Ops": [["fusion.9", 100, 100]],
                          "XLA Modules": []},
        "/host:CPU": {"python": host},
    }}


def test_trace_reduction_busy_idle_ops_and_gaps():
    red = trace_mod.reduce(_synthetic_trace(), n_devices=1)
    assert red["window_s"] == pytest.approx(400e-9)
    # Busy: 100..180 and 200..240 and 300..320 = 80 + 40 + 20.
    assert red["busy_s"] == pytest.approx(140e-9)
    assert red["idle_s"] == pytest.approx(260e-9)
    assert red["op_s"]["fusion.1"] == pytest.approx(60e-9)
    assert sum(red["op_s"].values()) == pytest.approx(180e-9)
    assert red["module_s"]["jit_chunk_fn(7)"] == pytest.approx(140e-9)
    # Gaps go whole to the innermost span open at their midpoint:
    # 50..100 and 180..200 to solve, 240..300 to requests, 320..450 to none.
    idle = red["idle_by_span_s"]
    assert idle["chipbench.solve"] == pytest.approx(70e-9)
    assert idle["chipbench.requests"] == pytest.approx(60e-9)
    assert idle["host: none"] == pytest.approx(130e-9)
    assert sum(idle.values()) == pytest.approx(red["idle_s"])
    both = trace_mod.reduce(_synthetic_trace(), n_devices=2)
    assert both["busy_s"] == pytest.approx((140e-9 + 100e-9) / 2)


def test_interval_helpers():
    assert trace_mod.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace_mod.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace_mod.total([(0, 2), (3, 5)]) == 4


def test_recorded_chip_trace_sums():
    """1.5 s of a gset800.batch window traced on one TPU v5e (trimmed)."""
    tr = trace_mod.load(str(FIXTURE))
    red = trace_mod.reduce(tr, n_devices=1)
    ops = trace_mod._line(tr["planes"][red["devices"][0]], "XLA Ops")
    lo = min(s for n, s, e in trace_mod.host_spans(tr)
             if n == trace_mod.WINDOW_SPAN)
    assert red["devices"] == ["/device:TPU:0"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"])
    assert sum(red["idle_by_span_s"].values()) == pytest.approx(red["idle_s"])
    # Per-op sums cover at least the busy time (ops may overlap) and no more
    # than the ops recorded.
    assert sum(red["op_s"].values()) >= red["busy_s"] * (1 - 1e-9)
    assert sum(red["op_s"].values()) <= sum(d for _, s, d in ops
                                            if s + d > lo) / 1e9 + 1e-9
    assert any("chunk_fn" in m for m in red["module_s"])


def _reader(name):
    from chipbench import harness

    return harness.metric_reader(HERE, name)


class _Resp:
    def __init__(self, chunks):
        self.chunks_run, self.result = chunks, object()


def _ctx():
    from chipbench import instances, reference

    hp = reference.HyperParams(n_trials=2, m_shot=3, n_rnd=2, i0_min=1,
                               i0_max=4, tau=10, beta_shift=1)
    red = trace_mod.reduce(_synthetic_trace(), n_devices=1)
    recs = [{"resp": _Resp(c), "chunks": c, "ok": True, "reached": c < 3}
            for c in (1, 3)]
    full = {"records": recs, "hp": hp, "trace": red, "chips": 1,
            "window_s": 4.0, "setup_s": 12.5,
            "instances": [instances.toroidal("t", 4, 5, 1)],
            "chunk_module": "chunk_fn",
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "counters": {"slot_chunks": 8, "live_lane_chunks": 2}}
    empty = dict(full, records=[], trace=None, counters={})
    return full, empty


EXPECT = {
    "solve_rate": 1 / 4.0,
    "setup_s": 12.5,
    "lane_occupancy.batch": 25.0,
    "cycles_to_target": 2 * 30.0,
    "idle_share.batch": 100 * 260 / 400,
    # Two live lane-chunks of 60 state bytes against 140 ns of chunk module.
    "chunk_roofline": 100 * (2 * 60 / 819e9) / 140e-9,
}


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    names = {m["name"] for sec in ("end_to_end", "per_layer")
             for m in bench[sec]}
    assert names == set(EXPECT)
    assert {p.stem for p in (HERE / "metrics").glob("*.py")} == names


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_a_metric_reader_reads_its_number(name):
    full, empty = _ctx()
    assert _reader(name)(full) == pytest.approx(EXPECT[name])
    if name != "setup_s":
        assert _reader(name)(empty) is None


def test_chunk_roofline_says_which_roof_binds():
    from chipbench import harness

    mod = harness.load_module(HERE, "metrics", "chunk_roofline")
    full, empty = _ctx()
    assert mod.note(full) == "memory-bound"
    assert mod.note(empty) is None
