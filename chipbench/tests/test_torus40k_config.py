"""The torus40k configuration and its cell torus40k.spin4, on the CPU.

In process: the instance is the rudy toroidal recipe at 200x200, the
roofline work reads only the instance and the hyperparameters, the frozen
targets are the calibration's readings, and the cell is a four-chip cell
on ``sweep2``.

In one subprocess with four forced host devices: a service built as the
harness builds it from the configuration's ``service`` section shards the
spin axis over all four, answers a small rudy torus bit for bit as the
plain reference and a ``partition='problem'`` service do, puts each
device's rows of the planes on that device alone, and counts its chunks,
shards and gathered bytes by their formula.
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from chipbench import calibrate, instances, peaks, traffic, work

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
CFG = json.loads((HERE / "configs" / "torus40k.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "torus40k.spin4"
METRICS = ["solve_rate", "lane_occupancy.batch", "cycles_to_target",
           "chunk_roofline", "idle_share.batch"]


# ---------------------------------------------------------------------------
# The configuration and the cell
# ---------------------------------------------------------------------------
def test_the_instance_is_a_200x200_degree_4_plus_minus_1_torus():
    from repro.core.engine import MAX_UNSHARDED_SPINS, bucket_n

    (spec,) = CFG["instances"]
    inst = instances.make(spec)
    assert inst.n == 40000 and len(inst.edges) == 80000
    assert np.array_equal(np.bincount(inst.edges.ravel(), minlength=inst.n),
                          np.full(inst.n, 4))
    pairs = {tuple(sorted(e)) for e in inst.edges.tolist()}
    assert len(pairs) == 80000 and all(i != j for i, j in pairs)
    assert set(np.unique(inst.weights).tolist()) == {-1, 1}
    # Above the one-chip ceiling, so admitted only spin-sharded: 16384
    # rows per chip of four.
    assert inst.n > MAX_UNSHARDED_SPINS
    assert bucket_n(inst.n, 64) == 65536


def test_the_work_reads_only_instance_and_hyperparameters_and_is_compute_bound():
    base = work.config_work(CFG)
    for backend in ("pallas", "dense", "sparse", "auto"):
        for field_mode in ("dense", "popcount", "auto"):
            for partition in ("problem", "spin", "auto"):
                alt = copy.deepcopy(CFG)
                alt["service"].update(backend=backend, field_mode=field_mode,
                                      partition=partition)
                assert work.config_work(alt) == base
    (w,) = base
    # 2 x 2E couplings x 16 trials x 600 cycles per lane-chunk.
    assert w["ops"] == 2 * 160000 * 16 * 600
    pk = peaks.peaks_for("TPU v5 lite")
    t4, bound = work.least_seconds(w["ops"], w["bytes"], pk, chips=4)
    assert bound == "compute"
    assert t4 == pytest.approx(work.least_seconds(w["ops"], w["bytes"], pk,
                                                  chips=1)[0] / 4)


def test_the_targets_carry_the_calibration_readings():
    (spec,) = CFG["instances"]
    t = CFG["targets"]
    read = t["readings"][spec["name"]]
    assert spec["target_cut"] == read["target_cut"] > 0
    assert t["calibration_seeds"] == "0..63"
    assert t["device"] == "TPU v5 lite"
    m_shot = CFG["hyperparams"]["m_shot"]
    assert read["reach_share"] >= calibrate.REACH_SHARE
    # With m_shot 2 the median seed reaches the target in its first shot.
    assert read["median_shots"] <= max(1.0, calibrate.MEDIAN_SHARE * m_shot)
    assert read["median_shots"] == 1.0
    assert read["final_min"] <= read["final_median"]
    assert read["target_cut"] <= read["final_median"]


def test_the_service_section_is_spin_partitioned_on_the_harness_path():
    assert CFG["service"] == {"backend": "auto", "field_mode": "auto",
                              "noise": "xorshift", "storage_layout": "packed",
                              "partition": "spin"}
    assert CFG["hyperparams"] == {"n_trials": 16, "m_shot": 2, "n_rnd": 2,
                                  "i0_min": 1, "i0_max": 32, "tau": 100,
                                  "beta_shift": 1}
    assert set(CFG["reduced"]) == {"n_trials", "m_shot"}


def test_sweep2_pools_two_seed_lists_on_the_one_instance():
    mix = json.loads((HERE / "traffic" / "sweep2.json").read_text())
    target = CFG["instances"][0]["target_cut"]
    pool = traffic.closed_pool(mix, [target])
    assert [len(lst) for lst in pool] == [2, 2, 2, 2]
    assert all(r.instance == 0 and r.target == target
               for lst in pool for r in lst)


def test_the_cell_is_four_chips_on_sweep2():
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "torus40k", "sweep2", 4)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "torus40k"]
    assert entry["file"] == "chipbench/configs/torus40k.json"
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_reports_the_batch_metrics_before_g81(metric):
    metrics = {m["name"]: m for sec in ("end_to_end", "per_layer")
               for m in BENCH[sec]}
    cells = metrics[metric]["workloads"]
    assert cells[-2:] == [CELL, "g81.batch"]


# ---------------------------------------------------------------------------
# Four forced host devices: the harness's service against the reference
# ---------------------------------------------------------------------------
MULTIDEV = textwrap.dedent(
    r"""
    import json, sys
    import numpy as np
    import jax
    assert len(jax.devices()) == 4, jax.devices()

    from chipbench import harness, instances, reference
    from repro.core import SSAHyperParams, distributed
    from repro.core.engine import (_stack_packed_models, bucket_n,
                                   make_batched_backend)
    from repro.serve import AnnealRequest, AnnealService

    cfg = json.loads(sys.argv[1])
    out = {}

    svc = harness.build_service(cfg["service"])
    out["mesh"] = [list(svc.mesh.axis_names), int(svc.mesh.devices.size)]

    inst = instances.toroidal("T16", 16, 16, 40000)
    hp = reference.HyperParams(n_trials=4, m_shot=3, n_rnd=2, i0_min=1,
                               i0_max=8, tau=5, beta_shift=1)
    hp_prog = SSAHyperParams(**vars(hp))
    problem = instances.to_program(inst)
    seeds = [2**40 + 3, 2**33 + 9]
    free = reference.solve(inst, hp, seeds, [None, None])
    # Lane 0 stops at its first chunk and lane 1 runs all three.
    stop = [free[0].trace[0], None]

    def solve(svc, targets):
        return svc.solve([AnnealRequest(problem=problem, hp=hp_prog, seed=s,
                                        target_cut=t)
                          for s, t in zip(seeds, targets)])

    def answers(resps):
        return [[r.chunks_run, [int(v) for v in r.chunk_best_cut],
                 np.asarray(r.result.best_cut, np.int64).tolist(),
                 np.asarray(r.result.best_m, np.int8).tolist(), r.backend]
                for r in resps]

    match, spin, prob = {}, {}, {}
    for name, targets in (("free", [None, None]), ("stop", stop)):
        want = reference.solve(inst, hp, seeds, targets)
        resps = solve(svc, targets)
        match[name] = [bool(harness._matches(r, o))
                       for r, o in zip(resps, want)]
        spin[name] = answers(resps)
        plain = AnnealService(backend="auto", noise="xorshift",
                              storage_layout="packed",
                              backend_opts={"field_mode": "auto"},
                              partition="problem")
        prob[name] = answers(solve(plain, targets))
    out["match"] = match
    out["same_as_problem"] = {k: [a[:4] == b[:4] for a, b in
                                  zip(spin[k], prob[k])] for k in spin}
    # A spin group's responses name the backend its shards' field follows,
    # the one the same bucket routes to unsharded.
    out["backends"] = [sorted({a[4] for k in d for a in d[k]})
                       for d in (spin, prob)]
    out["early"] = [a[0] for a in spin["stop"]]

    # The counters of the two solves: every chunk a 4-shard program that
    # gathers (cycles + eligible plateaus) x B x T x N / 8 bytes.
    st = svc.stats
    out["counters"] = {k: int(st[k]) for k in (
        "spin_chunks", "spin_shard_chunks", "spin_gather_bytes",
        "chunks_run")}
    out["counters"]["route_lanes"] = int(
        sum(v for k, v in st.items() if k.startswith("route_lanes.")))

    # Row-tiled shards, as at 65536 spins over four chips: the same answers.
    distributed.TILED_J_THRESHOLD = 32
    tiled = AnnealService(backend="auto", noise="xorshift",
                          storage_layout="packed",
                          backend_opts={"field_mode": "auto", "tile_n": 16},
                          partition="spin")
    want = reference.solve(inst, hp, seeds, stop)
    resps = solve(tiled, stop)
    out["tiled"] = {"tiled_lanes": int(tiled.stats["tiled_lanes"]),
                    "match": [bool(harness._matches(r, o))
                              for r, o in zip(resps, want)]}

    # Each device's row block of the planes lands on that device alone,
    # from host arrays.
    model = problem.to_ising()
    nb = bucket_n(model.n, 64)
    host = _stack_packed_models([model, model], nb, 1)
    out["host"] = sorted({type(v).__name__ for v in host.values()})
    bk = make_batched_backend("dense", n_bucket=nb, n_trials=4,
                              noise="xorshift", storage_layout="packed",
                              partition="spin", mesh=svc.mesh,
                              field_mode="popcount")
    stacked = bk.stack([model, model])
    rows = {"h": 1, "sign": 1, "mags": 2, "base": 1}
    shards = {}
    for k, ax in rows.items():
        arr = stacked[k]
        got = []
        for s in arr.addressable_shards:
            lo, hi, _ = s.index[ax].indices(arr.shape[ax])
            got.append([int(s.device.id), lo, hi, int(s.data.shape[ax])])
            np.testing.assert_array_equal(np.asarray(s.data), host[k][s.index])
        shards[k] = sorted(got)
    out["shards"] = shards
    out["n_bucket"] = nb
    print("RESULT " + json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def multidev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), str(REPO), env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV, json.dumps(CFG)], env=env,
        capture_output=True, text=True, timeout=900, cwd=REPO)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, (
        proc.stdout[-3000:] + "\n" + proc.stderr[-3000:])
    return json.loads(lines[-1][len("RESULT "):])


def test_spin_partition_without_a_mesh_takes_every_device(multidev):
    assert multidev["mesh"] == [["model"], 4]


@pytest.mark.parametrize("case", ["free", "stop"])
def test_sharded_answers_equal_the_reference(multidev, case):
    assert multidev["match"][case] == [True, True]
    spin, problem = multidev["backends"]
    assert len(spin) == 1 and spin == problem


def test_early_stop_freezes_one_lane_at_its_first_chunk(multidev):
    assert multidev["early"] == [1, 3]


@pytest.mark.parametrize("case", ["free", "stop"])
def test_sharded_answers_equal_the_problem_partition(multidev, case):
    assert multidev["same_as_problem"][case] == [True, True]


def test_row_tiled_shards_equal_the_reference(multidev):
    assert multidev["tiled"] == {"tiled_lanes": 2, "match": [True, True]}


def test_each_device_holds_only_its_rows_of_the_planes(multidev):
    nb = multidev["n_bucket"]
    assert nb == 256
    assert multidev["host"] == ["ndarray"]
    for k, got in multidev["shards"].items():
        assert [d for d, *_ in got] == [0, 1, 2, 3], k
        assert [(lo, hi, n) for _, lo, hi, n in got] == [
            (i * nb // 4, (i + 1) * nb // 4, nb // 4) for i in range(4)], k


def test_spin_counters_equal_their_formula(multidev):
    c = multidev["counters"]
    # Two solves: 1 + 3 chunks (lane 1 runs on after lane 0 stops) and 3.
    assert c["spin_chunks"] == c["chunks_run"] == 6
    assert c["spin_shard_chunks"] == 4 * c["spin_chunks"]
    cycles = 4 * 5          # plateaus 1, 2, 4, 8, five cycles each
    gathers = cycles + 1    # the i0max plateau's epilogue fold
    B, T, N = 2, 4, 256
    assert c["spin_gather_bytes"] == c["spin_chunks"] * gathers * B * T * N // 8
    assert c["route_lanes"] == 4
