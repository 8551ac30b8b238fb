"""The g81 configuration and its cell g81.batch, on the CPU.

The instance is the rudy toroidal recipe at G81's published size, the
roofline work reads only the instance and the hyperparameters, the frozen
targets are the calibration's readings, and the ``sweep2`` mix is a frozen
pool of four lists of two seeds.
"""
from __future__ import annotations

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import calibrate, instances, peaks, traffic, work

HERE = Path(__file__).resolve().parents[1]
CFG = json.loads((HERE / "configs" / "g81.json").read_text())
MIX = json.loads((HERE / "traffic" / "sweep2.json").read_text())


def test_the_instance_is_g81_size_degree_4_plus_minus_1():
    (spec,) = CFG["instances"]
    inst = instances.make(spec)
    assert inst.n == 20000 and len(inst.edges) == 40000
    assert np.array_equal(np.bincount(inst.edges.ravel(), minlength=inst.n),
                          np.full(inst.n, 4))
    # No edge twice and no self loop on a 100x200 torus.
    pairs = {tuple(sorted(e)) for e in inst.edges.tolist()}
    assert len(pairs) == 40000 and all(i != j for i, j in pairs)
    assert set(np.unique(inst.weights).tolist()) == {-1, 1}


def test_the_work_reads_only_instance_and_hyperparameters_and_is_compute_bound():
    base = work.config_work(CFG)
    for backend in ("pallas", "dense", "sparse", "auto"):
        for field_mode in ("dense", "popcount", "auto"):
            alt = copy.deepcopy(CFG)
            alt["service"].update(backend=backend, field_mode=field_mode,
                                  partition="spin")
            assert work.config_work(alt) == base
    (w,) = base
    # 2 x 2E couplings x 16 trials x 600 cycles per lane-chunk.
    assert w["ops"] == 2 * 80000 * 16 * 600
    _, bound = work.least_seconds(w["ops"], w["bytes"],
                                  peaks.peaks_for("TPU v5 lite"), 1)
    assert bound == "compute"


def test_the_targets_carry_the_calibration_readings():
    (spec,) = CFG["instances"]
    t = CFG["targets"]
    read = t["readings"][spec["name"]]
    assert spec["target_cut"] == read["target_cut"]
    assert t["calibration_seeds"] == "0..63"
    assert t["device"] == "TPU v5 lite"
    m_shot = CFG["hyperparams"]["m_shot"]
    assert read["reach_share"] >= calibrate.REACH_SHARE
    assert read["median_shots"] <= max(1.0, calibrate.MEDIAN_SHARE * m_shot)
    assert read["final_min"] <= read["final_median"]
    assert read["target_cut"] <= read["final_median"]


def test_sweep2_is_four_lists_of_two_seeds_from_pool_seed_81():
    assert (MIX["kind"], MIX["list_size"], MIX["pool_lists"],
            MIX["pool_seed"]) == ("closed_batch", 2, 4, 81)
    target = CFG["instances"][0]["target_cut"]
    pool = traffic.closed_pool(MIX, [target])
    seeds = np.random.default_rng([81, 0]).integers(
        traffic.SEED_LO, traffic.SEED_HI, size=8)
    assert [len(lst) for lst in pool] == [2, 2, 2, 2]
    flat = [r for lst in pool for r in lst]
    assert [r.seed for r in flat] == seeds.tolist()
    assert all(r.instance == 0 and r.target == target for r in flat)


@pytest.mark.parametrize("metric", ["solve_rate", "lane_occupancy.batch",
                                    "cycles_to_target", "chunk_roofline",
                                    "idle_share.batch"])
def test_the_cell_is_one_chip_and_reports_the_batch_metrics(metric):
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["g81.batch"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "g81", "sweep2", 1)
    (entry,) = [c for c in bench["configs"] if c["name"] == "g81"]
    assert entry["file"] == "chipbench/configs/g81.json"
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    metrics = {m["name"]: m for sec in ("end_to_end", "per_layer")
               for m in bench[sec]}
    assert metrics[metric]["workloads"][-1] == "g81.batch"
