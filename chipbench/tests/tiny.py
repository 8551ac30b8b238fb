"""A benchmark root at a size the CPU runs in seconds, built from new files.

:func:`make_root` writes ``BENCHMARK.json``, tiny configurations and mixes
into a fresh directory, next to copies of the repository's metric readers
and client loops, so a test can drive :func:`chipbench.harness.run_cell`
end to end without any file of the repository changing.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import calibrate, instances, reference

HERE = Path(__file__).resolve().parents[1]

HP = {"n_trials": 4, "m_shot": 6, "n_rnd": 2, "i0_min": 1, "i0_max": 8,
      "tau": 5, "beta_shift": 1}
SERVICE = {"backend": "auto", "field_mode": "auto", "noise": "xorshift",
           "storage_layout": "packed", "partition": "problem"}


def _targets(specs):
    hp = reference.HyperParams(**HP)
    out = []
    for spec in specs:
        outs = reference.solve(instances.make(spec), hp, list(range(16)),
                               [None] * 16)
        out.append(calibrate.choose_target([o.trace for o in outs],
                                           hp.m_shot)["target_cut"])
    return out


def make_root(tmp: Path) -> Path:
    """Write a tiny benchmark under ``tmp``; returns its root."""
    here = tmp / "chipbench"
    (here / "configs").mkdir(parents=True)
    (here / "traffic").mkdir()
    shutil.copytree(HERE / "metrics", here / "metrics")
    shutil.copytree(HERE / "loops", here / "loops")
    specs = [
        {"family": "toroidal", "name": "t0", "rows": 8, "cols": 12, "seed": 3},
        {"family": "toroidal", "name": "t1", "rows": 8, "cols": 12, "seed": 4},
    ]
    for spec, t in zip(specs, _targets(specs)):
        spec["target_cut"] = t
    cfg = {"name": "tiny", "instances": specs, "hyperparams": HP,
           "service": SERVICE, "check_samples": 6}
    (here / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mixes = {
        "sweep4": {"kind": "closed_batch", "list_size": 4, "pool_lists": 4,
                   "pool_seed": 3},
    }
    for name, mix in mixes.items():
        (here / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    workloads = [
        {"name": "tiny.batch", "config": "tiny", "traffic": "sweep4",
         "chips": 1, "why": "tiny closed loop"},
    ]
    configs = [{"name": "tiny", "source": "test", "why": "test",
                "file": "chipbench/configs/tiny.json", "reduced": []}]
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    bench["configs"] = configs
    bench["workloads"] = workloads
    # The repository's metrics, each pointed at the tiny cell.
    for sec in ("end_to_end", "per_layer"):
        for m in bench[sec]:
            if "workloads" in m:
                m["workloads"] = ["tiny.batch"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
