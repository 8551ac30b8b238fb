"""CPU tests that drive whole runs of the harness at a tiny size.

The look for a chip is skipped (``require_tpu=False``); everything else is
a whole run: a cell found by name from new files, its set-up,
window, the comparison with the reference and the result line.  Faults are
planted in the program underneath and must turn ``correct`` false.
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, run
from chipbench.tests import tiny

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=2**31 + 11, seconds=1.0, **kw):
    return harness.run_cell(cell, seed, seconds, False, root=root,
                            here=root / "chipbench", require_tpu=False, **kw)


def _correct(res):
    return res["correct"] and res["check"]["mismatched"]["value"] == 0


def test_a_cell_is_found_from_new_files_and_runs_correct(root):
    res = _run(root, "tiny.batch")
    assert _correct(res)
    assert set(res["metrics"]) == {"solve_rate", "setup_s"}
    assert res["metrics"]["solve_rate"]["value"] > 0
    assert res["window"]["compiles_in_window"] == 0
    assert res["attempted"] >= 4 and res["attempted"] % 4 == 0
    assert list(res)[-1] == "check"


def test_the_warmup_runs_one_chunk_and_the_window_compiles_nothing(root):
    res = _run(root, "tiny.batch")
    calls = res["window"]["calls_s"]
    assert len(calls) == res["attempted"] // 4
    assert sum(calls) <= res["window"]["seconds"]
    assert res["window"]["compiles_in_window"] == 0


def test_a_loop_kind_is_found_from_its_own_file(root, tmp_path):
    import shutil

    alt = tmp_path / "alt"
    shutil.copytree(root, alt, ignore=shutil.ignore_patterns(".jax_cache"))
    (alt / "chipbench" / "loops" / "twice.py").write_text(
        "from chipbench.loops import closed_batch\n"
        "def run(svc, problems, hp, mix, targets, seed, seconds, win):\n"
        "    out = closed_batch.run(svc, problems, hp,\n"
        "                           dict(mix, kind='closed_batch'),\n"
        "                           targets, seed, seconds, win)\n"
        "    out['records'] = out['records'] * 2\n"
        "    return out\n")
    mix = alt / "chipbench" / "traffic" / "sweep4.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()), kind="twice")))
    res = _run(alt, "tiny.batch")
    assert _correct(res)
    assert res["attempted"] == 8 * len(res["window"]["calls_s"])


@pytest.mark.parametrize("variant", ["shared_noise"])
def test_control_comes_out_not_correct(root, variant):
    res = _run(root, "tiny.batch", control=variant)
    assert not res["correct"]
    assert res["check"]["mismatched"]["value"] == res["check"]["compared"]["value"]


def test_the_cli_refuses_a_machine_without_a_tpu(capsys):
    rc = run.main(["--workload", "gset800.batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "tpu" in out.err.lower()


def test_a_checkout_without_the_program_prints_no_result(tmp_path):
    import shutil

    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        (REPO / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gset800.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


# ---------------------------------------------------------------------------
# Faults planted underneath the timed path
# ---------------------------------------------------------------------------
def _wide_sample(root, tmp_path):
    """The tiny root with every answer of the window compared."""
    cfg_path = root / "chipbench" / "configs" / "tiny.json"
    alt = tmp_path / "wide"
    if not alt.exists():
        import shutil

        shutil.copytree(root, alt, ignore=shutil.ignore_patterns(".jax_cache"))
        cfg = json.loads(cfg_path.read_text())
        cfg["check_samples"] = 64
        (alt / "chipbench" / "configs" / "tiny.json").write_text(
            json.dumps(cfg))
    return alt


def _state_unchanged(monkeypatch):
    from repro.core import engine

    for cls in (engine.BatchedBackend, engine.BatchedPallasBackend):
        monkeypatch.setattr(cls, "run_shots",
                            lambda self, problem, state, plateaus, n: state)


def _half_batch_left_out(monkeypatch):
    from repro.serve import AnnealService

    orig = AnnealService.solve

    def solve(self, requests, progress=None):
        half = max(1, len(requests) // 2)
        done = orig(self, requests[:half], progress)
        rest = [copy.copy(done[i % half]) for i in range(len(requests) - half)]
        for r, req in zip(rest, requests[half:]):
            r.request = req
        return done + rest

    monkeypatch.setattr(AnnealService, "solve", solve)


def _answer_altered(monkeypatch):
    from repro.serve import AnnealService

    orig = AnnealService.solve

    def solve(self, requests, progress=None):
        out = orig(self, requests, progress)
        for r in out:
            bm = np.array(r.result.best_m)
            bm[0, 0] = -bm[0, 0]
            r.result.best_m = bm
        return out

    monkeypatch.setattr(AnnealService, "solve", solve)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_comes_out_not_correct(root, tmp_path,
                                                         monkeypatch, fault):
    wide = _wide_sample(root, tmp_path)
    FAULTS[fault](monkeypatch)
    res = _run(wide, "tiny.batch", seconds=0.5)
    assert not res["correct"]
    assert res["check"]["mismatched"]["value"] > 0
