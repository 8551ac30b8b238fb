"""Roofline analysis: dry-run table + the XNOR-popcount datapath gate.

Two entry points share this module:

* :func:`run` — the original table over experiments/dryrun/*.json artifacts
  (written by ``repro.launch.dryrun``): compute/memory/collective terms per
  (arch x shape x mesh) and the dominant bound.

* :func:`run_popcount` — the PR-7 perf gate.  For each instance class it
  measures steady-state spin-cycles/s of the dense backend under
  ``field_mode='popcount'`` vs ``field_mode='dense'`` (same backend, same
  bit-identical results — only the contraction arithmetic differs), plus the
  analytic bytes-moved-per-spin-update model that explains the gap: the f32
  matmul streams 4N bytes of J per spin update, the XNOR-popcount path
  streams (1 + n_bits) x N/8 bytes of sign/magnitude bitplanes — a 32x/
  (1+n_bits) traffic reduction, which is the whole point of making the
  packed bitplanes the *arithmetic* format.  Results land in
  ``BENCH_popcount.json``; ``--gate`` enforces

      * K2000-class (dense instance) popcount speedup >= GATE_K2000_MIN, and
      * no instance below GATE_FLOOR x dense (the >15% regression rule)

  Steady-state means: backend constructed once, the plateau chain jitted
  once, timing the warm calls — pack/compile are one-time costs and are
  excluded, exactly as in benchmarks.timing.  The gate instance uses a
  small trial count (Table-II-style): dense-J streaming amortizes over
  trials, so large batches flatter the matmul and would hide the datapath
  difference the FPGA cares about.
"""
from __future__ import annotations

import glob
import json
import os

import jax

from repro.core import gset
from repro.core.engine import schedule_plateaus, single_problem_backend
from repro.core.ssa import SSAHyperParams
from repro.kernels.bitplane import adjacency_weight_bits

from .common import emit, time_call

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")

# Popcount-vs-dense gate thresholds (--gate).
GATE_K2000_MIN = 2.0   # required speedup on the dense (K2000-class) instance
GATE_FLOOR = 0.85      # no instance may regress spin-cycles/s by >15%

# (factory, hp) per instance class.  K2000 is the gate instance; G11 is the
# sparse torus; G81-class exercises the tiled regime (N > TILED_J_THRESHOLD:
# tiled-J slabs vs row-tiled popcount).  Smoke shrinks every class below the
# tile threshold so a CI cell finishes in seconds.
FULL_SPECS = {
    "G11": (lambda: gset.toroidal_grid(800, seed=11, name="G11"),
            SSAHyperParams(n_trials=4, m_shot=1, tau=30, i0_max=8)),
    "K2000": (lambda: gset.complete_graph(2000, seed=2000),
              SSAHyperParams(n_trials=4, m_shot=1, tau=30, i0_max=8)),
    "G81-class": (lambda: gset.toroidal_grid(6400, seed=81),
                  SSAHyperParams(n_trials=2, m_shot=1, tau=4, i0_max=2)),
}
SMOKE_SPECS = {
    "G11": (lambda: gset.toroidal_grid(256, seed=11),
            SSAHyperParams(n_trials=4, m_shot=1, tau=4, i0_max=4)),
    "K2000": (lambda: gset.complete_graph(256, seed=2000),
              SSAHyperParams(n_trials=4, m_shot=1, tau=4, i0_max=4)),
    "G81-class": (lambda: gset.toroidal_grid(576, seed=81),
                  SSAHyperParams(n_trials=2, m_shot=1, tau=4, i0_max=2)),
}


def load_records(dryrun_dir: str = DRYRUN_DIR):
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def run(csv_prefix: str = "roofline", dryrun_dir: str = DRYRUN_DIR):
    recs = load_records(dryrun_dir)
    if not recs:
        emit(f"{csv_prefix}/missing", 0.0,
             "run `python -m repro.launch.dryrun --all` first")
        return []
    for r in recs:
        name = f"{csv_prefix}/{r['arch']}/{r['shape']}/{r['mesh']}"
        if r.get("rules", "baseline") != "baseline":
            name += f"/{r['rules']}"
        if r["status"] == "skipped":
            emit(name, 0.0, f"SKIP:{r['reason'][:60]}")
            continue
        if r["status"] != "ok":
            emit(name, 0.0, f"ERROR:{r.get('error', '?')[:60]}")
            continue
        if "t_compute_s" not in r:
            emit(name, 0.0, f"compiled_only;peak_GB={r['peak_bytes_per_device']/1e9:.2f}")
            continue
        bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        frac = r["t_compute_s"] / bound if bound else 0.0
        emit(
            name,
            bound * 1e6,
            f"dom={r['dominant']};tc_ms={r['t_compute_s']*1e3:.2f};"
            f"tm_ms={r['t_memory_s']*1e3:.2f};tx_ms={r['t_collective_s']*1e3:.2f};"
            f"roofline_frac={frac:.3f};useful={r['useful_flops_ratio'] or 0:.3f};"
            f"peak_GB={r['peak_bytes_per_device']/1e9:.2f};"
            f"fits16G={r['fits_hbm_16g']}",
        )
    return recs


def _steady_spin_cycles_per_s(model, hp, field_mode: str) -> tuple:
    """(spin-cycles/s, measured J-residency bytes, wall us) at steady state."""
    plateaus = schedule_plateaus(hp.schedule("hassa"))
    cycles = sum(p.length for p in plateaus)
    bk, problem = single_problem_backend(
        model, "dense", n_trials=hp.n_trials, n_rnd=hp.n_rnd,
        noise="xorshift", field_mode=field_mode,
    )
    # What stays resident: bitplanes, dense J, or (tiled) the adjacency.
    j_bytes = int(sum(a.nbytes for k, a in problem.items() if k != "h"))
    state = bk.init_state(problem, bk.init_noise([0], [model.n]))
    chain = jax.jit(lambda s: bk.run_shots(problem, s, plateaus, 1))
    us = time_call(chain, state, warmup=1, iters=3)
    return cycles * hp.n_trials * model.n / (us * 1e-6), j_bytes, us


def run_popcount(
    smoke: bool = False,
    json_path: str = "BENCH_popcount.json",
    gate: bool = False,
    csv_prefix: str = "popcount",
):
    """Popcount-vs-dense spin-cycles/s bench; returns (report, failures)."""
    specs = SMOKE_SPECS if smoke else FULL_SPECS
    rows, failures = [], []
    for name, (factory, hp) in specs.items():
        model = factory().to_ising()
        dense_scs, dense_j, _ = _steady_spin_cycles_per_s(model, hp, "dense")
        pc_scs, pc_j, pc_us = _steady_spin_cycles_per_s(model, hp, "popcount")
        speedup = pc_scs / dense_scs
        # Analytic bytes-moved per spin update (the roofline model): the
        # matmul reads one f32 row of J, the popcount path one sign word
        # row + n_bits magnitude rows, 1 bit per coupling each.
        jb = adjacency_weight_bits(model.n, model.nbr_idx, model.nbr_w)
        bytes_dense = 4.0 * model.n
        bytes_pc = (1 + jb) * model.n / 8.0
        row = {
            "instance": name,
            "n": int(model.n),
            "n_trials": hp.n_trials,
            "cycles": int(sum(p.length
                              for p in schedule_plateaus(hp.schedule("hassa")))),
            "j_bits": int(jb),
            "dense_spin_cycles_per_s": dense_scs,
            "popcount_spin_cycles_per_s": pc_scs,
            "speedup": speedup,
            "j_bytes_dense": dense_j,
            "j_bytes_packed": pc_j,
            "model_bytes_per_spin_update_dense": bytes_dense,
            "model_bytes_per_spin_update_popcount": bytes_pc,
            "model_traffic_ratio": bytes_dense / bytes_pc,
        }
        rows.append(row)
        emit(
            f"{csv_prefix}/{name}/n{model.n}",
            pc_us,
            f"speedup={speedup:.2f};dense_scs={dense_scs:.3e};"
            f"pc_scs={pc_scs:.3e};traffic_ratio={bytes_dense/bytes_pc:.1f};"
            f"j_bytes={dense_j}->{pc_j}",
        )
        if gate and speedup < GATE_FLOOR:
            failures.append(
                f"{name}: popcount {speedup:.2f}x dense "
                f"(< {GATE_FLOOR}x regression floor)"
            )
    if gate and not smoke:
        k2000 = next(r for r in rows if r["instance"] == "K2000")
        if k2000["speedup"] < GATE_K2000_MIN:
            failures.append(
                f"K2000: popcount speedup {k2000['speedup']:.2f}x "
                f"< required {GATE_K2000_MIN}x"
            )
    report = {
        "smoke": smoke,
        "gate": {"k2000_min": GATE_K2000_MIN, "floor": GATE_FLOOR,
                 "enforced": gate, "failures": failures},
        "instances": rows,
    }
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    emit(f"{csv_prefix}/gate", 0.0,
         "PASS" if not failures else ";".join(failures))
    return report, failures


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced instance sizes (CI smoke cell)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 if the popcount speedup gate fails")
    ap.add_argument("--json", default="BENCH_popcount.json")
    ap.add_argument("--dryrun-table", action="store_true",
                    help="emit the dry-run artifact roofline table instead")
    args = ap.parse_args()
    if args.dryrun_table:
        run()
        sys.exit(0)
    _, failures = run_popcount(smoke=args.smoke, json_path=args.json,
                               gate=args.gate)
    if failures:
        print("GATE FAILURES:")
        for f in failures:
            print("  -", f)
        sys.exit(1)
