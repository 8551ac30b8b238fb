"""Algorithm-family comparisons on dense instances.

Two entry points share this module:

* :func:`run` — paper Table VII: HA-SSA vs parallel tempering (IPAPT-class
  baseline) at matched cycle budgets.  The paper: IPAPT reaches best-known
  G11 with avg 561 in 2.64 ms; HA-SSA reaches best-known with avg 558 in
  1.00 ms (2.64x faster).

* :func:`run_ssqa` — the PR-10 gate: SSQA vs SSA *time-to-target* on the
  K2000-class dense instance (DESIGN.md §13).  Both families get their
  hyper-parameters from the same autotuner (:mod:`repro.core.autotune` —
  SSQA additionally gets its Trotter depth and J⊥ ramp from the local-field
  σ), run at equal ``n_trials`` × ``total_cycles`` on the same dense
  backend with the same noise generator, so the comparison is compute-fair:
  the replica ring is the only difference.  Per seed, the target cut is
  ``TARGET_FRAC`` × the weaker family's final best (a self-normalizing
  time-to-quality bar); cycles-to-target comes from the deterministic
  per-cycle energy trace and is converted to wall time with each family's
  measured steady-state seconds/cycle.  Results land in ``BENCH_ssqa.json``;
  ``--gate`` at full size enforces

      time-to-target(SSA) / time-to-target(SSQA) >= GATE_TT_MIN (1x)

  i.e. SSQA must reach the shared quality bar at least as fast as SSA.
  ``--smoke`` shrinks the instance below the quality-saturation point where
  time-to-target stops discriminating, so the smoke cell only checks that
  both families reach the target at all.
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

from repro.core import (
    PTHyperParams,
    SolverConfig,
    SSAHyperParams,
    anneal,
    anneal_pt,
    gset,
)
from repro.core.autotune import resolve_hyperparams
from repro.core.engine import schedule_plateaus, single_problem_backend
from repro.core.ssqa import SSQAHyperParams

from .common import emit, time_call

# SSQA-vs-SSA time-to-target gate (--gate, full size only).
GATE_TT_MIN = 1.0    # required tt(SSA)/tt(SSQA) speedup on K2000-class
TARGET_FRAC = 0.99   # per-seed quality bar: frac of the weaker final best
SSQA_SEEDS = (0, 1, 2)

# Budget knobs (the autotuner derives the energy-scale knobs and the SSQA
# Trotter dimension from the instance's local-field distribution).  K2000
# is the paper's dense benchmark; smoke shrinks it so a CI cell finishes
# in seconds.
FULL_SPEC = {"name": "K2000", "n": 2000, "n_trials": 16, "m_shot": 2}
SMOKE_SPEC = {"name": "K256", "n": 256, "n_trials": 16, "m_shot": 2}


def run(problem: str = "G11", trials: int = 8, m_shot: int = 15,
        csv_prefix: str = "table7_pt"):
    p = gset.load(problem)
    hp = SSAHyperParams(n_trials=trials, m_shot=m_shot)
    cycles = hp.total_cycles

    t0 = time.perf_counter()
    r_ha = anneal(p, hp, seed=0, track_energy=False, config=SolverConfig())
    t_ha = time.perf_counter() - t0

    t0 = time.perf_counter()
    r_pt = anneal_pt(p, PTHyperParams(n_replicas=8, n_cycles=cycles), seed=0,
                     track_energy=False)
    t_pt = time.perf_counter() - t0

    emit(f"{csv_prefix}/{problem}/hassa", t_ha * 1e6,
         f"best={r_ha.overall_best_cut};avg={r_ha.mean_best_cut:.1f}")
    emit(f"{csv_prefix}/{problem}/pt", t_pt * 1e6, f"best={r_pt.best_cut}")
    emit(f"{csv_prefix}/{problem}/hassa_vs_pt_cut", 0.0,
         f"{r_ha.overall_best_cut - r_pt.best_cut:+d}")
    return dict(ha=r_ha, pt=r_pt, t_ha=t_ha, t_pt=t_pt)


# ---------------------------------------------------------------------------
# SSQA vs SSA time-to-target (DESIGN.md §13)
# ---------------------------------------------------------------------------
def _cut_trace(p, hp, seed: int, cfg: SolverConfig) -> np.ndarray:
    """Best-so-far cut per cycle (deterministic; dense-backend scan path)."""
    r = anneal(p, hp, seed=seed, config=cfg, track_energy=True)
    best_h = np.minimum.accumulate(np.asarray(r.energy_min))
    return (p.w_total - best_h) // 2


def _s_per_cycle(model, hp) -> float:
    """Steady-state seconds per annealing cycle (compile excluded)."""
    plateaus = schedule_plateaus(hp.schedule("hassa"))
    cycles = sum(pl.length for pl in plateaus)
    opts = {}
    nr = int(getattr(hp, "n_replicas", 0) or 0)
    if nr:
        opts["n_replicas"] = nr
    bk, problem = single_problem_backend(
        model, "dense", n_trials=hp.n_trials, n_rnd=hp.n_rnd,
        noise="xorshift", **opts,
    )
    state = bk.init_state(problem, bk.init_noise([0], [model.n]))
    chain = jax.jit(lambda s: bk.run_shots(problem, s, plateaus, 1))
    # The tt gate divides two of these, so noise matters: median of 7 warm
    # calls (the deterministic cycles-to-target term carries the signal).
    us = time_call(chain, state, warmup=2, iters=7)
    return us * 1e-6 / cycles


def run_ssqa(
    smoke: bool = False,
    json_path: str = "BENCH_ssqa.json",
    gate: bool = False,
    csv_prefix: str = "ssqa",
):
    """SSQA-vs-SSA time-to-target bench; returns (report, failures)."""
    spec = SMOKE_SPEC if smoke else FULL_SPEC
    p = gset.complete_graph(spec["n"], seed=2000, name=spec["name"])
    cfg = SolverConfig(backend="dense")

    budget = dict(n_trials=spec["n_trials"], m_shot=spec["m_shot"])
    hp_ssa, _ = resolve_hyperparams("auto", p, base=SSAHyperParams(**budget))
    hp_ssqa, _ = resolve_hyperparams(
        "auto", p, base=SSQAHyperParams(**budget), algo="ssqa")
    hps = {"ssa": hp_ssa, "ssqa": hp_ssqa}

    failures = []
    seeds = []
    ctt = {"ssa": [], "ssqa": []}
    finals = {"ssa": [], "ssqa": []}
    for seed in SSQA_SEEDS:
        tr = {a: _cut_trace(p, hps[a], seed, cfg) for a in ("ssa", "ssqa")}
        target = int(
            TARGET_FRAC * min(int(tr["ssa"][-1]), int(tr["ssqa"][-1]))
        )
        row = {"seed": seed, "target_cut": target}
        for algo in ("ssa", "ssqa"):
            reached = tr[algo] >= target
            if not reached.any():
                failures.append(
                    f"{algo} seed {seed}: never reached target {target}")
                continue
            c = int(np.argmax(reached)) + 1
            ctt[algo].append(c)
            finals[algo].append(int(tr[algo][-1]))
            row[algo] = {
                "final_cut": int(tr[algo][-1]), "cycles_to_target": c,
            }
        seeds.append(row)

    model = p.to_ising()
    summary = {}
    for algo in ("ssa", "ssqa"):
        spc = _s_per_cycle(model, hps[algo])
        mean_ctt = float(np.mean(ctt[algo])) if ctt[algo] else float("nan")
        summary[algo] = {
            "hp": repr(hps[algo]),
            "mean_cycles_to_target": mean_ctt,
            "s_per_cycle": spc,
            "time_to_target_s": mean_ctt * spc,
            "best_final_cut": max(finals[algo]) if finals[algo] else None,
        }
    tt_speedup = (summary["ssa"]["time_to_target_s"]
                  / summary["ssqa"]["time_to_target_s"])
    # The 1x bar applies at full size only: at smoke size both families
    # saturate the instance early and cycles-to-target is decided by noise.
    if gate and not smoke and not (tt_speedup >= GATE_TT_MIN):
        failures.append(
            f"{spec['name']}: SSQA time-to-target speedup {tt_speedup:.2f}x "
            f"< required {GATE_TT_MIN}x"
        )

    for algo in ("ssa", "ssqa"):
        s = summary[algo]
        emit(
            f"{csv_prefix}/{spec['name']}/{algo}",
            s["time_to_target_s"] * 1e6,
            f"mean_ctt={s['mean_cycles_to_target']:.0f}cyc;"
            f"s_per_cycle={s['s_per_cycle']:.2e};"
            f"best_final={s['best_final_cut']}",
        )
    emit(f"{csv_prefix}/{spec['name']}/tt_speedup", 0.0, f"{tt_speedup:.2f}")

    report = {
        "smoke": smoke,
        "instance": {"name": spec["name"], "n": spec["n"]},
        "target_frac": TARGET_FRAC,
        "seeds": seeds,
        "ssa": summary["ssa"],
        "ssqa": summary["ssqa"],
        "tt_speedup": tt_speedup,
        "gate": {"min_tt_speedup": GATE_TT_MIN,
                 "enforced": bool(gate and not smoke),
                 "failures": failures},
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
                    help="reduced instance size (CI smoke cell)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 if the SSQA time-to-target gate fails")
    ap.add_argument("--json", default="BENCH_ssqa.json")
    ap.add_argument("--table7", action="store_true",
                    help="emit the paper Table VII HA-SSA-vs-PT rows instead")
    args = ap.parse_args()
    if args.table7:
        run()
        sys.exit(0)
    _, failures = run_ssqa(smoke=args.smoke, json_path=args.json,
                           gate=args.gate)
    if failures:
        print("GATE FAILURES:")
        for f in failures:
            print("  -", f)
        sys.exit(1)
