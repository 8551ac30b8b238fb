"""Problem-frontend sweep: every family end-to-end through the service.

The paper demonstrates SSA/HA-SSA on G-set Max-Cut (and Sec. VI-B argues
the extension to integer-weight Ising models); the problem frontend
(:mod:`repro.problems`, DESIGN.md §9) opens generic QUBO, maximum
independent set, graph coloring and number partitioning through the same
:class:`~repro.serve.AnnealService`.  This benchmark is the end-to-end
witness:

* every family solves a smoke instance through the service on all three
  backends (sparse / dense / pallas), decodes to a domain solution, and the
  family's *feasibility verifier* must accept it — on every backend;
* the three backends must agree on the decoded objective (they run the
  same xorshift noise stream and are bit-identical per the engine
  property tests — a disagreement here is a frontend bug);
* ``hyperparams='auto'`` (local-energy-distribution autotuning,
  :mod:`repro.core.autotune`) must **match or beat** the hand-set defaults
  on the G11 cut and the QUBO smoke objective — the acceptance gate.

Writes ``BENCH_problems.json`` and exits 1 if any gate fails.

    python -m benchmarks.other_problems            # full sweep (nightly)
    python -m benchmarks.other_problems --smoke    # CI: reduced budgets
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro.core import SSAHyperParams, gset
from repro.problems import make_demo
from repro.serve import AnnealRequest, AnnealService

from .common import emit

BACKEND_NAMES = ("sparse", "dense", "pallas")

# family → (smoke size, full size) in frontend units (see FAMILIES factories).
SIZES = {
    "qubo": (32, 96),
    "mis": (48, 128),
    "coloring": (36, 90),
    "partition": (24, 48),
}


def _solve_one(backend, enc_or_problem, hp, *, seed=0, auto_base=None):
    svc = AnnealService(backend=backend, noise="xorshift")
    req = AnnealRequest(problem=enc_or_problem, hp=hp, seed=seed,
                        auto_base=auto_base)
    t0 = time.perf_counter()
    resp = svc.solve([req])[0]
    return resp, time.perf_counter() - t0


def run(smoke: bool = False, json_path: str = "BENCH_problems.json",
        csv_prefix: str = "problems"):
    base = (SSAHyperParams(n_trials=4, m_shot=2) if smoke
            else SSAHyperParams(n_trials=16, m_shot=10))
    report = {"smoke": smoke, "families": {}, "acceptance": {}}
    failures = []

    # -- family sweep: all backends, decoded-solution verification ---------
    for kind, (n_smoke, n_full) in SIZES.items():
        enc = make_demo(kind, n=n_smoke if smoke else n_full, seed=0)
        row = {"name": enc.model.name, "n_spins": enc.model.n, "backends": {}}
        objectives = {}
        for backend in BACKEND_NAMES:
            resp, wall = _solve_one(backend, enc, "auto", auto_base=base)
            rhp = resp.request.hp
            row["backends"][backend] = {
                "objective": resp.objective,
                "feasible": bool(resp.feasible),
                "wall_s": wall,
                "n_rnd": rhp.n_rnd,
                "i0_max": rhp.i0_max,
                "tau": rhp.tau,
            }
            objectives[backend] = resp.objective
            emit(f"{csv_prefix}/{kind}/{backend}", wall * 1e6,
                 f"objective={resp.objective};feasible={resp.feasible};"
                 f"n_rnd={rhp.n_rnd};i0_max={rhp.i0_max}")
            if not resp.feasible:
                failures.append(f"{kind}/{backend}: decoded solution infeasible")
        if len(set(objectives.values())) != 1:
            failures.append(f"{kind}: backends disagree: {objectives}")
        row["backends_agree"] = len(set(objectives.values())) == 1
        report["families"][kind] = row

    # -- acceptance: auto matches-or-beats hand on G11 and the QUBO case ---
    g11 = gset.load("G11")
    hand, _ = _solve_one("sparse", g11, base)
    auto, _ = _solve_one("sparse", g11, "auto", auto_base=base)
    g11_row = {
        "hand_cut": int(hand.result.overall_best_cut),
        "auto_cut": int(auto.result.overall_best_cut),
        "auto_params": {"n_rnd": auto.request.hp.n_rnd,
                        "i0_max": auto.request.hp.i0_max,
                        "tau": auto.request.hp.tau},
    }
    emit(f"{csv_prefix}/acceptance/g11", 0.0,
         f"hand={g11_row['hand_cut']};auto={g11_row['auto_cut']}")
    if g11_row["auto_cut"] < g11_row["hand_cut"]:
        failures.append(f"G11: auto cut {g11_row['auto_cut']} < "
                        f"hand cut {g11_row['hand_cut']}")
    report["acceptance"]["g11"] = g11_row

    qenc = make_demo("qubo", n=SIZES["qubo"][0], seed=0)  # the QUBO smoke case
    handq, _ = _solve_one("sparse", qenc, base)
    autoq, _ = _solve_one("sparse", qenc, "auto", auto_base=base)
    q_row = {"hand_objective": handq.objective, "auto_objective": autoq.objective}
    emit(f"{csv_prefix}/acceptance/qubo", 0.0,
         f"hand={q_row['hand_objective']};auto={q_row['auto_objective']}")
    if autoq.objective > handq.objective:  # minimization
        failures.append(f"qubo: auto objective {autoq.objective} > "
                        f"hand objective {handq.objective}")
    report["acceptance"]["qubo"] = q_row

    if smoke:
        # Structural witness for the 32-spin smoke regression (PR 7): on
        # tiny instances the resident pallas kernel's launch overhead loses
        # to the scan backends, so 'auto' must route them to dense.  Gate
        # the resolver itself — cheaper and less flaky than re-timing it.
        from repro.core.engine import MIN_RESIDENT_N, resolve_backend

        picked = resolve_backend("auto", 32)
        emit(f"{csv_prefix}/auto_backend_n32", 0.0,
             f"{picked};min_resident_n={MIN_RESIDENT_N}")
        if picked != "dense":
            failures.append(
                f"auto backend at n=32 resolved to {picked!r}, not 'dense' "
                f"(MIN_RESIDENT_N={MIN_RESIDENT_N} regression)"
            )
        report["acceptance"]["auto_backend_n32"] = picked

    report["failures"] = failures
    report["ok"] = not failures
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI: reduced instance sizes and cycle budgets")
    ap.add_argument("--json", default="BENCH_problems.json")
    args = ap.parse_args()
    rep = run(smoke=args.smoke, json_path=args.json)
    if not rep["ok"]:
        for f in rep["failures"]:
            print(f"FAIL: {f}", file=sys.stderr)
        sys.exit(1)
