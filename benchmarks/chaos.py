"""Chaos suite: the annealing service under injected faults (DESIGN.md §10).

The resilience layer's claims are only worth stating if they are *measured*:
this benchmark drives :class:`~repro.serve.AnnealService` through every
fault class the failure model names — via the
:mod:`repro.ft.faults` injector — and gates on the recovery contracts:

* **kill/resume** — a process killed between chunks, resumed from its
  chunk-level checkpoints, must produce bit-identical best energy/spins to
  an uninterrupted run (all three backends, noise='xorshift');
* **compile fallback** — an injected pallas compile failure must complete
  via the pallas→dense→sparse chain, bit-identical, with the downgrade on
  ``AnnealResponse.status``/``events``;
* **oom→tiled** — an injected dense-J OOM must re-enter as tiled-J on the
  same backend, bit-identical;
* **nan quarantine** — a NaN burst on one batch slot must quarantine only
  that request (solo retry) while its batchmate stays bit-exact;
* **deadline** — an expired per-request deadline must return best-so-far
  with ``status='deadline'`` instead of raising;
* **chaos schedules** — seeded random fault plans
  (:func:`repro.ft.faults.chaos_schedule`) must all end in served
  responses, every produced result bit-identical to the fault-free run.

Writes ``BENCH_chaos.json`` and exits 1 if any gate fails.

    python -m benchmarks.chaos            # full sweep (nightly)
    python -m benchmarks.chaos --smoke    # CI: fewer seeds, smaller budgets
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np

from repro.core import SSAHyperParams, gset
from repro.ft.faults import FaultInjector, InjectedKill, chaos_schedule
from repro.serve import AnnealRequest, AnnealService, ResiliencePolicy

from .common import emit

BACKEND_NAMES = ("sparse", "dense", "pallas")


def _problems(smoke):
    n = 36 if smoke else 100
    return (gset.toroidal_grid(n, seed=0, name=f"t{n}"),
            gset.king_graph(n, seed=3, name=f"k{n}"))


def _hp(smoke):
    return (SSAHyperParams(n_trials=3, m_shot=6, tau=4, i0_min=1, i0_max=8)
            if smoke else SSAHyperParams(n_trials=8, m_shot=10))


def _requests(problems, hp, **kw):
    return [AnnealRequest(problem=p, hp=hp, seed=i + 1, **kw)
            for i, p in enumerate(problems)]


def _bit_identical(a, b):
    return (np.array_equal(a.result.best_energy, b.result.best_energy)
            and np.array_equal(a.result.best_m, b.result.best_m))


def run(smoke: bool = False, json_path: str = "BENCH_chaos.json",
        csv_prefix: str = "chaos"):
    problems, hp = _problems(smoke), _hp(smoke)
    failures = []
    report = {"smoke": smoke, "scenarios": {}}
    baseline = {
        b: AnnealService(backend=b, min_bucket=16).solve(_requests(problems, hp))
        for b in BACKEND_NAMES
    }

    # -- kill at a chunk boundary, resume from checkpoints ---------------
    for backend in BACKEND_NAMES:
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as d:
            pol = ResiliencePolicy(checkpoint_dir=d)
            inj = FaultInjector()
            inj.arm("kill", chunk=2)
            svc = AnnealService(backend=backend, min_bucket=16,
                                resilience=pol, faults=inj)
            killed = False
            try:
                svc.solve(_requests(problems, hp))
            except InjectedKill:
                killed = True
            resumed = AnnealService(backend=backend, min_bucket=16,
                                    resilience=pol).solve(_requests(problems, hp))
        identical = all(_bit_identical(a, b)
                        for a, b in zip(baseline[backend], resumed))
        resumed_from = [e.detail.get("chunk") for r in resumed
                        for e in r.events if e.kind == "resume"]
        ok = killed and identical and bool(resumed_from)
        report["scenarios"][f"kill_resume_{backend}"] = {
            "killed": killed, "bit_identical": identical,
            "resumed_from_chunk": resumed_from[:1], "ok": ok,
        }
        emit(f"{csv_prefix}/kill_resume/{backend}",
             (time.perf_counter() - t0) * 1e6, f"bit_identical={identical}")
        if not ok:
            failures.append(f"kill_resume[{backend}]: killed={killed} "
                            f"bit_identical={identical} resume={resumed_from}")

    # -- injected pallas compile failure → fallback chain ----------------
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    svc = AnnealService(backend="pallas", min_bucket=16, faults=inj)
    t0 = time.perf_counter()
    resp = svc.solve(_requests(problems, hp))
    hops = [(e.detail["from"], e.detail["to"])
            for e in resp[0].events if e.kind == "fallback"]
    identical = all(_bit_identical(a, b)
                    for a, b in zip(baseline["pallas"], resp))
    ok = (all(r.status == "fallback" for r in resp)
          and hops == [("pallas", "dense")] and identical)
    report["scenarios"]["compile_fallback"] = {
        "statuses": [r.status for r in resp], "hops": hops,
        "bit_identical": identical, "ok": ok,
    }
    emit(f"{csv_prefix}/compile_fallback", (time.perf_counter() - t0) * 1e6,
         f"hops={hops}")
    if not ok:
        failures.append(f"compile_fallback: statuses="
                        f"{[r.status for r in resp]} hops={hops} "
                        f"bit_identical={identical}")

    # -- injected dense-J OOM → tiled-J downgrade ------------------------
    inj = FaultInjector()
    inj.arm("oom", backend="dense", j_mode="dense")
    svc = AnnealService(backend="dense", min_bucket=16, faults=inj)
    t0 = time.perf_counter()
    resp = svc.solve(_requests(problems, hp))
    to_opts = [e.detail["to_opts"] for e in resp[0].events
               if e.kind == "fallback"]
    identical = all(_bit_identical(a, b)
                    for a, b in zip(baseline["dense"], resp))
    ok = (all(r.status == "fallback" for r in resp) and identical
          and to_opts and to_opts[0].get("j_mode") == "tiled")
    report["scenarios"]["oom_tiled"] = {
        "statuses": [r.status for r in resp], "to_opts": to_opts,
        "bit_identical": identical, "ok": ok,
    }
    emit(f"{csv_prefix}/oom_tiled", (time.perf_counter() - t0) * 1e6,
         f"to_opts={to_opts}")
    if not ok:
        failures.append(f"oom_tiled: to_opts={to_opts} "
                        f"bit_identical={identical}")

    # -- NaN burst → quarantine, batchmate bit-exact ---------------------
    inj = FaultInjector()
    inj.arm("nan", chunk=1, slots=(1,))
    svc = AnnealService(backend="sparse", min_bucket=16, faults=inj)
    t0 = time.perf_counter()
    resp = svc.solve(_requests(problems, hp))
    mate_exact = _bit_identical(baseline["sparse"][0], resp[0])
    ok = (resp[0].status == "ok" and mate_exact
          and resp[1].status == "quarantined" and resp[1].result is not None)
    report["scenarios"]["nan_quarantine"] = {
        "statuses": [r.status for r in resp],
        "batchmate_bit_exact": mate_exact, "ok": ok,
    }
    emit(f"{csv_prefix}/nan_quarantine", (time.perf_counter() - t0) * 1e6,
         f"statuses={[r.status for r in resp]}")
    if not ok:
        failures.append(f"nan_quarantine: statuses={[r.status for r in resp]} "
                        f"batchmate_exact={mate_exact}")

    # -- deadline expiry → best-so-far, never raises ---------------------
    svc = AnnealService(backend="sparse", min_bucket=16)
    t0 = time.perf_counter()
    resp = svc.solve(_requests(problems, hp, deadline_s=1e-9))
    ok = (all(r.status == "deadline" for r in resp)
          and all(r.result is not None for r in resp)
          and all(r.chunks_run < r.chunks_total for r in resp))
    report["scenarios"]["deadline"] = {
        "statuses": [r.status for r in resp],
        "chunks": [(r.chunks_run, r.chunks_total) for r in resp], "ok": ok,
    }
    emit(f"{csv_prefix}/deadline", (time.perf_counter() - t0) * 1e6,
         f"chunks={[r.chunks_run for r in resp]}")
    if not ok:
        failures.append(f"deadline: statuses={[r.status for r in resp]}")

    # -- seeded chaos schedules ------------------------------------------
    n_seeds = 6 if smoke else 24
    survived = 0
    t0 = time.perf_counter()
    for seed in range(n_seeds):
        with tempfile.TemporaryDirectory() as d:
            pol = ResiliencePolicy(checkpoint_dir=d)
            svc = AnnealService(backend="pallas", min_bucket=16,
                                resilience=pol, faults=chaos_schedule(seed))
            try:
                resp = svc.solve(_requests(problems, hp))
            except InjectedKill:
                resp = AnnealService(backend="pallas", min_bucket=16,
                                     resilience=pol).solve(
                    _requests(problems, hp))
            # Quarantined responses retried with a re-autotuned I0max —
            # a *different valid run*, so they are exempt from bit-identity.
            good = all(
                (r.result is not None if r.status == "quarantined"
                 else _bit_identical(b, r))
                for b, r in zip(baseline["pallas"], resp)
            )
            survived += bool(good and len(resp) == len(problems))
    ok = survived == n_seeds
    report["scenarios"]["chaos_schedules"] = {
        "seeds": n_seeds, "survived": survived, "ok": ok,
    }
    emit(f"{csv_prefix}/chaos_schedules", (time.perf_counter() - t0) * 1e6,
         f"survived={survived}/{n_seeds}")
    if not ok:
        failures.append(f"chaos_schedules: survived {survived}/{n_seeds}")

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
                    help="CI: fewer chaos seeds and smaller budgets")
    ap.add_argument("--json", default="BENCH_chaos.json")
    args = ap.parse_args()
    rep = run(smoke=args.smoke, json_path=args.json)
    if not rep["ok"]:
        for f in rep["failures"]:
            print(f"FAIL: {f}", file=sys.stderr)
        sys.exit(1)
