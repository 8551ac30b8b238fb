"""Weak-scaling benchmark for the spin-sharded annealer (DESIGN.md §11).

Measures steady-state spin-cycles/s of ONE instance sharded over P devices
at fixed N/device (weak scaling: N = P × n_per_dev), the per-device
residency drop, and the largest-N-solved row — an instance above
``engine.MAX_UNSHARDED_SPINS`` that the single-device service path REJECTS
at admission and the spin-sharded path solves end to end.  Every
multi-device row also asserts sharded ≡ single-device **bit-identity** for
both field arithmetic paths (f32 tiled-slab matmul and XNOR-popcount) —
the numbers only count because the answers are exactly equal.

The device count must be fixed before jax initializes, so the benchmark
runs parent/worker: the parent (never imports jax) spawns one subprocess
per device count with ``XLA_FLAGS=--xla_force_host_platform_device_count``
set, and aggregates into ``BENCH_scale.json``.

Speedup gate (``--gate``): weak scaling doubles the work at constant wall
time, so 2 devices must reach ``GATE_SPEEDUP_2DEV`` (1.6×) the 1-device
spin-cycles/s.  **CPU-emulation floor**: forced host devices on a machine
with fewer than ``2 × devices`` cores share the same silicon — no speedup
is physically available, and the gate degrades (documented, recorded in
the JSON as ``emulation: true``) to ``GATE_EMULATION_FLOOR`` (0.45×):
sharding overhead (the per-cycle all-gather + psum) must not destroy
throughput even when it cannot add any.  On real multi-device hardware the
full 1.6× gate applies.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

GATE_SPEEDUP_2DEV = 1.6     # weak-scaling speedup @ 2 devices, real hardware
GATE_EMULATION_FLOOR = 0.45  # same-silicon forced-device floor (see docstring)

SMOKE = {"n_per_dev": 512, "n_trials": 2, "tau": 4, "i0_max": 4,
         "devices": (1, 2, 4), "big_n": 40000,
         "big_hp": dict(n_trials=1, m_shot=1, tau=2, i0_min=1, i0_max=2)}
FULL = {"n_per_dev": 4096, "n_trials": 4, "tau": 16, "i0_max": 8,
        "devices": (1, 2, 4, 8), "big_n": 40000,
        "big_hp": dict(n_trials=2, m_shot=1, tau=4, i0_min=1, i0_max=4)}


# ---------------------------------------------------------------------------
# Worker: runs inside one forced-device-count process
# ---------------------------------------------------------------------------
def _worker(args) -> None:
    import jax
    import numpy as np

    from repro.core import SSAHyperParams, anneal, gset, memory
    from repro.core.engine import (
        MAX_UNSHARDED_SPINS,
        schedule_plateaus,
        single_problem_backend,
    )
    from repro.sharding import spin_mesh

    from .common import time_call

    P = len(jax.devices())
    assert P == args.devices, f"forced {args.devices} devices, got {P}"
    mesh = spin_mesh(P)
    out = {"devices": P, "platform": jax.devices()[0].platform,
           "cpu_count": os.cpu_count() or 1}

    # -- bit-identity: sharded == single-device, both field arithmetics ----
    # Small instance, every trial compared on best_energy AND best_m.  This
    # is the contract that makes the throughput rows comparable at all.
    small = gset.toroidal_grid(1024, seed=7, name="bitid")
    hp_id = SSAHyperParams(n_trials=2, m_shot=2, tau=3, i0_min=1, i0_max=4)
    bit_identity = {}
    for label, plain_opts, shard_opts in (
        ("tiled", {"j_mode": "tiled"}, {}),
        ("popcount", {"field_mode": "popcount"}, {"field_mode": "popcount"}),
    ):
        ref = anneal(small, hp_id, seed=3, backend="dense", noise="xorshift",
                     backend_opts=plain_opts)
        sh = anneal(small, hp_id, seed=3, backend="dense", noise="xorshift",
                    backend_opts={"partition": "spin", "mesh": mesh,
                                  **shard_opts})
        same = (np.array_equal(ref.best_energy, sh.best_energy)
                and np.array_equal(ref.best_m, sh.best_m))
        bit_identity[label] = bool(same)
        if not same:
            print(f"BIT-IDENTITY FAILURE ({label}, P={P})", file=sys.stderr)
    out["bit_identity"] = bit_identity

    # -- weak-scaling throughput: N = P * n_per_dev ------------------------
    n = P * args.n_per_dev
    model = gset.toroidal_grid(n, seed=11, name=f"scale{n}").to_ising()
    hp = SSAHyperParams(n_trials=args.n_trials, m_shot=1, tau=args.tau,
                        i0_min=1, i0_max=args.i0_max)
    plateaus = schedule_plateaus(hp.schedule("hassa"))
    cycles = sum(p.length for p in plateaus)
    bk, problem = single_problem_backend(
        model, "dense", n_trials=hp.n_trials, n_rnd=hp.n_rnd,
        noise="xorshift", partition="spin", mesh=mesh,
    )
    state = bk.init_state(problem, bk.init_noise([0], [model.n]))
    out["max_device_bytes"] = memory.max_device_bytes((problem, state))
    chain = jax.jit(lambda s: bk.run_shots(problem, s, plateaus, 1))
    us = time_call(chain, state, warmup=1, iters=3)
    out["n"] = n
    out["wall_us"] = us
    out["spin_cycles_per_s"] = cycles * hp.n_trials * n / (us * 1e-6)

    # -- largest-N row: service rejection + sharded end-to-end solve -------
    if args.big_n:
        from repro.serve import AdmissionError, AnnealRequest, AnnealService

        big = gset.toroidal_grid(args.big_n, seed=5, name="bigN")
        assert big.n > MAX_UNSHARDED_SPINS
        hp_big = SSAHyperParams(**json.loads(args.big_hp))
        rejected = False
        try:
            AnnealService(backend="sparse").solve(
                [AnnealRequest(problem=big, hp=hp_big, seed=1)]
            )
        except AdmissionError:
            rejected = True
        resp = AnnealService(
            backend="sparse", partition="spin", mesh=mesh
        ).solve([AnnealRequest(problem=big, hp=hp_big, seed=1)])[0]
        out["largest_n"] = {
            "n": int(big.n),
            "bucket": int(resp.bucket),
            "single_device_rejected": rejected,
            "status": resp.status,
            "best_cut": int(np.max(np.asarray(resp.result.best_cut))),
        }
    print("RESULT_JSON:" + json.dumps(out))


# ---------------------------------------------------------------------------
# Parent: one subprocess per device count, then aggregate + gate
# ---------------------------------------------------------------------------
def _spawn(devices: int, cfg: dict, big_n: int) -> dict:
    env = dict(os.environ)
    # Workers import repro regardless of how the parent found it.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root, env.get("PYTHONPATH"))
        if p
    )
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=1", ""
        )
    ).strip()
    cmd = [sys.executable, "-m", "benchmarks.scale", "--worker",
           "--devices", str(devices),
           "--n-per-dev", str(cfg["n_per_dev"]),
           "--n-trials", str(cfg["n_trials"]),
           "--tau", str(cfg["tau"]), "--i0-max", str(cfg["i0_max"]),
           "--big-n", str(big_n),
           "--big-hp", json.dumps(cfg["big_hp"])]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3600)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT_JSON:"):
            return json.loads(line[len("RESULT_JSON:"):])
    raise RuntimeError(
        f"worker P={devices} produced no result\n--- stdout ---\n"
        f"{proc.stdout[-2000:]}\n--- stderr ---\n{proc.stderr[-2000:]}"
    )


def run(smoke: bool = False, json_path: str = "BENCH_scale.json",
        gate: bool = False):
    from .common import emit

    cfg = SMOKE if smoke else FULL
    rows, failures = [], []
    for i, p in enumerate(cfg["devices"]):
        # The largest-N service row runs once, on the widest mesh.
        big_n = cfg["big_n"] if p == max(cfg["devices"]) else 0
        row = _spawn(p, cfg, big_n)
        rows.append(row)
        for label, ok in row["bit_identity"].items():
            if not ok:
                failures.append(f"P={p}: sharded != single-device ({label})")
        emit(
            f"scale/P{p}/n{row['n']}", row["wall_us"],
            f"scs={row['spin_cycles_per_s']:.3e};"
            f"max_dev_bytes={row['max_device_bytes']};"
            f"bit_identity={all(row['bit_identity'].values())}",
        )
    base = rows[0]["spin_cycles_per_s"]
    for row in rows:
        row["weak_scaling_speedup"] = row["spin_cycles_per_s"] / base

    platform = rows[0]["platform"]
    cpu_count = rows[0]["cpu_count"]
    emulation = platform == "cpu" and cpu_count < 2 * 2
    two = next((r for r in rows if r["devices"] == 2), None)
    speedup2 = two["weak_scaling_speedup"] if two else None
    required = GATE_EMULATION_FLOOR if emulation else GATE_SPEEDUP_2DEV
    if gate and speedup2 is not None and speedup2 < required:
        failures.append(
            f"2-device weak-scaling speedup {speedup2:.2f}x < required "
            f"{required}x ({'CPU-emulation floor' if emulation else 'hardware gate'})"
        )
    big = next((r["largest_n"] for r in rows if "largest_n" in r), None)
    if gate and big is not None:
        if not big["single_device_rejected"]:
            failures.append("largest-N instance was NOT rejected unsharded")
        if big["status"] != "ok":
            failures.append(f"largest-N sharded solve status={big['status']}")

    report = {
        "smoke": smoke,
        "platform": platform,
        "cpu_count": cpu_count,
        "emulation": emulation,
        "gate": {"speedup_2dev_hardware": GATE_SPEEDUP_2DEV,
                 "speedup_2dev_emulation_floor": GATE_EMULATION_FLOOR,
                 "required": required, "measured_2dev": speedup2,
                 "enforced": gate, "failures": failures},
        "weak_scaling": rows,
        "largest_n": big,
    }
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    emit("scale/speedup_2dev", 0.0,
         f"{speedup2:.2f}x (required {required}x, "
         f"{'emulation' if emulation else 'hardware'})" if speedup2 else "n/a")
    emit("scale/gate", 0.0, "PASS" if not failures else ";".join(failures))
    return report, failures


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + device counts (CI smoke cell)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 on bit-identity/speedup/largest-N failure")
    ap.add_argument("--json", default="BENCH_scale.json")
    # worker-mode flags (internal)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--n-per-dev", type=int, dest="n_per_dev",
                    default=512, help=argparse.SUPPRESS)
    ap.add_argument("--n-trials", type=int, dest="n_trials", default=2,
                    help=argparse.SUPPRESS)
    ap.add_argument("--tau", type=int, default=4, help=argparse.SUPPRESS)
    ap.add_argument("--i0-max", type=int, dest="i0_max", default=4,
                    help=argparse.SUPPRESS)
    ap.add_argument("--big-n", type=int, dest="big_n", default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--big-hp", dest="big_hp", default="{}",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        return _worker(args)
    _, failures = run(smoke=args.smoke, json_path=args.json, gate=args.gate)
    if failures:
        print("GATE FAILURES:")
        for f in failures:
            print("  -", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
