"""Annealing launcher (the paper's own workload, production form).

    PYTHONPATH=src python -m repro.launch.anneal --problem G11 --trials 16 \
        --m-shot 20 [--storage i0max|all] [--backend sparse|dense|pallas]

Selectable problems: G-set instances (real files if present under
data/gset/, structure-faithful generated twins otherwise), King1, K2000.

The solve runs on the plateau engine (DESIGN.md §2): `--backend pallas`
executes each temperature plateau as one resident `pallas_call` (J pinned
in VMEM); `sparse`/`dense` run the single-contraction-per-cycle scan.
`--track-energy` records per-cycle energy traces (forces the scan path on
the pallas backend, which has no per-cycle outputs).

Service mode (DESIGN.md §7): pass a comma list to ``--problem`` (or
``--service``) and the launcher routes the batch through
:class:`repro.serve.AnnealService` — bucketed, stacked, one compiled
plateau program per shape bucket, with per-chunk streaming progress and
optional ``--target-cut`` early stop.

Streaming mode (DESIGN.md §12): add ``--stream`` to submit the problem
list to the always-on continuous-batching front door
(:class:`repro.serve.StreamingAnnealService`) instead of a single
``solve()`` batch — ``--arrival-rate`` paces the submissions as an
open-loop client, ``--priority`` picks the admission class.

Problem frontend (DESIGN.md §9): ``--problem-kind qubo|mis|coloring|
partition`` generates demo instances of the selected family (sized by
``--problem-n``, seeded by ``--seed``, ``--count`` of them) and solves them
through the service with decoded-solution verification.  ``--auto-tune``
replaces the Table-II hyperparameters with the local-energy-distribution
determination (:mod:`repro.core.autotune`) in every mode.
"""
from __future__ import annotations

import argparse
import time

from repro.configs import ANNEAL_PROBLEMS
from repro.core import (
    SolverConfig,
    SSAHyperParams,
    SSQAHyperParams,
    anneal,
    autotune_hyperparams,
    gset,
    memory,
)
from repro.core.engine import model_weight_bits, route_backend
from repro.launch.compile_cache import enable_compile_cache


def _resilience_policy(args):
    from repro.serve import ResiliencePolicy

    return ResiliencePolicy(checkpoint_dir=args.checkpoint_dir,
                            fallback=not args.no_fallback)


def _backend_opts(args):
    """--field-mode reaches the field-capable backends; sparse ignores it."""
    if args.field_mode != "dense" and args.backend != "sparse":
        return {"field_mode": args.field_mode}
    return {}


def _backend_choice(args, model, hp) -> str:
    """What ``--backend`` resolves to for one instance, and why if 'auto'
    was kept off the resident kernel by its VMEM budget."""
    if args.backend != "auto":
        return args.backend
    chosen, why = route_backend(
        "auto", model.n, noise=args.noise, field_mode=args.field_mode,
        j_bits=model_weight_bits(model), n_cycles=hp.tau,
    )
    return f"auto→{chosen}" + (f" ({why})" if why else "")


def _partition_mesh(args):
    """(partition, mesh) from --partition/--mesh-shape (DESIGN.md §11).

    The mesh is built lazily and only when spin sharding can apply, so
    partition='problem' launches never construct one.
    """
    if args.partition == "problem":
        return "problem", None
    from repro.launch.mesh import make_spin_mesh

    return args.partition, make_spin_mesh(args.mesh_shape)


def _run_service(problem_names, hp, args):
    from repro.serve import AnnealRequest, AnnealService

    problems = [gset.load(name) for name in problem_names]
    requests = [
        AnnealRequest(problem=p, hp="auto" if args.auto_tune else hp,
                      seed=args.seed + i, storage=args.storage,
                      target_cut=args.target_cut, auto_base=hp,
                      deadline_s=args.deadline_s, algo=args.algo)
        for i, p in enumerate(problems)
    ]
    partition, mesh = _partition_mesh(args)
    svc = AnnealService(backend=args.backend, noise=args.noise,
                        storage_layout=args.storage_layout,
                        chunk_shots=args.chunk_shots,
                        backend_opts=_backend_opts(args),
                        resilience=_resilience_policy(args),
                        partition=partition, mesh=mesh)

    def progress(ev):
        bests = ", ".join(
            f"{problems[i].name}={b}"
            for i, b in zip(ev.request_indices, ev.best_cut)
        )
        print(f"[chunk {ev.chunk + 1}/{ev.chunks_total} bucket={ev.bucket}] "
              f"best cut: {bests}")

    t0 = time.time()
    responses = svc.solve(requests, progress=progress)
    dt = time.time() - t0
    total_spin_cycles = 0
    for p, r in zip(problems, responses):
        if r.result is None:
            # No result to report: distinguish 'shed'/'deadline' (the
            # service declined or timed the work out) from 'failed'
            # (retries exhausted) instead of labeling everything a failure.
            print(f"{p.name}: {r.status.upper()} — no result "
                  f"({'; '.join(e.kind for e in r.events) or 'no events'})")
            continue
        rhp = r.request.hp  # resolved (autotuned hp differs from the base)
        shots = r.chunks_run * (rhp.m_shot // r.chunks_total)
        total_spin_cycles += (
            shots * rhp.cycles_per_iter * rhp.n_trials * p.n
        )
        tuned = (f" auto[n_rnd={rhp.n_rnd} i0_max={rhp.i0_max} "
                 f"tau={rhp.tau}]" if r.autotune else "")
        degraded = "" if r.status == "ok" else f" status={r.status}"
        print(f"{p.name}: best cut {r.result.overall_best_cut} "
              f"avg {r.result.mean_best_cut:.1f} "
              f"[backend={r.backend} bucket={r.bucket} batch={r.batch} "
              f"chunks={r.chunks_run}/{r.chunks_total}]{tuned}{degraded}")
        for ev in r.events:
            print(f"  event[{ev.t:.2f}s] {ev.kind}: {ev.detail}")
    info = svc.cache_info()
    print(f"batch of {len(problems)} in {dt:.1f}s "
          f"({total_spin_cycles/dt:.2e} aggregate spin-cycles/s; "
          f"{info['programs']} compiled program(s), "
          f"{info.get('traces_chunk', 0)} plateau-program trace(s))")


def _run_stream(problem_names, hp, args):
    """Streaming client mode (DESIGN.md §12): submit the problem list to an
    always-on StreamingAnnealService — optionally paced as an open-loop
    arrival process — and await the tickets."""
    from repro.serve import (
        AnnealRequest,
        AnnealService,
        StreamingAnnealService,
        StreamPolicy,
    )

    problems = [gset.load(name) for name in problem_names]
    partition, mesh = _partition_mesh(args)
    svc = AnnealService(backend=args.backend, noise=args.noise,
                        storage_layout=args.storage_layout,
                        chunk_shots=args.chunk_shots,
                        backend_opts=_backend_opts(args),
                        resilience=_resilience_policy(args),
                        partition=partition, mesh=mesh)
    ss = StreamingAnnealService(
        service=svc,
        policy=StreamPolicy(slots_per_table=args.stream_slots))
    ss.start()
    t0 = time.time()
    tickets = []
    try:
        for i, p in enumerate(problems):
            if args.arrival_rate > 0 and i:
                time.sleep(1.0 / args.arrival_rate)
            req = AnnealRequest(
                problem=p, hp="auto" if args.auto_tune else hp,
                seed=args.seed + i, storage=args.storage,
                target_cut=args.target_cut, auto_base=hp,
                deadline_s=args.deadline_s, algo=args.algo)
            tickets.append(ss.submit(req, priority=args.priority))
        shed = deadline = 0
        for p, t in zip(problems, tickets):
            r = t.result(timeout=None)
            if r.status == "shed":
                # Dropped unstarted (deadline already unmeetable) — not a
                # solver failure; count it separately in the summary.
                shed += 1
                print(f"{p.name}: SHED — dropped from the queue unstarted "
                      f"(deadline_s={r.request.deadline_s})")
                continue
            if r.result is None:
                print(f"{p.name}: {r.status.upper()} — no result "
                      f"({'; '.join(e.kind for e in r.events) or 'no events'})")
                continue
            if r.status == "deadline":
                deadline += 1
            print(f"{p.name}: best cut {r.result.overall_best_cut} "
                  f"[backend={r.backend} chunks={r.chunks_run}/{r.chunks_total} "
                  f"queued {r.queued_s:.2f}s lane {r.lane_wall_s:.2f}s] "
                  f"status={r.status}"
                  + (" (best-so-far at deadline)"
                     if r.status == "deadline" else ""))
    finally:
        ss.stop()
    dt = time.time() - t0
    st = ss.stream_stats()
    print(f"stream of {len(problems)} in {dt:.1f}s: "
          f"occupancy={st['occupancy']:.2f} "
          f"backfills={st['stream_backfills']} "
          f"tables={st['stream_tables_created']} "
          f"quanta={st['stream_quanta']} "
          f"shed={shed} deadline={deadline}")


def _run_problem_kind(hp, args):
    """Demo instances of a problem family through the service (DESIGN.md §9)."""
    from repro.problems import make_demo
    from repro.serve import AnnealRequest, AnnealService

    encs = [
        make_demo(args.problem_kind, n=args.problem_n, seed=args.seed + i)
        for i in range(args.count)
    ]
    requests = [
        AnnealRequest(problem=enc, hp="auto" if args.auto_tune else hp,
                      seed=args.seed + i, storage=args.storage, auto_base=hp)
        for i, enc in enumerate(encs)
    ]
    partition, mesh = _partition_mesh(args)
    svc = AnnealService(backend=args.backend, noise=args.noise,
                        storage_layout=args.storage_layout,
                        chunk_shots=args.chunk_shots,
                        backend_opts=_backend_opts(args),
                        resilience=_resilience_policy(args),
                        partition=partition, mesh=mesh)
    t0 = time.time()
    responses = svc.solve(requests)
    dt = time.time() - t0
    for enc, r in zip(encs, responses):
        if r.result is None:
            print(f"{enc.model.name}: {r.status.upper()} — no result "
                  f"({'; '.join(e.kind for e in r.events) or 'no events'})")
            continue
        rhp = r.request.hp
        tuned = (f" auto[n_rnd={rhp.n_rnd} i0_max={rhp.i0_max} "
                 f"tau={rhp.tau}]" if r.autotune else "")
        degraded = "" if r.status == "ok" else f" status={r.status}"
        print(f"{enc.model.name}: objective={r.objective} "
              f"feasible={r.feasible} energy={int(r.result.best_energy.min())} "
              f"[backend={r.backend} bucket={r.bucket} "
              f"batch={r.batch}]{tuned}{degraded}")
    info = svc.cache_info()
    print(f"{len(encs)} × {args.problem_kind} in {dt:.1f}s "
          f"({info['programs']} compiled program(s))")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="G11",
                    help="instance name, or a comma list for service mode "
                         f"(known: {sorted(ANNEAL_PROBLEMS)})")
    ap.add_argument("--problem-kind", default="gset",
                    choices=("gset", "qubo", "mis", "coloring", "partition"),
                    help="problem family: 'gset' uses --problem names; other "
                         "kinds generate demo instances through the service "
                         "frontend (DESIGN.md §9)")
    ap.add_argument("--problem-n", type=int, default=0,
                    help="demo instance size for non-gset kinds (0 = family "
                         "default)")
    ap.add_argument("--count", type=int, default=1,
                    help="number of demo instances for non-gset kinds")
    ap.add_argument("--auto-tune", action="store_true",
                    help="derive n_rnd/I0 from the local-energy distribution "
                         "(repro.core.autotune) instead of the Table-II flags")
    ap.add_argument("--service", action="store_true",
                    help="route through the AnnealService even for one problem")
    ap.add_argument("--stream", action="store_true",
                    help="streaming client mode: submit the problem list to "
                         "the continuous-batching StreamingAnnealService "
                         "(DESIGN.md §12) instead of one solve() batch")
    ap.add_argument("--stream-slots", type=int, default=4,
                    help="--stream: compiled slot-table width (power of two)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="--stream: pace submissions at this rate in req/s "
                         "(0 = submit everything immediately)")
    ap.add_argument("--priority", choices=("interactive", "batch"),
                    default="batch",
                    help="--stream: admission priority class")
    ap.add_argument("--target-cut", type=int, default=None,
                    help="service mode: early-stop once every request hits it")
    ap.add_argument("--chunk-shots", type=int, default=1,
                    help="service mode: iterations per progress chunk")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="service mode: chunk-level checkpoint root — a "
                         "killed solve resumes bit-identically (DESIGN.md §10)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="service mode: per-request wall-clock budget; expiry "
                         "returns best-so-far with status='deadline'")
    ap.add_argument("--no-fallback", action="store_true",
                    help="service mode: disable the backend fallback chain "
                         "(pallas→dense→sparse) — faults propagate instead")
    ap.add_argument("--algo", choices=("ssa", "ssqa"), default="ssa",
                    help="algorithm family: 'ssqa' runs the Trotter-replica "
                         "quantum variant (DESIGN.md §13) — the replica ring "
                         "lives on the trial axis, so --trials must be a "
                         "multiple of --replicas")
    ap.add_argument("--replicas", type=int, default=8,
                    help="--algo ssqa: Trotter replicas per ring (>= 2)")
    ap.add_argument("--jperp-max", type=int, default=4,
                    help="--algo ssqa: integer replica coupling at the "
                         "coldest plateau (the Γ→0 end of the ramp)")
    ap.add_argument("--trials", type=int, default=16)
    ap.add_argument("--m-shot", type=int, default=20)
    ap.add_argument("--tau", type=int, default=100)
    ap.add_argument("--i0-min", type=int, default=1)
    ap.add_argument("--i0-max", type=int, default=32)
    ap.add_argument("--n-rnd", type=int, default=2)
    ap.add_argument("--beta-shift", type=int, default=1)
    ap.add_argument("--storage", choices=("i0max", "all"), default="i0max")
    ap.add_argument("--storage-layout", choices=("dense", "packed"),
                    default="dense",
                    help="HBM-resident engine state: int8 spins or uint32 "
                         "bitplanes (DESIGN.md §4; bit-identical results)")
    ap.add_argument("--backend", choices=("sparse", "dense", "pallas", "auto"),
                    default="sparse",
                    help="'auto' picks pallas at/above MIN_RESIDENT_N spins "
                         "where the resident kernel fits the chip's VMEM "
                         "budget, dense otherwise (printed with the reason)")
    ap.add_argument("--field-mode", choices=("dense", "popcount", "auto"),
                    default="dense",
                    help="field contraction arithmetic (dense/pallas "
                         "backends): 'popcount' = XNOR-popcount on uint32 "
                         "bitplanes (DESIGN.md §8; bit-identical results), "
                         "'auto' by coupling bit depth")
    ap.add_argument("--partition", choices=("problem", "spin", "auto"),
                    default="problem",
                    help="work partitioning: 'spin' shards the spin axis of "
                         "each problem over the mesh via shard_map "
                         "collectives (DESIGN.md §11; bit-identical), 'auto' "
                         "picks per instance/bucket")
    ap.add_argument("--mesh-shape", default=None,
                    help="1-D device count for --partition spin|auto, e.g. "
                         "'4' (default: every available device); combine "
                         "with XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for CPU fleets")
    ap.add_argument("--record", choices=("best", "traj"), default="best")
    ap.add_argument("--track-energy", action="store_true",
                    help="record per-cycle energy traces (scan path)")
    ap.add_argument("--noise", choices=("xorshift", "threefry"), default="xorshift")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.algo == "ssqa":
        hp = SSQAHyperParams(
            n_trials=args.trials, m_shot=args.m_shot, n_rnd=args.n_rnd,
            i0_min=args.i0_min, i0_max=args.i0_max, tau=args.tau,
            beta_shift=args.beta_shift, n_replicas=args.replicas,
            jperp_max=args.jperp_max,
        )
    else:
        hp = SSAHyperParams(
            n_trials=args.trials, m_shot=args.m_shot, n_rnd=args.n_rnd,
            i0_min=args.i0_min, i0_max=args.i0_max, tau=args.tau,
            beta_shift=args.beta_shift,
        )
    if args.problem_kind != "gset":
        return _run_problem_kind(hp, args)
    names = args.problem.split(",")
    if args.stream:
        return _run_stream(names, hp, args)
    if args.service or len(names) > 1:
        return _run_service(names, hp, args)

    p = gset.load(args.problem)
    if args.auto_tune:
        hp, rep = autotune_hyperparams(p.to_ising(), hp)
        print(f"auto-tune: sigma={rep.sigma:.2f} |z|max={rep.z_max} → "
              f"n_rnd={hp.n_rnd} I0:{hp.i0_min}→{hp.i0_max} tau={hp.tau}")
    algo_name = ("SSQA" if args.algo == "ssqa"
                 else "HA-SSA" if args.storage == "i0max" else "SSA")
    extra = (f"; R={hp.n_replicas} jperp_max={hp.jperp_max}"
             if args.algo == "ssqa" else "")
    print(f"{p.name}: N={p.n} |E|={len(p.edges)}; {hp.total_cycles} cycles "
          f"× {hp.n_trials} trials; "
          f"backend={_backend_choice(args, p.to_ising(), hp)}; "
          f"storage={args.storage} ({algo_name}){extra}")
    partition, mesh = _partition_mesh(args)
    cfg = SolverConfig(
        backend=args.backend, noise=args.noise,
        storage_layout=args.storage_layout,
        field_mode=(args.field_mode
                    if args.backend != "sparse" else "auto"),
        partition=partition, mesh=mesh,
    )
    t0 = time.time()
    r = anneal(p, hp, seed=args.seed, storage=args.storage, record=args.record,
               config=cfg, track_energy=args.track_energy)
    dt = time.time() - t0
    spin_cycles = hp.total_cycles * hp.n_trials
    print(f"best cut {r.overall_best_cut}  avg {r.mean_best_cut:.1f}  "
          f"best energy {r.best_energy.min()}  ({dt:.1f}s, "
          f"{spin_cycles/dt:.0f} trial-cycles/s, "
          f"{spin_cycles*p.n/dt:.2e} spin-cycles/s)")
    if p.best_known:
        print(f"best known {p.best_known} → {100*r.overall_best_cut/p.best_known:.2f}%")
    print(f"trajectory memory/iter: {memory.hassa_bits_per_iteration(p.n, hp)} bits "
          f"(SSA would use {memory.ssa_bits_per_iteration(p.n, hp)}; "
          f"{memory.memory_ratio(hp)}× saving)")


if __name__ == "__main__":
    main()
