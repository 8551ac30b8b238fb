"""Bring-up smoke test: the annealing service's main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the spin-sharded path on four chips

One chip.  G11, G12 and G13 (800-spin toroidal ±1 twins, the launcher's
Table-II hyperparameters, one stacked group in bucket 1024) and K2000
(complete ±1, 2000 spins, ``hp='auto'`` from the same base with its
iteration budget cut to ``K2000_M_SHOT``, bucket 2048) are solved through
``AnnealService.solve`` on ``backend='pallas'`` with packed storage, once
per field mode: 'dense' runs the streamed-noise f32 kernel, 'popcount' the
XNOR-popcount chain kernel.  The service's fallback is off, so a kernel that
fails is an error, not a rerun on XLA.  Every response must be
``status='ok'`` with no fallback event, from programs whose compiled text
launches the resident kernel (``tpu_custom_call``); every returned spin
vector's cut must equal a numpy cut over the edge list; best cuts and spins
must equal the same requests on ``backend='sparse'`` bit for bit; and the
G11-class requests, submitted through ``StreamingAnnealService``, must
equal the one-shot results.

Four chips.  The G81-class instance (20000 spins, popcount field, iteration
budget cut to ``G81_M_SHOT``) spin-sharded over a 4-device mesh must equal
the same request on a 1-device mesh bit for bit, with the problem and engine
state spread over all four devices at about a quarter of the one-device
bytes on the busiest one.

Each phase prints one line per instance.  The last line of a passing run is
one JSON object naming the device.  Any failure, or a JAX that finds no
TPU, exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# The launcher's defaults: Table II with the trial and shot budget cut.
TABLE_II = dict(n_trials=16, m_shot=20, n_rnd=2, i0_min=1, i0_max=32,
                tau=100, beta_shift=1)
G_SET = ("G11", "G12", "G13")
# Depth cuts: the sparse reference gathers all 1999 neighbours of every
# K2000 spin per cycle, and G81's one-device reference contracts 32768 rows.
K2000_M_SHOT = 2
G81_M_SHOT = 2
FIELD_MODES = ("dense", "popcount")


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def smoke_service(backend: str = "pallas", field_mode: str = "dense", *,
                  faults=None, **kw):
    """The service as this script runs it: xorshift noise, packed storage,
    and no fallback chain, so a failing backend raises."""
    from repro.serve import AnnealService, ResiliencePolicy

    opts = {} if backend == "sparse" else {"field_mode": field_mode}
    return AnnealService(backend=backend, noise="xorshift",
                         storage_layout="packed", backend_opts=opts,
                         resilience=ResiliencePolicy(fallback=False),
                         faults=faults, **kw)


def smoke_requests(g_problems, auto_problems, hp, auto_base):
    """Fixed-hp requests (one stacked group) plus hp='auto' requests."""
    from repro.serve import AnnealRequest

    reqs = [AnnealRequest(problem=p, hp=hp, seed=i)
            for i, p in enumerate(g_problems)]
    reqs += [AnnealRequest(problem=p, hp="auto", auto_base=auto_base,
                           seed=len(reqs) + i)
             for i, p in enumerate(auto_problems)]
    return reqs


def numpy_cuts(problem, spins) -> np.ndarray:
    """Cut of each spin row, straight from the edge list."""
    s = np.asarray(spins)
    i, j = problem.edges[:, 0], problem.edges[:, 1]
    return (s[:, i] != s[:, j]).astype(np.int64) @ np.asarray(
        problem.weights, np.int64)


def check_response(tag: str, r, problem, backend: str) -> None:
    check(r.status == "ok", f"{tag}: status={r.status!r}")
    kinds = [e.kind for e in r.events]
    check("fallback" not in kinds, f"{tag}: fallback event {r.events}")
    check(r.backend == backend, f"{tag}: ran on {r.backend!r}, not {backend!r}")
    res = r.result
    cuts = numpy_cuts(problem, res.best_m)
    check(np.array_equal(cuts, np.asarray(res.best_cut)),
          f"{tag}: cuts {np.asarray(res.best_cut)} != numpy {cuts}")


def same_result(tag: str, a, b) -> None:
    check(np.array_equal(np.asarray(a.result.best_cut),
                         np.asarray(b.result.best_cut)),
          f"{tag}: best cuts differ")
    check(np.array_equal(np.asarray(a.result.best_m),
                         np.asarray(b.result.best_m)),
          f"{tag}: best spins differ")


def _program_stats(svc):
    """{bucket: (compile seconds, kernels launched, chunk texts)}."""
    from repro.core.engine import resident_kernel

    out = {}
    for prog in svc.programs():
        bk = prog.backend
        nb = bk.n_bucket
        comp, kern, texts = out.get(nb, (0.0, None, []))
        comp += prog.compile_s or 0.0
        if bk.name == "pallas":
            kern = resident_kernel(bk.field_mode, bk.noise_mode)
        if prog.key[-1] == "chunk" and prog.compiled is not None:
            texts = texts + [prog.compiled.as_text()]
        out[nb] = (comp, kern, texts)
    return out


def solve_phase(phase: str, svc, reqs, problems, backend: str):
    """Solve ``reqs`` in one call, check each response, print one line each.

    Where kernels are compiled (not interpreted), every pallas chunk program
    must launch one.
    """
    from repro.kernels.ssa_update import default_interpret

    responses = svc.solve(reqs)
    stats = _program_stats(svc)
    require_kernel = backend == "pallas" and not default_interpret()
    for r, p in zip(responses, problems):
        tag = f"{phase}/{p.name}"
        check_response(tag, r, p, backend)
        comp, kern, texts = stats[r.bucket]
        if require_kernel:
            check(texts and all("tpu_custom_call" in t for t in texts),
                  f"{tag}: compiled chunk program holds no tpu_custom_call")
        print(f"{phase:<16} {p.name:<12} bucket={r.bucket} "
              f"backend={r.backend} kernel={kern or 'xla'} "
              f"compile_s={comp:.2f} solve_s={r.wall_s - comp:.2f} "
              f"best_cut={int(np.max(r.result.best_cut))}", flush=True)
    return responses


def run_one_chip(g_problems, auto_problems, hp, auto_base) -> None:
    """Every one-chip phase: pallas per field mode, the sparse reference and
    the stream, with their checks."""
    from repro.serve import StreamingAnnealService, StreamPolicy

    problems = list(g_problems) + list(auto_problems)
    reqs = smoke_requests(g_problems, auto_problems, hp, auto_base)
    ref = solve_phase("sparse", smoke_service("sparse"), reqs, problems,
                      "sparse")
    one_shot = {}
    for mode in FIELD_MODES:
        got = solve_phase(f"pallas/{mode}", smoke_service("pallas", mode),
                          reqs, problems, "pallas")
        for a, b, p in zip(got, ref, problems):
            same_result(f"pallas/{mode}/{p.name} vs sparse", a, b)
        one_shot[mode] = got
        print(f"pallas/{mode:<9} bit-identical to sparse: "
              f"{len(problems)}/{len(problems)}", flush=True)

    stream = StreamingAnnealService(
        service=smoke_service("pallas", "dense"),
        policy=StreamPolicy(slots_per_table=4))
    n_g = len(g_problems)
    t0 = time.perf_counter()
    tickets = [stream.submit(r) for r in reqs[:n_g]]
    stream.run_until_idle()
    wall = time.perf_counter() - t0
    for t, a, p in zip(tickets, one_shot["dense"], g_problems):
        r = t.result(timeout=0)
        check_response(f"stream/{p.name}", r, p, "pallas")
        same_result(f"stream/{p.name} vs one-shot", r, a)
    print(f"stream/dense     {n_g} G-set requests in {wall:.2f}s "
          f"bit-identical to one-shot: {n_g}/{n_g}", flush=True)


def run_four_chip(problem, hp) -> None:
    """The spin-sharded G81-class solve on four devices vs one device."""
    import jax

    from repro.core import memory
    from repro.serve import AnnealRequest
    from repro.sharding import spin_mesh

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")
    req = AnnealRequest(problem=problem, hp=hp, seed=81)
    model = problem.to_ising()
    out, busiest = {}, {}
    for n_dev in (4, 1):
        svc = smoke_service("dense", "popcount", partition="spin",
                            mesh=spin_mesh(n_dev))
        t0 = time.perf_counter()
        r = svc.solve([req])[0]
        wall = time.perf_counter() - t0
        check_response(f"spin{n_dev}/{problem.name}", r, problem, "dense")
        comp = sum(p.compile_s or 0.0 for p in svc.programs())
        bk = svc.programs()[0].backend
        problem_arrays = bk.stack([model])
        state = bk.init_state(problem_arrays,
                              bk.init_noise([req.seed], [model.n]))
        per = {k: v for k, v in
               memory.per_device_bytes((problem_arrays, state)).items()
               if k != "host"}
        check(len(per) == n_dev,
              f"spin{n_dev}: state on {len(per)} devices, want {n_dev}")
        busiest[n_dev] = max(per.values())
        out[n_dev] = r
        print(f"spin/{n_dev}-device    {problem.name:<12} bucket={r.bucket} "
              f"backend={r.backend} field=popcount compile_s={comp:.2f} "
              f"solve_s={wall - comp:.2f} "
              f"best_cut={int(np.max(r.result.best_cut))} "
              f"busiest_device_MiB={busiest[n_dev] / 2**20:.1f}", flush=True)
    same_result(f"spin4 vs spin1 {problem.name}", out[4], out[1])
    ratio = busiest[4] / busiest[1]
    check(0.2 <= ratio <= 0.3,
          f"busiest device holds {ratio:.3f} of the one-device bytes")
    print(f"spin/4-device    bit-identical to 1-device: yes; busiest-device "
          f"bytes {ratio:.3f} of 1-device", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the spin-sharded four-chip phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import SSAHyperParams, gset
    from repro.launch.compile_cache import (
        compile_cache_stats,
        enable_compile_cache,
    )

    enable_compile_cache()
    hp = SSAHyperParams(**TABLE_II)
    try:
        if args.chips == 4:
            run_four_chip(gset.load("G81"),
                          SSAHyperParams(**dict(TABLE_II, m_shot=G81_M_SHOT)))
        else:
            run_one_chip([gset.load(n) for n in G_SET], [gset.load("K2000")],
                         hp, SSAHyperParams(**dict(TABLE_II,
                                                   m_shot=K2000_M_SHOT)))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    cache = compile_cache_stats()
    print(f"compile cache {cache['dir']}: {cache['hits']} hits, "
          f"{cache['misses']} misses", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
