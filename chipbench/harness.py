"""Run one cell of the chip benchmark once.

Everything that belongs to one cell is found by name from files of its
own: ``BENCHMARK.json`` names the cell's configuration and traffic mix;
the configuration is ``chipbench/configs/<config>.json`` (instances, frozen
targets, hyperparameters, service options), the mix is
``chipbench/traffic/<traffic>.json``, sent by the client loop
``chipbench/loops/<kind>.py`` its ``kind`` names, and each metric, end to
end or per layer, is read by ``chipbench/metrics/<metric>.py``.  A run:

1. names its device and refuses anything but a TPU with enough chips;
2. set-up (``setup_s``, from process start): builds the instances from
   the configuration, the service, the traffic from ``--seed``, and warms
   exactly the cell's shapes with a fixed warm-up, compiles served from
   the persistent cache in ``<checkout>/.jax_cache``;
3. the window: the client loop, with ``chipbench.*`` host spans around
   each call and the instance handling; compiles inside the window are
   counted (there should be none);
4. reads the peak device memory, frees the service, and compares a sample
   of the window's answers, drawn from the seed, with the plain reference
   (:mod:`chipbench.reference`): every compared request must match
   exactly in its stop chunk, per-chunk best cuts, per-trial best cuts and
   best spins;
5. prints the numbers compared beside their limits (last lines of standard
   error) and one JSON result line (last line of standard output).
"""
from __future__ import annotations

import collections
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import instances as inst_mod
from . import reference, score, traffic
from . import trace as trace_mod
from .peaks import peaks_for

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CHUNK_MODULE = "chunk_fn"   # the service's jitted chunk program
WAIT_AFTER_CLOSE_S = 60.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------
def load_bench(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(root: Path, entry: dict) -> dict:
    return load_json(root / entry["file"])


def load_mix(here: Path, name: str) -> dict:
    return load_json(here / "traffic" / f"{name}.json")


def load_module(here: Path, kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module."""
    path = here / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(here: Path, name: str):
    return load_module(here, "metrics", name).read


def cell_metrics(bench: dict, cell_name: str, section: str) -> List[dict]:
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def hyperparams(cfg: dict) -> reference.HyperParams:
    return reference.HyperParams(**cfg["hyperparams"])


# ---------------------------------------------------------------------------
# JAX set-up: compile cache, compile counting, device
# ---------------------------------------------------------------------------
def enable_cache(root: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded from the cache while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.counts: Dict[str, int] = collections.Counter()

        def on_event(event, **_kw):
            if self.armed and event.startswith("/jax/compilation_cache/cache_"):
                self.counts[event.rsplit("/", 1)[-1]] += 1

        def on_duration(event, duration, **_kw):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.counts["backend_compile"] += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    @property
    def total(self) -> int:
        return self.counts["backend_compile"] + self.counts["cache_hits"]


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX platform is {devs[0].platform!r}, not 'tpu'")
    if require_tpu and len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def build_service(svc_cfg: dict):
    from repro.serve import AnnealService

    return AnnealService(backend=svc_cfg["backend"], noise=svc_cfg["noise"],
                         storage_layout=svc_cfg["storage_layout"],
                         backend_opts={"field_mode": svc_cfg["field_mode"]},
                         partition=svc_cfg["partition"])


def program_hp(hp: reference.HyperParams):
    from repro.core import SSAHyperParams

    return SSAHyperParams(**vars(hp))


def program_request(problems, hp_prog, req: traffic.Req):
    from repro.serve import AnnealRequest

    return AnnealRequest(problem=problems[req.instance], hp=hp_prog,
                         seed=req.seed, target_cut=req.target)


def record(req: traffic.Req, resp, hp: reference.HyperParams) -> dict:
    ok = resp is not None and resp.status == "ok" and resp.result is not None
    best = int(np.max(np.asarray(resp.result.best_cut))) if ok else None
    if not ok:
        reached = False
    elif req.target is None:
        reached = resp.chunks_run == hp.m_shot
    else:
        reached = best >= req.target
    return {"req": req, "resp": resp, "ok": ok, "reached": reached,
            "best": best, "chunks": resp.chunks_run if resp is not None else 0}


class Window:
    """What happens at the edges of the measured window: the end of set-up,
    the compile counter, and the profiler in a traced run."""

    def __init__(self, t_start: float, trace_dir: Optional[str]):
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.setup_s: Optional[float] = None
        self.counter = CompileCounter()

    def open(self):
        # Set-up's garbage is collected in set-up, not inside the window.
        gc.collect()
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace_dir:
            import jax

            # Host spans come from the harness's annotations; tracing every
            # Python call would slow the host it measures.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.counter.armed = True

    def close(self):
        self.counter.armed = False
        if self.trace_dir:
            import jax

            jax.profiler.stop_trace()


def loop_runner(here: Path, kind: str):
    """The client loop ``chipbench/loops/<kind>.py``."""
    return load_module(here, "loops", kind).run


# ---------------------------------------------------------------------------
# Correctness: a sample of the window's answers against the reference
# ---------------------------------------------------------------------------
def sample(records: List[dict], k: int, seed: int) -> List[dict]:
    """``k`` records drawn from the seed, the longest-running among them."""
    answered = [r for r in records if r["resp"] is not None]
    if len(answered) <= k:
        return answered
    rng = np.random.default_rng([int(seed) % 2 ** 64, 7])
    longest = max(range(len(answered)), key=lambda i: answered[i]["chunks"])
    rest = [i for i in range(len(answered)) if i != longest]
    pick = [longest] + list(rng.choice(rest, size=k - 1, replace=False))
    return [answered[i] for i in sorted(pick)]


def _matches(resp, out: reference.Outcome) -> bool:
    res = resp.result
    return (res is not None
            and resp.chunks_run == out.chunks
            and [int(v) for v in resp.chunk_best_cut] == out.trace
            and np.array_equal(np.asarray(res.best_cut, np.int64), out.best_cut)
            and np.array_equal(np.asarray(res.best_m, np.int8), out.best_m))


def compare(picked: List[dict], insts, hp: reference.HyperParams,
            variant: str = "exact") -> dict:
    """Mismatches of the picked answers against the reference.

    With ``variant`` other than 'exact', that variant of the reference takes
    the program's place (the control) and is compared with the exact
    reference in the same way.
    """
    exact: Dict[int, reference.Outcome] = {}
    other: Dict[int, reference.Outcome] = {}
    cache: Dict = {}
    by_inst = collections.defaultdict(list)
    for i, rec in enumerate(picked):
        if rec["resp"].result is not None:
            by_inst[rec["req"].instance].append(i)
    for k, idx in by_inst.items():
        args = ([picked[i]["req"].seed for i in idx],
                [picked[i]["req"].target for i in idx])
        exact.update(zip(idx, reference.solve(insts[k], hp, *args,
                                              shot_fns=cache)))
        if variant != "exact":
            other.update(zip(idx, reference.solve(
                insts[k], hp, *args, variant=variant, shot_fns=cache)))
    mismatched = unanswered = 0
    for i, rec in enumerate(picked):
        resp = rec["resp"]
        if resp.result is None:
            unanswered += 1
        elif variant == "exact":
            mismatched += not _matches(resp, exact[i])
        else:
            mismatched += not _same_outcome(other[i], exact[i])
    return {"compared": len(picked), "mismatched": mismatched,
            "unanswered": unanswered}


def _same_outcome(a: reference.Outcome, b: reference.Outcome) -> bool:
    return (a.chunks == b.chunks and a.trace == b.trace
            and np.array_equal(a.best_cut, b.best_cut)
            and np.array_equal(a.best_m, b.best_m))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, here: Path = HERE, control: Optional[str] = None,
             require_tpu: bool = True, keep_trace: Optional[str] = None,
             t_start: Optional[float] = None, log=None) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = load_bench(root)
    cell, cfg_entry = find_cell(bench, cell_name)
    cfg = load_config(root, cfg_entry)
    mix = load_mix(here, cell["traffic"])
    chips = int(cell["chips"])

    device = device_info(chips, require_tpu)
    enable_cache(root)

    insts = [inst_mod.make(s) for s in cfg["instances"]]
    targets = [int(s["target_cut"]) for s in cfg["instances"]]
    hp = hyperparams(cfg)
    problems = [inst_mod.to_program(i) for i in insts]
    svc = build_service(cfg["service"])
    loop = loop_runner(here, mix["kind"])

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    win = Window(t_start, tmp)
    out = loop(svc, problems, hp, mix, targets, seed, seconds, win)
    records, window_s = out["records"], out["window_s"]
    device["memory_peak_bytes"] = memory_peak(chips)
    st0, st1 = out["stats0"], out["stats1"]
    counters = {k: st1.get(k, 0) - st0.get(k, 0) for k in st1}
    counter = win.counter
    log(f"compiles_in_window: {counter.total} {dict(counter.counts)}")

    red = None
    if trace:
        tr = trace_mod.load(tmp)
        if keep_trace:
            trace_mod.dump(tr, keep_trace)
        shutil.rmtree(tmp, ignore_errors=True)
        red = trace_mod.reduce(tr, n_devices=chips)
        del tr

    backends = collections.Counter(
        r["resp"].backend for r in records if r["resp"] is not None)
    del svc
    gc.collect()

    picked = sample(records, int(cfg["check_samples"]), seed)
    chk = compare(picked, insts, hp, variant=control or "exact")
    checks = {
        "mismatched": {"value": chk["mismatched"], "limit": 0},
        "unanswered": {"value": chk["unanswered"], "limit": 0},
        "compared": {"value": chk["compared"], "min": 1},
    }
    correct = (chk["mismatched"] == 0 and chk["unanswered"] == 0
               and chk["compared"] >= 1)

    ctx = {"records": records, "window_s": window_s, "setup_s": win.setup_s,
           "counters": counters, "hp": hp, "instances": insts, "trace": red,
           "chips": chips, "chunk_module": CHUNK_MODULE, "mix": mix,
           "config": cfg,
           "peaks": peaks_for(device["kind"]) if red is not None else None}
    # --trace 0 reports the cell's end-to-end metrics, --trace 1 its
    # per-layer ones; each is read by its own file.
    metrics: Dict[str, dict] = {}
    notes: Dict[str, str] = {}
    for m in cell_metrics(bench, cell_name,
                          "per_layer" if trace else "end_to_end"):
        mod = load_module(here, "metrics", m["name"])
        val = mod.read(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        note = mod.note(ctx) if hasattr(mod, "note") else None
        if note is not None:
            notes[m["name"]] = note
            log(f"{m['name']}: {note}")
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": sum(1 for r in records if not r["ok"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": trace_mod.top(red["op_s"]),
            "idle_gaps": trace_mod.top(red["idle_by_span_s"]),
        }
    if notes:
        result["notes"] = notes
    result["window"] = {
        "seconds": window_s, "compiles_in_window": counter.total,
        "solved": sum(1 for r in records if score.solved(r)),
        "calls_s": out.get("calls_s"),
        "backends": dict(backends),
        "t_total_s": time.perf_counter() - t_start,
    }
    if control:
        result["control"] = control
    result["check"] = checks
    return result
