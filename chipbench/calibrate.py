"""Set a configuration's frozen target cuts from the plain reference.

    python3 chipbench/calibrate.py chipbench/configs/gset800.json --seeds 64

Runs the reference (never the program) at the full budget on annealing
seeds ``0 .. seeds-1`` (the traffic draws its seeds from [2**20, 2**31), so
it never uses these), records each seed's best cut over trials after every
shot, and applies the rule of :func:`choose_target` per instance.  Prints
one JSON object per instance: the chosen target, the share of seeds that
reach it within the budget and the median shot at which they do.  With
``--write`` the targets and these readings go into the configuration file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REACH_SHARE = 0.95     # nearly every request reaches its target in budget
MEDIAN_SHARE = 0.25    # median demand at most a quarter of the budget


def first_reach(trace, target):
    for c, v in enumerate(trace):
        if v >= target:
            return c + 1
    return None


def choose_target(traces, m_shot: int) -> dict:
    """The largest cut that at least ``REACH_SHARE`` of the seeds reach
    within the budget and that the median seed reaches within
    ``MEDIAN_SHARE`` of it (at least one shot)."""
    import statistics

    best = None
    for t in sorted({v for tr in traces for v in tr}):
        reach = [first_reach(tr, t) for tr in traces]
        hit = [r for r in reach if r is not None]
        share = len(hit) / len(traces)
        med = statistics.median(r if r is not None else m_shot + 1
                                for r in reach)
        if share >= REACH_SHARE and med <= max(1.0, MEDIAN_SHARE * m_shot):
            best = {"target_cut": t, "reach_share": share,
                    "median_shots": med}
    if best is None:
        raise ValueError("no cut meets the rule")
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seeds", type=int, default=64)
    ap.add_argument("--group", type=int, default=16)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from chipbench import instances, reference

    with open(args.config) as f:
        cfg = json.load(f)
    hp = reference.HyperParams(**cfg["hyperparams"])
    import jax

    readings = {}
    for spec in cfg["instances"]:
        inst = instances.make(spec)
        t0 = time.perf_counter()
        traces = []
        for lo in range(0, args.seeds, args.group):
            seeds = list(range(lo, min(args.seeds, lo + args.group)))
            outs = reference.solve(inst, hp, seeds, [None] * len(seeds))
            traces += [o.trace for o in outs]
        pick = choose_target(traces, hp.m_shot)
        spec["target_cut"] = pick["target_cut"]
        readings[spec["name"]] = {
            **pick, "final_min": min(t[-1] for t in traces),
            "final_median": sorted(t[-1] for t in traces)[len(traces) // 2],
        }
        print(json.dumps({"instance": spec["name"], **readings[spec["name"]],
                          "seconds": time.perf_counter() - t0}), flush=True)
    if args.write:
        cfg["targets"] = {
            "rule": (f"largest cut that at least {REACH_SHARE:.0%} of the "
                     f"calibration seeds reach within m_shot shots and the "
                     f"median seed reaches within max(1, "
                     f"{MEDIAN_SHARE} * m_shot) shots; plain reference, "
                     f"full budget"),
            "calibration_seeds": f"0..{args.seeds - 1}",
            "device": jax.devices()[0].device_kind,
            "readings": readings,
        }
        with open(args.config, "w") as f:
            json.dump(cfg, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
