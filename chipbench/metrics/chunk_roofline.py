"""Chunk program (resident kernel or XLA field): the least time the
chunks' algorithmic work needs on the chips used, over the device time of
the chunk programs in the trace.

Work per live lane-chunk comes from ``chipbench/work.py`` (the instance and
the hyperparameters only); the live lane-chunks are the service's
``live_lane_chunks`` counter over the traced window.  The device time is
the ``XLA Modules`` time of the service's chunk program, averaged over the
devices.  Nothing to read (no trace, no chunk module): no value.
:func:`note` says which roof binds: compute (int8 operations) or memory.
"""
from chipbench.work import lane_chunk_work, least_seconds


def _least(ctx):
    """``(least seconds, bound, chunk device seconds)`` or None."""
    red = ctx["trace"]
    if red is None:
        return None
    t_dev = sum(s for name, s in red["module_s"].items()
                if ctx["chunk_module"] in name)
    lanes = ctx["counters"].get("live_lane_chunks", 0)
    if t_dev <= 0 or not lanes:
        return None
    # Requests are spread evenly over the configuration's instances, so the
    # mean lane-chunk work over them is the work of the average lane.
    works = [lane_chunk_work(i, ctx["hp"]) for i in ctx["instances"]]
    ops = lanes * sum(w["ops"] for w in works) / len(works)
    nbytes = lanes * sum(w["bytes"] for w in works) / len(works)
    t_min, bound = least_seconds(ops, nbytes, ctx["peaks"], ctx["chips"])
    return t_min, bound, t_dev


def read(ctx):
    got = _least(ctx)
    return None if got is None else 100.0 * got[0] / got[2]


def note(ctx):
    got = _least(ctx)
    return None if got is None else f"{got[1]}-bound"
