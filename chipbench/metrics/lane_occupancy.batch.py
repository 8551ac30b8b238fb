"""Service batching (``AnnealService._chunk_loop``): the share of launched
lane-chunks whose request was still running, over the window.

Counters ``live_lane_chunks / slot_chunks`` of the service.  A lane that
reached its target, or a padding lane, still runs until its group ends.
"""


def read(ctx):
    c = ctx["counters"]
    slots = c.get("slot_chunks", 0)
    if not slots:
        return None
    return 100.0 * c.get("live_lane_chunks", 0) / slots
