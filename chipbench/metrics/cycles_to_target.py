"""Engine (``core/engine.py``): annealing cycles a request ran before it
stopped, mean over the window's answered requests.

``chunks_run`` of each response times the cycles of one chunk (one shot of
the schedule).  A count fixed by the instance, the hyperparameters and the
seeds: it repeats exactly for one seed over the same requests.
"""


def read(ctx):
    runs = [r["chunks"] for r in ctx["records"] if r["resp"] is not None
            and r["resp"].result is not None]
    if not runs:
        return None
    return sum(runs) / len(runs) * ctx["hp"].cycles_per_shot
