"""Requests that reached their target cut, per second of the whole window
(host clock).  A request that missed its target or failed counts 0; the
window closes at a whole ``solve()`` return, so every counted request is
whole."""
from chipbench.score import solve_rate


def read(ctx):
    if not ctx["records"] or ctx["window_s"] <= 0:
        return None
    return solve_rate(ctx["records"], ctx["window_s"])
