"""Closed loop of one sweep client: the next ``solve()`` list goes out when
the previous one returns.

Warm-up sends one list of the cell's size whose targets the first chunk
reaches, so it builds and loads the same programs as the window's lists
while running one chunk.  The window closes at the first ``solve()``
return after ``seconds``, so every request it counts is whole.
"""
from __future__ import annotations

import time
from typing import List

from chipbench import harness, traffic


def run(svc, problems, hp, mix, targets, seed, seconds, win) -> dict:
    hp_prog = harness.program_hp(hp)
    with harness.span("chipbench.requests"):
        warm = [harness.program_request(problems, hp_prog, r)
                for r in traffic.warmup_list(mix, targets)]
    svc.solve(warm)
    lists = traffic.closed_lists(mix, targets, seed)
    records: List[dict] = []
    calls_s: List[float] = []
    stats0 = dict(svc.stats)
    win.open()
    try:
        t0 = time.perf_counter()
        with harness.span("chipbench.window"):
            while True:
                t_call = time.perf_counter()
                with harness.span("chipbench.requests"):
                    reqs = next(lists)
                    batch = [harness.program_request(problems, hp_prog, r)
                             for r in reqs]
                with harness.span("chipbench.solve"):
                    resps = svc.solve(batch)
                records.extend(harness.record(r, x, hp)
                               for r, x in zip(reqs, resps))
                now = time.perf_counter()
                calls_s.append(now - t_call)
                if now - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
    finally:
        win.close()
    return {"records": records, "window_s": window_s, "calls_s": calls_s,
            "stats0": stats0, "stats1": dict(svc.stats)}
