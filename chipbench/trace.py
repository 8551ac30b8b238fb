"""Reduce a JAX profiler trace to what the per-layer metrics read.

A trace is first normalised to plain data::

    {"planes": {plane name: {line name: [[event name, start_ns, dur_ns], ...]}}}

either from the ``.xplane.pb`` the profiler writes (:func:`load`: device
planes, and of the host only the harness's ``chipbench.*`` spans) or from a
gzipped JSON dump of that form (the test fixture).  :func:`reduce` then
works on the normalised form only:

* the window is the host span ``chipbench.window`` that the harness places
  around its measured loop;
* each device's busy time is the union of its ``XLA Ops`` events inside
  the window; idle is the rest of the window;
* per-op and per-module (``XLA Modules``) device seconds are summed by name;
* each idle gap is attributed to the innermost ``chipbench.*`` host span
  open at its midpoint (``host: none`` where the harness had none open).

Device figures are means over the devices the cell uses.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
_DEVICE_RE = re.compile(r"^/device:(TPU|GPU):(\d+)$")

Interval = Tuple[int, int]


def load(path: str) -> dict:
    """Normalised trace from an ``.xplane.pb`` file, a directory holding one,
    or a ``.json.gz`` dump."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        host = plane.name.startswith("/host:CPU")
        if not (host or _DEVICE_RE.match(plane.name)):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                evs.append([short_name(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)])
            if evs:
                lines[line.name] = evs
    return {"planes": planes}


def short_name(name: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name (``%fusion.3 = (...) ...`` -> ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def dump(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def device_planes(tr: dict) -> List[str]:
    found = []
    for name in tr["planes"]:
        m = _DEVICE_RE.match(name)
        if m:
            found.append((int(m.group(2)), name))
    return [name for _, name in sorted(found)]


def _line(plane: dict, key: str) -> list:
    return plane.get(key, [])


def host_spans(tr: dict) -> List[Tuple[str, int, int]]:
    out = []
    for pname, plane in tr["planes"].items():
        if not pname.startswith("/host:"):
            continue
        for evs in plane.values():
            for name, start, dur in evs:
                if name.startswith(SPAN_PREFIX):
                    out.append((name, int(start), int(start) + int(dur)))
    return sorted(out, key=lambda s: s[1])


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(evs, lo: int, hi: int):
    for name, start, dur in evs:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def _attribute(gaps: Sequence[Interval], spans) -> Dict[str, int]:
    """Idle ns per innermost harness span open at each gap's midpoint."""
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    starts = [s[1] for s in inner]
    out: Dict[str, int] = collections.Counter()
    for a, b in gaps:
        mid = (a + b) // 2
        k = bisect.bisect_right(starts, mid)
        name = "host: none"
        # Nested spans: the innermost open one is the latest started.
        for i in range(k - 1, -1, -1):
            if inner[i][2] >= mid:
                name = inner[i][0]
                break
        out[name] += b - a
    return out


def reduce(tr: dict, n_devices: Optional[int] = None,
           window: Optional[Interval] = None) -> dict:
    """Busy, idle, per-op, per-module and idle-gap figures."""
    spans = host_spans(tr)
    devices = device_planes(tr)
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("trace holds no device plane")
    if window is None:
        wins = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
        if wins:
            window = wins[0]
        else:
            ops = [(s, s + d) for p in devices
                   for _, s, d in _line(tr["planes"][p], "XLA Ops")]
            window = (min(a for a, _ in ops), max(b for _, b in ops))
    lo, hi = window
    n = len(devices)
    busy_ns = 0
    op_ns: Dict[str, float] = collections.Counter()
    mod_ns: Dict[str, float] = collections.Counter()
    idle_by: Dict[str, float] = collections.Counter()
    for p in devices:
        plane = tr["planes"][p]
        ops = list(_clip(_line(plane, "XLA Ops"), lo, hi))
        busy = union([(a, b) for _, a, b in ops])
        busy_ns += total(busy)
        for name, a, b in ops:
            op_ns[name] += (b - a) / n
        for name, a, b in _clip(_line(plane, "XLA Modules"), lo, hi):
            mod_ns[name] += (b - a) / n
        gaps = subtract([(lo, hi)], busy)
        for name, ns in _attribute(gaps, spans).items():
            idle_by[name] += ns / n
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n / 1e9
    return {
        "devices": devices,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_s": window_s - busy_s,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "module_s": {k: v / 1e9 for k, v in mod_ns.items()},
        "idle_by_span_s": {k: v / 1e9 for k, v in idle_by.items()},
    }


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[name, sec] for name, sec in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
