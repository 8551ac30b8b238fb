"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload gset800.batch --seed 7 --seconds 20 --trace 0

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, and with ``--trace 1``
``breakdown``); the numbers compared for ``correct`` are the last lines of
standard error and the last key of that object.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.

``--control <variant>`` puts a variant of the reference in the program's
place in the comparison (see ``chipbench/reference.py``): such a run must
come out not correct.  ``--keep-trace <file.json.gz>`` keeps the reduced
trace's source events of a ``--trace 1`` run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            control=args.control, keep_trace=args.keep_trace,
            t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["check"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
