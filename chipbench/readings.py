#!/usr/bin/env python3
"""Readings that set the check's limits: several seeds of one cell, on
the chip, in one process.

    python3 chipbench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control 1] [--fault <name>] [--dump <dir>]

Each seed is one whole run of the harness (weights, warm-up, sessions,
window, check).  ``--control 1`` puts the reference at the lower
precision in the program's place for the check, which must then come out
not correct; ``--fault`` plants a fault of ``chipbench/faults.py`` under
the timed path.  One JSON line per seed: the seed, ``correct`` and the
numbers compared beside their limits.  ``--dump`` writes each seed's
per-token gaps there.  The benchmark's own runs (``run.py``) never do
any of this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    harness = run.prepare()
    if harness is None:
        return 2
    from chipbench.faults import FAULTS
    fault = FAULTS[args.fault] if args.fault else None
    t = T_START
    for seed in args.seeds:
        dump = (None if args.dump is None
                else f"{args.dump}/{args.workload}.{seed}.json")
        out = harness.run(run.ROOT, args.workload, seed, args.seconds, False,
                          t_start=t, fault=fault,
                          control=bool(args.control), dump=dump)
        print(json.dumps({"seed": seed, "control": bool(args.control),
                          "fault": args.fault, "correct": out["correct"],
                          "check": out["check"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
