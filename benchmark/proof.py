"""Correctness readings of one cell on many seeds in one process.

    python benchmark/proof.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds <s>

Sets the cell up once (its set-up is long), then runs one measured
window per seed through the timed path, and one per control seed with
the float32 reference in the program's place (``control.py``).  Prints
one JSON line per window: the seed, which kind, ``correct`` and the
numbers compared.  The benchmark's own runs (``run.py``) never run
this; their set-up and memory peak are their own.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import control
    import harness
    runs = [(s, "program") for s in args.seeds] + \
        [(s, "control") for s in args.control_seeds]
    prep = harness.Prepared(args.workload, runs[0][0], T_PROCESS)
    for seed, kind in runs:
        wrap = control.float32_in_place(args.workload, seed) \
            if kind == "control" else None
        out = harness.measure(prep, seed, args.seconds, False, wrap)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "kind": kind, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
