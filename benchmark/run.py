"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (imports, zone load, tessellation, index build, warm-up of the
cell's own launch shapes) is timed from process start; then the cell's
traffic is offered for ``--seconds``; then the sampled answers are
checked against the plain float64 reference.  The last line of standard
output is one JSON object; the last lines of standard error give each
number compared beside its limit.  Exits nonzero, printing no result,
when JAX finds no accelerator or fewer chips than the cell asks for.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_PROCESS)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
