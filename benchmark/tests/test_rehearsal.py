"""Each cell rehearsed end to end on the CPU at a tiny size, and the
faults of the timed path that ``correct`` has to catch.

The runs skip the harness's look for a chip (``require_chip=False``),
so no number here is a device number."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import control
import harness

TINY = {"points_per_request": 1 << 16}
with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SEED = 2**31 + 77


def rehearse(cell, trace=False, wrap=None, overrides=TINY):
    out = harness.run_cell(cell, SEED, 1.0, trace, time.perf_counter(),
                           require_chip=False, wrap=wrap,
                           overrides=overrides)
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reaches_result_line(cell):
    line = rehearse(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "join_pts_per_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_layer_metrics():
    line = rehearse("nyc_taxi_h3r9.bulk", trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-read metrics stay silent
    assert {"tessellate_s", "index_build_s", "uncertain_frac",
            "recheck_s_per_mpt"} <= set(line["metrics"])
    assert "device_ns_per_pt" not in line["metrics"]


def stale(run):
    last = {}

    def broken(pts):
        z, n = run(pts)
        out = last.get("z", z)
        last["z"] = z
        return (out if len(out) == len(z) else z * 0 - 1), n
    return broken


def half_left_out(run):
    def broken(pts):
        z, n = run(pts[:len(pts) // 2])
        return np.r_[z, np.full(len(pts) - len(z), -1, z.dtype)], n
    return broken


def altered(run):
    def broken(pts):
        z, n = run(pts)
        z = z.copy()
        z[::97] += 1
        return z, n
    return broken


@pytest.mark.parametrize("cell,fault", [
    ("nyc_taxi_h3r9.bulk", stale),
    ("nyc_taxi_h3r9.bulk", half_left_out),
    ("nyc_taxi_h3r9.bulk", altered),
])
def test_fault_is_not_correct(cell, fault):
    line = rehearse(cell, wrap=fault)
    assert line["correct"] is False
    assert line["checks"]["mismatched_answers"]["value"] > 0 or \
        line["checks"]["failed_requests"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The float32 reference in the program's place, through the
    harness's own check, at the cell's request size."""
    big = {"points_per_request":
           harness.load_cell(cell)[3]["points_per_request"]}
    line = harness.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                            require_chip=False, overrides=big,
                            wrap=control.float32_in_place(cell, SEED, big))
    assert line["correct"] is False
    assert line["checks"]["mismatched_answers"]["value"] > 0


def test_command_without_a_chip_prints_no_result():
    run = os.path.join(harness.BENCH_DIR, "run.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run(
        [sys.executable, run, "--workload", "nyc_taxi_h3r9.bulk", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert got.returncode != 0 and got.stdout == ""
    assert "accelerator" in got.stderr
