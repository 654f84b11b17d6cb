"""The streamed entry's stage counters and the generator's wait reach
the result line of a traced run, and only of a traced one (CPU
rehearsal at a tiny size: no number here is a device number)."""

import json
import time

import pytest

import harness

CELL = "nyc_taxi_h3r9.bulk"
TINY = {"points_per_request": 1 << 16}
SEED = 2**31 + 78
STREAM = {"stream_head_ms", "stream_tail_ms", "stream_put_ms",
          "dispatch_wait_ms"}


def rehearse(trace):
    out = harness.run_cell(CELL, SEED, 1.0, trace, time.perf_counter(),
                           require_chip=False, overrides=TINY)
    return json.loads(json.dumps(out))


def test_traced_run_reports_stream_stages():
    line = rehearse(trace=True)
    assert line["correct"] is True
    assert STREAM <= set(line["metrics"])
    for name in STREAM:
        m = line["metrics"][name]
        assert m["unit"] == "ms" and m["value"] > 0, name
    wait = line["metrics"]["generator_wait_ms"]
    assert wait["unit"] == "ms" and wait["value"] >= 0


def test_untraced_run_reports_end_to_end_only():
    line = rehearse(trace=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "join_pts_per_s"}


def test_generator_wait_reader():
    record = {"requests": 8, "generator_wait_s": 0.02, "counters": {}}
    got = harness.read_layer_metric("generator_wait_ms", record)
    assert got == pytest.approx(2.5)


@pytest.mark.parametrize("record", [
    {"requests": 0, "generator_wait_s": 0.02},
    {"requests": 8},
])
def test_generator_wait_reader_silent_without_requests_or_waits(record):
    assert harness.read_layer_metric("generator_wait_ms", record) is None
