"""Median over the traced requests of the wall time in which no device
ran an operation (the request's span less the device's busy time inside
it, mean over devices), in milliseconds."""

import statistics


def read(r):
    t = r["trace"]
    if not t or not t["request_host_s"] or not t["busy_s"]:
        return None
    return statistics.median(t["request_host_s"]) * 1e3
