"""Milliseconds a request spends in ``stream()`` before its first
compute dispatch: the host work before the device has anything to do.
The program's counter ``pipeline/head_s`` over the window, per request."""


def read(r):
    s = r["counters"].get("pipeline/head_s")
    if s is None or not r["requests"]:
        return None
    return s / r["requests"] * 1e3
