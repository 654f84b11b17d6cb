"""Milliseconds a request spends in ``stream()`` after its last chunk's
fetch returned: the last consume and the worker's join, with the
device done.  The program's counter ``pipeline/tail_s`` over the
window, per request."""


def read(r):
    s = r["counters"].get("pipeline/tail_s")
    if s is None or not r["requests"]:
        return None
    return s / r["requests"] * 1e3
