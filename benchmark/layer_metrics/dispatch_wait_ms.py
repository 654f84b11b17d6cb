"""Milliseconds a request's dispatch loop spends blocked on the oldest
fetch, ahead of the device and waiting for it.  The program's counter
``pipeline/wait_s`` over the window, per request."""


def read(r):
    s = r["counters"].get("pipeline/wait_s")
    if s is None or not r["requests"]:
        return None
    return s / r["requests"] * 1e3
