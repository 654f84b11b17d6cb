"""Milliseconds a request's dispatch loop spends staging chunks (origin
shift, f32 cast, async ``device_put``), mostly overlapped with the
device.  The program's counter ``pipeline/put_s`` over the window, per
request."""


def read(r):
    s = r["counters"].get("pipeline/put_s")
    if s is None or not r["requests"]:
        return None
    return s / r["requests"] * 1e3
