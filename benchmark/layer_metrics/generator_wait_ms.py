"""Milliseconds a request waited, in the window, for the benchmark's own
point generator (``Feeder.get``): above 0 by more than noise, the
generator and not the program sets the pace.  The harness's summed
waits over the window, per request."""


def read(r):
    s = r.get("generator_wait_s")
    if s is None or not r["requests"]:
        return None
    return s / r["requests"] * 1e3
