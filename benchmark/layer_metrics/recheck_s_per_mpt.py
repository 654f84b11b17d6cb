"""Host recheck seconds per million points: the program's counter
``pip_join/recheck_s`` over the window (single-chip streamed path)."""


def read(r):
    s = r["counters"].get("pip_join/recheck_s")
    if s is None or not r["points"]:
        return None
    return s / (r["points"] * 1e-6)
