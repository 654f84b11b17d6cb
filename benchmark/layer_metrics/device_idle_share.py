"""Share of the traced window in which a device ran nothing: 1 - busy
over window, averaged over the cell's devices."""


def read(r):
    t = r["trace"]
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
