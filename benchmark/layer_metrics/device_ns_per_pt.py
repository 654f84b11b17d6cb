"""Device busy nanoseconds per point joined in the traced window: the
union of each device's operation intervals, summed over the cell's
devices, over the points of the requests completed."""


def read(r):
    t = r["trace"]
    if not t or not r["points"] or not t["busy_s"]:
        return None
    return sum(t["busy_s_by_device"].values()) / r["points"] * 1e9
