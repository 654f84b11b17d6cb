"""Share of the window's points that the device flagged for the float64
host recheck: the rechecked counts that ``run()`` returns, over the
points."""


def read(r):
    if not r["points"]:
        return None
    return r["rechecked"] / r["points"]
