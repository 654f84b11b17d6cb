"""Seconds of the one ``tessellate`` call in set-up (host clock)."""


def read(r):
    return r["timers"].get("tessellate_s")
