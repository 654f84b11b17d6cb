"""Seconds of the one ``build_pip_index`` call in set-up (host clock)."""


def read(r):
    return r["timers"].get("index_build_s")
