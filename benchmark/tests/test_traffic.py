"""The feeder hands out requests in index order, each the same bit for
bit as ``Mix.points`` makes it alone, whichever worker made it."""

import sys
import threading
import time

import pytest

import traffic

SEED = 3 * 2**31 + 11
MIXES = {
    "one_box": [{"weight": 1.0, "bbox": "config"}],
    "two_components": [{"weight": 0.85, "bbox": [0, 0, 1, 1]},
                       {"weight": 0.15, "bbox": "config"}],
}


def mix_of(dist, **spec):
    return traffic.Mix({"points_per_request": 4096, "distribution": dist,
                        "loop": {"kind": "closed", "clients": 1},
                        "queue_depth": 2, **spec}, [0, 0, 10, 10])


def feeders_alive():
    return [t for t in threading.enumerate()
            if t.name.startswith("bench-feeder")]


@pytest.mark.parametrize("workers", [traffic.WORKERS, 16])
@pytest.mark.parametrize("dist", list(MIXES.values()), ids=list(MIXES))
def test_requests_in_order_and_bit_identical(dist, workers, monkeypatch):
    """With the committed worker count, and with more workers than
    cores and a short switch interval, so that a lost update shows."""
    assert traffic.WORKERS > 1
    monkeypatch.setattr(traffic, "WORKERS", workers)
    mix = mix_of(dist)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    got, same = [], []
    try:
        feeder = traffic.Feeder(mix, SEED)
        feeder.fill()
        for _ in range(16):
            # a request's points hold until the next get
            i, pts = feeder.get()
            want = mix.points(SEED, i)
            got.append(i)
            same.append(pts.dtype == want.dtype and pts.shape == want.shape
                        and pts.tobytes() == want.tobytes())
        feeder.close()
    finally:
        sys.setswitchinterval(interval)
    assert got == list(range(16))
    assert all(same), same
    assert len(feeder.waited) == 16
    assert not feeders_alive()


def test_look_ahead_is_bounded():
    feeder = traffic.Feeder(mix_of(MIXES["one_box"], queue_depth=3), SEED)
    try:
        feeder.fill()
        with feeder.cv:
            assert all(k in feeder.ready for k in range(3))
        feeder.get()
        ahead = 3 + traffic.WORKERS
        with feeder.cv:
            # depth waiting and one in progress a worker, no more
            assert feeder.cv.wait_for(lambda: len(feeder.ready) == ahead,
                                      timeout=30)
            assert feeder.claimed - feeder.taken == ahead
        time.sleep(0.05)
        with feeder.cv:
            assert feeder.claimed - feeder.taken == ahead
    finally:
        feeder.close()
    assert not feeders_alive()


def test_a_failing_generator_fails_get():
    mix = mix_of(MIXES["one_box"])

    def broken(seed, index, *a, **k):
        raise MemoryError("no room")
    mix.points = broken
    feeder = traffic.Feeder(mix, SEED)
    feeder.fill(timeout=5)
    with pytest.raises(RuntimeError, match="feeder failed"):
        feeder.get()
    feeder.close()
    assert not feeders_alive()
