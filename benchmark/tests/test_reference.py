"""The plain reference and the zone generator against the program's own
oracle and generator, at a small size on the CPU."""

import numpy as np
import pytest

import reference
import traffic
import zones as zonesets
from harness import to_geometry

NYC = {"n_side": 16, "seed": 7, "bbox": [-74.3, 40.45, -73.65, 40.95],
       "hole_every": 7, "merge_every": 11}


@pytest.fixture(scope="module")
def nyc():
    return zonesets.taxi_zones(**NYC)


def test_zones_match_program_generator(nyc):
    from mosaic_tpu.bench.workloads import taxi_zones
    ours, theirs = to_geometry(nyc), taxi_zones(16, 7)
    assert len(nyc) == 281
    np.testing.assert_array_equal(ours.coords, theirs.coords)
    np.testing.assert_array_equal(ours.ring_offsets, theirs.ring_offsets)
    np.testing.assert_array_equal(ours.part_offsets, theirs.part_offsets)
    np.testing.assert_array_equal(ours.geom_offsets, theirs.geom_offsets)


@pytest.fixture(scope="module")
def nyc_dense():
    return zonesets.taxi_zones(**NYC, detail=(6, 2))


def test_detail_gives_the_real_files_vertex_density(nyc, nyc_dense):
    """``tests/data/nyc_taxi_zones.geojson``: 11,511 vertices over 35
    TLC zones, 329 a zone; the stand-ins had 17."""
    def per_zone(zs):
        return sum(len(r) for z in zs for p in z for r in p) / len(zs)
    assert len(nyc_dense) == len(nyc) == 281
    assert per_zone(nyc) < 20 and 300 < per_zone(nyc_dense) < 360


@pytest.mark.parametrize("which", ["nyc", "nyc_dense"])
def test_reference_matches_host_truth(which, request):
    from mosaic_tpu.parallel.pip_join import pip_host_truth
    zs = request.getfixturevalue(which)
    mix = traffic.Mix({"points_per_request": 20_000,
                       "distribution": [{"weight": 1, "bbox": "config"}],
                       "loop": {"kind": "closed"}},
                      zonesets.bbox(zs))
    pts = mix.points(2**31 + 5, 0)
    want = pip_host_truth(pts, to_geometry(zs))
    np.testing.assert_array_equal(reference.Reference(zs).zones_of(pts),
                                  want)
    assert np.all(want >= 0)            # the zones tile their bbox


def test_reference_holes_and_outside():
    sq = np.array([[0, 0], [4, 0], [4, 4], [0, 4], [0, 0]], float)
    hole = np.array([[1, 1], [1, 3], [3, 3], [3, 1], [1, 1]], float)
    zones = [[[sq, hole]], [[hole[::-1]]]]
    pts = np.array([[0.5, 0.5], [2, 2], [5, 5], [3.5, 2]])
    got = reference.Reference(zones).zones_of(pts)
    np.testing.assert_array_equal(got, [0, 1, -1, 0])


def test_zone_cache_round_trip(tmp_path, nyc):
    spec = {"generator": "taxi_zones", "args": NYC}
    first = zonesets.load(spec, str(tmp_path))
    again = zonesets.load(spec, str(tmp_path))
    for a, b in zip(first, again):
        for pa, pb in zip(a, b):
            for ra, rb in zip(pa, pb):
                np.testing.assert_array_equal(ra, rb)
    assert len(list(tmp_path.iterdir())) == 1


def test_points_depend_on_seed_and_index_only():
    mix = traffic.Mix({"points_per_request": 1000,
                       "distribution": [{"weight": 0.85,
                                         "bbox": [0, 0, 1, 1]},
                                        {"weight": 0.15, "bbox": "config"}],
                       "loop": {"kind": "closed"}},
                      [0, 0, 10, 10])
    a = mix.points(3 * 2**31, 4)
    np.testing.assert_array_equal(a, mix.points(3 * 2**31, 4))
    assert not np.array_equal(a, mix.points(3 * 2**31, 5))
    share = np.mean(np.all(a <= 1, axis=1))
    assert 0.8 < share < 0.9
