"""The request generator: one fixed multiset of sizes per block, ordered
by the seed, and the same stream for the same seed."""

import itertools
import statistics

import pytest

from bench import traffic
from bench.tests.tiny import BENCH


def _mix(name):
    return traffic.Mix.load(BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", ["offline-b256", "offline-b64",
                                  "online-mixed"])
def test_stream_repeats_for_a_seed(name):
    mix = _mix(name)
    for seed in (0, 7, 2**31 + 12345, 2**40 + 3):
        a = list(itertools.islice(traffic.requests(mix, seed), 300))
        b = list(itertools.islice(traffic.requests(mix, seed), 300))
        assert a == b
        assert all(0 <= o <= mix.pool_images - s for s, o in a)


def test_seeds_reorder_the_same_block():
    mix = _mix("online-mixed")
    n = mix.block
    a = [s for s, _ in itertools.islice(traffic.requests(mix, 1), n)]
    b = [s for s, _ in itertools.islice(traffic.requests(mix, 2), n)]
    assert a != b and sorted(a) == sorted(b) == mix.block_sizes()


def test_online_mix_is_inverse_size():
    sizes = _mix("online-mixed").block_sizes()
    assert len(sizes) == 2048 and min(sizes) == 1 and max(sizes) == 64
    assert statistics.mean(sizes) == pytest.approx(13.49, abs=0.05)
    assert sum(s > 32 for s in sizes) / len(sizes) == pytest.approx(
        0.1445, abs=0.005)
    assert sizes.count(1) == pytest.approx(2 * sizes.count(2), abs=1)
    buckets = [1 << (s - 1).bit_length() for s in sizes]
    assert 1 - sum(sizes) / sum(buckets) == pytest.approx(0.25, abs=0.01)


def test_offline_mixes_are_one_size():
    assert _mix("offline-b256").distinct_sizes() == [256]
    assert _mix("offline-b64").distinct_sizes() == [64]


def test_bad_mix_is_refused(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"loop": "open", "in_flight": 1, "sizes": {"1": 1}, '
                    '"block": 4, "pool_images": 4, "check_requests": 1}')
    with pytest.raises(ValueError):
        traffic.Mix.load(path)
