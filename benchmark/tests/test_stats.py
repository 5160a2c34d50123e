"""Arithmetic on hand-made inputs."""
import pytest

from benchmark.harness import stats


def test_nearest_rank_and_its_sample_count():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 95) == (95, 100)
    assert stats.nearest_rank(xs, 99) == (99, 100)
    assert stats.nearest_rank([5, 1, 3], 50) == (3, 3)
    assert stats.nearest_rank([7], 99) == (7, 1)
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)


def test_block_readings_and_their_median():
    # three blocks of 2 steps of 100 tokens on 2 chips; one block hiccups
    starts, ends = [0.0, 1.0, 2.0], [1.0, 2.0, 4.0]
    got = stats.block_readings(ends, starts, k=2, tokens_per_step=100,
                               chips=2)
    assert got == [100.0, 100.0, 50.0]
    assert stats.median(got) == 100.0        # the hiccup leaves the median
    assert sum(got) / 3 < 100.0              # and would move a mean


def test_the_fact_reader_reduces_as_the_metrics_file_says():
    from benchmark.harness import manifest
    fact = manifest.load_module('readers', 'fact')
    facts = {'block_tokens_per_s_chip': [100.0, 102.0, 50.0, 100.0],
             'compiles_in_window': 0, 'empty': []}
    assert fact.read({'key': 'block_tokens_per_s_chip', 'reduce': 'median'},
                     facts, None) == 100.0
    assert fact.read({'key': 'block_tokens_per_s_chip', 'reduce': 'p50'},
                     facts, None) == 100.0
    assert fact.read({'key': 'block_tokens_per_s_chip', 'reduce': 'mean'},
                     facts, None) == 88.0
    assert fact.read({'key': 'compiles_in_window'}, facts, None) == 0
    # a reader that finds nothing to read returns nothing
    assert fact.read({'key': 'empty', 'reduce': 'mean'}, facts, None) is None
    assert fact.read({'key': 'absent'}, facts, None) is None
