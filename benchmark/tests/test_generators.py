"""A generator gives the same stream for the same seed and another for
another, whatever the size of the seed."""
import numpy as np

from benchmark.harness import manifest


def test_lm_stream_follows_the_seed():
    gen = manifest.load_module('generators', 'lm_stream')
    p = {'zipf_a': 1.3, 'stream_tokens': 10000}
    a, b, c = (gen.make(p, s, 512) for s in (3_000_000_000, 3_000_000_000,
                                            4))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 512
