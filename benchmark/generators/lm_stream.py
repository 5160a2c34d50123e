"""A flat token stream for language-model training: a Zipf unigram law over
the vocabulary, so that a few steps already pull the loss down. The sizes
(stream length, batch, sequence) are the mix's; only the tokens follow the
seed."""
import numpy as np


def make(params, seed, vocab_size):
    rng = np.random.RandomState(seed % 2 ** 32)
    draws = rng.zipf(params['zipf_a'], params['stream_tokens'])
    return ((draws - 1) % vocab_size).astype(np.int32)
