"""The counts of operations and bytes a roofline share rests on."""
import json
import os

import pytest

from benchmark.harness import device, manifest

PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def test_flash_products_and_bytes():
    fa = manifest.load_module('kernels', 'flash_attention')
    flops, byts = fa.call_cost('fwd', batch=1, heads=1, seq=1024,
                               head_dim=64)
    assert flops == 2 * 1024 * 1024 * 64          # two causal products
    assert byts == 4 * 1024 * 64 * 2 + 1024 * 4   # q, k, v, o and lse
    assert fa.call_cost('dq', 1, 1, 1024, 64)[0] == 3 * 1024 * 1024 * 64
    assert fa.call_cost('dkv', 1, 1, 1024, 64)[0] == 4 * 1024 * 1024 * 64


def test_flash_least_time_of_a_training_mix():
    fa = manifest.load_module('kernels', 'flash_attention')
    facts = {'shape': {'num_heads': 16, 'hidden_size': 1024}, 'batch': 8,
             'seq': 1024, 'mesh': {}, 'remat_policy': 'dots', 'layers': 24}
    got = fa.least_seconds(facts, steps=2, peaks=PEAKS)
    unit = 128 * 1024 * 1024 * 64 / 197e12
    # a layer of a step: forward twice (rematerialised), dq, dkv:
    # 2 + 2 + 3 + 4 products
    assert got['seconds'] == pytest.approx(11 * unit * 24 * 2)
    half = fa.least_seconds(dict(facts, mesh={'dp': 2, 'mp': 2}), 2, PEAKS)
    assert half['seconds'] == pytest.approx(got['seconds'] / 4)


def test_model_flops_per_token():
    gm = manifest.load_module('kernels', 'gpt_model')
    shape = {'hidden_size': 1024, 'num_layers': 24, 'vocab_size': 50304}
    n = 12 * 24 * 1024 ** 2 + 50304 * 1024
    assert gm.matmul_params(shape) == n
    assert gm.train_flops_per_token(shape, 1024) == \
        6 * n + 6 * 1024 * 1024 * 24


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = device.peaks('TPU v5 lite')
    assert v5e['bf16_flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError):
        device.peaks('TPU v9')
    with pytest.raises(KeyError):
        device.peaks('_source')
    with open(os.path.join(device.HERE, 'peaks.json')) as f:
        assert 'cloud.google.com' in json.load(f)['_source']
