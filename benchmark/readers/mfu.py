"""Model FLOP/s utilization of training: the operations the forward and
backward passes require for a token (benchmark/kernels/<model>.py;
recomputed operations do not count) times the end-to-end tokens/s/chip, over
the chip's published bf16 peak."""
from benchmark.harness import device, manifest


def read(params, facts, reduced):
    model = manifest.load_module('kernels', params['model'])
    flops = model.train_flops_per_token(facts['shape'], facts['seq'])
    peak = device.peaks(facts['device_kind'])['bf16_flops_per_s']
    return 100.0 * flops * facts['tokens_per_s_chip'] / peak
