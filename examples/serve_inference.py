"""Export a model with jit.save and serve it with the inference Predictor.

python examples/serve_inference.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import tempfile

import numpy as np


import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.vision.models import mobilenet_v2


def main():
    net = mobilenet_v2(num_classes=10, scale=0.25)
    net.eval()
    d = tempfile.mkdtemp()
    path = os.path.join(d, 'mnv2')
    spec = [paddle.static.InputSpec([1, 3, 32, 32], 'float32')]
    paddle.jit.save(net, path, input_spec=spec)
    print('saved:', sorted(os.listdir(d)))

    config = Config(path + '.pdmodel')
    config.set_precision('bfloat16')
    predictor = create_predictor(config)
    predictor.attach_layer(mobilenet_v2(num_classes=10, scale=0.25))

    x = np.random.rand(1, 3, 32, 32).astype('float32')
    handle = predictor.get_input_handle(predictor.get_input_names()[0])
    handle.copy_from_cpu(x)
    predictor.run()
    out = predictor.get_output_handle(predictor.get_output_names()[0]).copy_to_cpu()
    print('logits:', np.round(out[0], 3))


if __name__ == '__main__':
    main()
