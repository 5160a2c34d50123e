"""Image classification with the high-level API (ResNet / MNIST-class data).

python examples/train_resnet.py --arch resnet18 --epochs 2
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import argparse

import jax


import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.vision import models, transforms as T
from paddle_tpu.vision.datasets import Cifar10


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--arch', default='resnet18')
    p.add_argument('--epochs', type=int, default=2)
    p.add_argument('--batch', type=int, default=64)
    p.add_argument('--lr', type=float, default=1e-3)
    args = p.parse_args()

    tf = T.Compose([T.RandomHorizontalFlip(),
                    T.Normalize([125., 123., 114.], [63., 62., 67.],
                                data_format='HWC'),
                    T.Transpose()])
    train = Cifar10(mode='train', transform=tf)
    test = Cifar10(mode='test', transform=tf)

    net = getattr(models, args.arch)(num_classes=10)
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.AdamW(args.lr, parameters=model.parameters(),
                               grad_clip=nn.ClipGradByGlobalNorm(1.0)),
        nn.CrossEntropyLoss(), paddle.metric.Accuracy())
    model.fit(train, test, epochs=args.epochs, batch_size=args.batch,
              num_workers=2, verbose=1)


if __name__ == '__main__':
    main()
