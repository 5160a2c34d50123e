"""SVTR-lite text recognition with CTC on synthetic glyph strips.

JAX_PLATFORMS=cpu python examples/train_ocr.py --steps 10

Renders digit-like bar glyphs into 32xW strips and trains
models.SVTRLite (local/global token mixing, CTC head) to read them.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import argparse

import numpy as np



def synth_strip(rng, n_chars, n_classes, char_w=16):
    """Each class = a distinct vertical-bar pattern; blank-separable."""
    w = n_chars * char_w
    img = np.zeros((32, w), 'f4')
    labels = rng.randint(1, n_classes, n_chars)
    for i, c in enumerate(labels):
        x0 = i * char_w
        for b in range(4):
            if (c >> b) & 1:
                img[4 + b * 6: 8 + b * 6, x0 + 2:x0 + char_w - 2] = 1.0
    return img[None], labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=30)
    p.add_argument('--batch', type=int, default=4)
    p.add_argument('--chars', type=int, default=4)
    p.add_argument('--classes', type=int, default=12)
    p.add_argument('--lr', type=float, default=2e-3)
    args = p.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu.models import SVTRLite

    paddle.seed(0)
    rng = np.random.RandomState(0)
    net = SVTRLite(num_classes=args.classes, dim=48, num_heads=2)
    opt = paddle.optimizer.Adam(learning_rate=args.lr,
                                parameters=net.parameters())
    ctc = paddle.nn.CTCLoss(blank=0)
    t_len = args.chars * 16 // 4

    for step in range(args.steps):
        imgs, labs = zip(*(synth_strip(rng, args.chars, args.classes)
                           for _ in range(args.batch)))
        x = paddle.to_tensor(np.stack(imgs).astype('f4'))
        labels = paddle.to_tensor(np.stack(labs).astype('i4'))
        logits = net(x)                                  # [N, T, C]
        lp = paddle.transpose(logits, [1, 0, 2])
        loss = ctc(lp, labels,
                   paddle.to_tensor(np.full((args.batch,), t_len, 'i8')),
                   paddle.to_tensor(np.full((args.batch,), args.chars,
                                            'i8')))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 10 == 0 or step == args.steps - 1:
            print(f'step {step} ctc loss {float(loss):.4f}', flush=True)

    # greedy CTC decode of one sample
    img, labs = synth_strip(rng, args.chars, args.classes)
    logits = np.asarray(net(paddle.to_tensor(img[None].astype('f4')))._value)
    path = logits[0].argmax(-1)
    decoded = [int(c) for i, c in enumerate(path)
               if c != 0 and (i == 0 or path[i - 1] != c)]
    print(f'target {labs.tolist()} -> decoded {decoded}')


if __name__ == '__main__':
    main()
