"""PP-YOLOE detection training + serving export on synthetic boxes.

JAX_PLATFORMS=cpu python examples/train_detection.py --steps 5

Trains the anchor-free PPYOLOE (TAL assignment + VFL/GIoU/DFL,
vision/detection.py) on a synthetic box dataset, then exports the decode +
static-NMS serving graph through jit.save -> Predictor and ONNX.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import argparse
import tempfile

import numpy as np



def synth_batch(rng, batch, size, num_classes, max_boxes=4):
    """Images with bright rectangles; the boxes are the ground truth."""
    x = rng.rand(batch, 3, size, size).astype('f4') * 0.2
    gt_boxes = np.zeros((batch, max_boxes, 4), 'f4')
    gt_labels = np.zeros((batch, max_boxes), 'i4')
    gt_mask = np.zeros((batch, max_boxes), bool)
    for b in range(batch):
        n = rng.randint(1, max_boxes)
        for i in range(n):
            w, h = rng.randint(12, size // 2, 2)
            x0 = rng.randint(0, size - w)
            y0 = rng.randint(0, size - h)
            c = rng.randint(0, num_classes)
            x[b, c % 3, y0:y0 + h, x0:x0 + w] = 0.9
            gt_boxes[b, i] = [x0, y0, x0 + w, y0 + h]
            gt_labels[b, i] = c
            gt_mask[b, i] = True
    return x, gt_boxes, gt_labels, gt_mask


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--batch', type=int, default=2)
    p.add_argument('--size', type=int, default=64)
    p.add_argument('--classes', type=int, default=4)
    p.add_argument('--lr', type=float, default=2e-3)
    args = p.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.models import PPYOLOE
    from paddle_tpu.vision.ops import nms_static

    paddle.seed(0)
    rng = np.random.RandomState(0)
    net = PPYOLOE(num_classes=args.classes, width=8, reg_max=8)
    opt = paddle.optimizer.Adam(learning_rate=args.lr,
                                parameters=net.parameters())
    for step in range(args.steps):
        x, gb, gl, gm = synth_batch(rng, args.batch, args.size,
                                    args.classes)
        loss = net.loss(net(paddle.to_tensor(x)), paddle.to_tensor(gb),
                        paddle.to_tensor(gl), paddle.to_tensor(gm))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 5 == 0 or step == args.steps - 1:
            print(f'step {step} loss {float(loss):.4f}', flush=True)

    # ---- serve: decode + static NMS inside the exported graph ----------
    net.eval()

    class Served(paddle.nn.Layer):
        def __init__(self, det):
            super().__init__()
            self.det = det

        def forward(self, img):
            boxes, scores = self.det.decode(self.det(img))
            best = scores[0].max(axis=-1)
            keep, valid = nms_static(boxes[0], best, iou_threshold=0.5,
                                     max_out=8, unroll=True)
            return boxes, scores, keep, valid

    served = Served(net)
    served.eval()
    tmp = tempfile.mkdtemp()
    spec = [paddle.static.InputSpec([1, 3, args.size, args.size],
                                    'float32')]
    base = os.path.join(tmp, 'ppyoloe')
    paddle.jit.save(served, base, input_spec=spec)
    pred = inference.create_predictor(inference.Config(base + '.pdmodel'))
    xq, _, _, _ = synth_batch(rng, 1, args.size, args.classes)
    boxes, scores, keep, valid = pred.run([xq])
    print(f'predictor: {int(np.asarray(valid))} boxes kept after NMS')

    paddle.onnx.export(served, base + '.onnx', input_spec=spec)
    with open(base + '.onnx', 'rb') as f:
        ob = paddle.onnx.reference_run(f.read(), [xq])
    np.testing.assert_allclose(np.asarray(keep), ob[2], atol=0)
    print('onnx round-trip matches predictor keep indices')


if __name__ == '__main__':
    main()
