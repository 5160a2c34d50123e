"""PP-YOLOE fidelity (VERDICT r5 item 6): TAL assignment, VFL/DFL/GIoU
losses, end-to-end synthetic-box training with decreasing loss, and the
static-NMS export path through Predictor AND ONNX. Plus the SVTR-lite rec
model's CTC training."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.vision import detection as D


def test_tal_assigner_basic():
    """Anchors inside a gt with aligned scores are assigned to it; padding
    gt rows assign nothing; conflicts go to the best metric."""
    pts, sts = D.anchor_points([(4, 4)], [8])          # 16 anchors, 32px
    A, C, M = 16, 3, 3
    gt_boxes = jnp.asarray([[0, 0, 16, 16], [16, 16, 32, 32],
                            [0, 0, 32, 32]], jnp.float32)
    gt_labels = jnp.asarray([0, 2, 1], jnp.int32)
    gt_mask = jnp.asarray([True, True, False])         # 3rd row = padding
    # predictions: boxes equal to the cell's gt, scores favor the gt class
    pred_boxes = jnp.where((pts[:, :1] < 16) & (pts[:, 1:2] < 16),
                           gt_boxes[0][None], gt_boxes[1][None])
    scores = jnp.full((A, C), 0.1, jnp.float32)
    scores = scores.at[:, 0].set(jnp.where(
        (pts[:, 0] < 16) & (pts[:, 1] < 16), 0.9, 0.1))
    scores = scores.at[:, 2].set(jnp.where(
        (pts[:, 0] >= 16) & (pts[:, 1] >= 16), 0.9, 0.1))

    fg, lab, abox, ascore = D.task_aligned_assign(
        scores, pred_boxes, pts, gt_boxes, gt_labels, gt_mask, topk=4)
    fg, lab = np.asarray(fg), np.asarray(lab)
    pts_n = np.asarray(pts)
    # top-left quadrant anchors -> gt0 (label 0); bottom-right -> gt1 (2)
    tl = (pts_n[:, 0] < 16) & (pts_n[:, 1] < 16)
    br = (pts_n[:, 0] >= 16) & (pts_n[:, 1] >= 16)
    assert (lab[fg & tl] == 0).all()
    assert (lab[fg & br] == 2).all()
    assert fg[tl].any() and fg[br].any()
    # the padded gt (label 1) must never be assigned
    assert (lab[fg] != 1).all()
    # quality targets are in (0, 1]
    ascore = np.asarray(ascore)
    assert (ascore[fg] > 0).all() and (ascore[fg] <= 1.0 + 1e-6).all()
    assert (ascore[~fg] == 0).all()


def test_giou_and_dfl_properties():
    box = jnp.asarray([[0., 0., 10., 10.]])
    assert float(D.giou_loss(box, box)[0]) == pytest.approx(0.0, abs=1e-6)
    far = jnp.asarray([[20., 20., 30., 30.]])
    assert float(D.giou_loss(box, far)[0]) > 1.0     # disjoint -> >1

    # DFL: a sharp distribution at the target bin has near-zero loss
    reg_max = 8
    t = jnp.asarray([3.0])
    sharp = jax.nn.one_hot(jnp.asarray([3]), reg_max + 1) * 50.0
    assert float(D.distribution_focal_loss(sharp, t)[0]) < 1e-3
    flat = jnp.zeros((1, reg_max + 1))
    assert float(D.distribution_focal_loss(flat, t)[0]) > 1.0
    # fractional target: loss is minimized by the two-bin mixture
    t2 = jnp.asarray([3.5])
    mix = jnp.log(jnp.asarray([[1e-6] * 3 + [0.5, 0.5] + [1e-6] * 4]))
    assert float(D.distribution_focal_loss(mix, t2)[0]) < float(
        D.distribution_focal_loss(sharp, t2)[0])


def test_varifocal_loss_weighting():
    """Positives weighted by target quality; confident-wrong negatives
    weighted up (focal)."""
    logits = jnp.asarray([[2.0, -2.0]])
    tgt_pos = jnp.asarray([[0.8, 0.0]])
    l = float(D.varifocal_loss(logits, tgt_pos))
    assert np.isfinite(l) and l > 0
    # a confident wrong negative contributes more than a correct one
    wrong = float(D.varifocal_loss(jnp.asarray([[3.0]]),
                                   jnp.asarray([[0.0]])))
    right = float(D.varifocal_loss(jnp.asarray([[-3.0]]),
                                   jnp.asarray([[0.0]])))
    assert wrong > right


def _synth_batch(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(2, 3, 64, 64).astype('f4')
    gt_boxes = np.zeros((2, 3, 4), 'f4')
    gt_boxes[:, 0] = [8, 8, 40, 40]
    gt_boxes[:, 1] = [28, 20, 60, 56]
    gt_labels = np.zeros((2, 3), 'i4')
    gt_labels[:, 1] = 2
    gt_mask = np.zeros((2, 3), bool)
    gt_mask[:, :2] = True
    return (paddle.to_tensor(x), paddle.to_tensor(gt_boxes),
            paddle.to_tensor(gt_labels), paddle.to_tensor(gt_mask))


def test_ppyoloe_train_decreasing_loss():
    from paddle_tpu.models import PPYOLOE
    paddle.seed(0)
    net = PPYOLOE(num_classes=4, width=8, reg_max=8)
    opt = paddle.optimizer.Adam(learning_rate=2e-3,
                                parameters=net.parameters())
    x, gb, gl, gm = _synth_batch()
    losses = []
    for _ in range(6):      # the loss is under 0.9 of its start from step 3 on
        loss = net.loss(net(x), gb, gl, gm)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0] * 0.9, losses


def test_ppyoloe_export_predictor_and_onnx(tmp_path):
    """Serve the detector e2e: decode + static NMS inside the exported
    graph, through BOTH the Predictor path and ONNX round-trip."""
    import os
    from paddle_tpu import inference
    from paddle_tpu.models import PPYOLOE
    from paddle_tpu.vision.ops import nms_static

    paddle.seed(1)
    net = PPYOLOE(num_classes=4, width=8, reg_max=8)
    net.eval()

    class Served(paddle.nn.Layer):
        def __init__(self, det):
            super().__init__()
            self.det = det

        def forward(self, x):
            boxes, scores = self.det.decode(self.det(x))
            best = scores[0].max(axis=-1)
            # unroll: the ONNX exporter has no structured control flow
            keep, valid = nms_static(boxes[0], best, iou_threshold=0.5,
                                     max_out=10, unroll=True)
            return boxes, scores, keep, valid

    served = Served(net)
    served.eval()
    x = np.random.RandomState(2).rand(1, 3, 64, 64).astype('f4')
    want = [np.asarray(t._value) for t in served(paddle.to_tensor(x))]

    path = os.path.join(tmp_path, 'ppyoloe')
    spec = [paddle.static.InputSpec(shape=[1, 3, 64, 64], dtype='float32')]
    paddle.jit.save(served, path, input_spec=spec)
    pred = inference.create_predictor(inference.Config(path + '.pdmodel'))
    got = pred.run([x])
    for w, g in zip(want, got):
        np.testing.assert_allclose(w, np.asarray(g), atol=1e-4, rtol=1e-4)

    onnx_path = os.path.join(tmp_path, 'ppyoloe.onnx')
    paddle.onnx.export(served, onnx_path, input_spec=spec)
    with open(onnx_path, 'rb') as f:
        onnx_got = paddle.onnx.reference_run(f.read(), [x])
    for w, g in zip(want, onnx_got):
        np.testing.assert_allclose(w, np.asarray(g), atol=1e-3, rtol=1e-3)


def test_svtr_ctc_train_decreasing_loss():
    from paddle_tpu.models import SVTRLite
    paddle.seed(3)
    net = SVTRLite(num_classes=12, dim=32, num_heads=2)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    ctc = paddle.nn.CTCLoss(blank=0)
    rng = np.random.RandomState(4)
    x = paddle.to_tensor(rng.rand(2, 1, 32, 64).astype('f4'))
    labels = paddle.to_tensor(rng.randint(1, 12, (2, 5)).astype('i4'))
    in_len = paddle.to_tensor(np.asarray([16, 16], 'i8'))
    lab_len = paddle.to_tensor(np.asarray([5, 5], 'i8'))
    losses = []
    for _ in range(4):      # it falls from the first step on
        logits = net(x)                                  # [N, T, C]
        lp = paddle.transpose(logits, [1, 0, 2])         # CTC wants [T,N,C]
        loss = ctc(lp, labels, in_len, lab_len)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
