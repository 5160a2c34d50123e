"""Round-4b user journeys: reference-tutorial-shaped programs.

Each test mimics a published PaddlePaddle 2.1 tutorial workflow
(docs/practices: DCGAN, transfer learning, seq2seq, U-Net segmentation,
hapi callbacks, LR-on-plateau resume) at toy scale. The point is the API
*combinations* a migrating user writes, not the individual ops."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_dcgan_alternating_training_journey():
    """DCGAN practice tutorial: G(ConvTranspose+BN) vs D(Conv+BN), two
    optimizers, detach() for the D step, BCE-with-logits on real/fake
    labels; one alternating round must move both nets' params."""
    paddle.seed(0)

    class G(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(16, 4 * 4 * 8)
            self.bn0 = nn.BatchNorm2D(8)
            self.deconv = nn.Conv2DTranspose(8, 1, 4, stride=2, padding=1)

        def forward(self, z):
            x = self.fc(z).reshape([-1, 8, 4, 4])
            x = F.relu(self.bn0(x))
            return paddle.tanh(self.deconv(x))        # [B,1,8,8]

    class D(nn.Layer):
        def __init__(self):
            super().__init__()
            self.conv = nn.Conv2D(1, 8, 4, stride=2, padding=1)
            self.bn = nn.BatchNorm2D(8)
            self.fc = nn.Linear(8 * 4 * 4, 1)

        def forward(self, x):
            x = F.leaky_relu(self.bn(self.conv(x)), 0.2)
            return self.fc(x.flatten(1))              # logits

    g, d = G(), D()
    opt_g = paddle.optimizer.Adam(parameters=g.parameters(),
                                  learning_rate=2e-3)
    opt_d = paddle.optimizer.Adam(parameters=d.parameters(),
                                  learning_rate=2e-3)
    bce = nn.BCEWithLogitsLoss()
    real = paddle.to_tensor(
        np.random.RandomState(0).randn(4, 1, 8, 8).astype('float32'))
    z = paddle.to_tensor(
        np.random.RandomState(1).randn(4, 16).astype('float32'))
    ones = paddle.ones([4, 1])
    zeros = paddle.zeros([4, 1])

    g_before = {n: np.asarray(p._value).copy()
                for n, p in g.named_parameters()}
    d_before = {n: np.asarray(p._value).copy()
                for n, p in d.named_parameters()}

    # D step: real up, fake (detached) down
    fake = g(z)
    loss_d = bce(d(real), ones) + bce(d(fake.detach()), zeros)
    loss_d.backward()
    opt_d.step()
    opt_d.clear_grad()

    # G step: fool D
    loss_g = bce(d(g(z)), ones)
    loss_g.backward()
    opt_g.step()
    opt_g.clear_grad()

    assert np.isfinite(float(loss_d)) and np.isfinite(float(loss_g))
    moved_d = [n for n, p in d.named_parameters()
               if not np.allclose(np.asarray(p._value), d_before[n])]
    moved_g = [n for n, p in g.named_parameters()
               if not np.allclose(np.asarray(p._value), g_before[n])]
    assert moved_d, 'D params did not move'
    assert moved_g, 'G params did not move'
    # the D step must NOT have pushed gradients into G (fake was detached):
    # verify by checking G's grads were only populated by the G step — run
    # a fresh D step after clear and confirm G grads stay empty
    fake2 = g(z)
    loss_d2 = bce(d(fake2.detach()), zeros)
    loss_d2.backward()
    for n, p in g.named_parameters():
        assert p.grad is None or float(
            paddle.abs(paddle.to_tensor(p.grad)).sum()) == 0.0, \
            f'detach leaked grad into G param {n}'


def test_transfer_learning_freeze_journey(tmp_path):
    """Transfer-learning tutorial: pretrain a small CNN, save, reload into
    a fresh net, freeze the backbone (stop_gradient), replace the head,
    train — backbone must stay EXACTLY fixed while the head moves."""
    paddle.seed(1)

    def make_net(num_classes):
        return nn.Sequential(
            nn.Conv2D(1, 4, 3, padding=1), nn.ReLU(),
            nn.MaxPool2D(2, 2), nn.Flatten(),
            nn.Linear(4 * 4 * 4, num_classes))

    src = make_net(10)
    path = str(tmp_path / 'pre.pdparams')
    paddle.save(src.state_dict(), path)

    tgt = make_net(10)
    tgt.set_state_dict(paddle.load(path))
    # replace head for a 3-class task, freeze everything else
    tgt[4] = nn.Linear(4 * 4 * 4, 3)
    for name, p in tgt.named_parameters():
        if not name.startswith('4.'):
            p.stop_gradient = True

    frozen_before = {n: np.asarray(p._value).copy()
                     for n, p in tgt.named_parameters()
                     if not n.startswith('4.')}
    head_before = np.asarray(tgt[4].weight._value).copy()
    opt = paddle.optimizer.Momentum(parameters=tgt.parameters(),
                                    learning_rate=0.1)
    x = paddle.to_tensor(
        np.random.RandomState(2).rand(8, 1, 8, 8).astype('float32'))
    y = paddle.to_tensor(np.arange(8, dtype='int64') % 3)
    for _ in range(3):
        loss = F.cross_entropy(tgt(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()

    head_w = np.asarray(tgt[4].weight._value)
    assert not np.allclose(head_w, head_before), 'head never trained'
    for n, p in tgt.named_parameters():
        if not n.startswith('4.'):
            np.testing.assert_array_equal(
                np.asarray(p._value), frozen_before[n],
                err_msg=f'frozen param {n} moved')


def test_seq2seq_teacher_forcing_journey():
    """Seq2seq practice tutorial: LSTM encoder -> decoder with teacher
    forcing, shared loss over shifted targets; trains to lower loss."""
    paddle.seed(3)
    V, H, B, S = 20, 16, 4, 6

    class Seq2Seq(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, H)
            self.enc = nn.LSTM(H, H)
            self.dec = nn.LSTM(H, H)
            self.out = nn.Linear(H, V)

        def forward(self, src, tgt_in):
            _, (h, c) = self.enc(self.emb(src))
            y, _ = self.dec(self.emb(tgt_in), (h, c))
            return self.out(y)

    net = Seq2Seq()
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
    rs = np.random.RandomState(4)
    src = paddle.to_tensor(rs.randint(0, V, (B, S)).astype('int64'))
    tgt = paddle.to_tensor(rs.randint(0, V, (B, S)).astype('int64'))
    bos = paddle.zeros([B, 1], dtype='int64')
    tgt_in = paddle.concat([bos, tgt[:, :-1]], axis=1)

    losses = []
    for _ in range(18):     # 0.7 of the first loss is passed at step 13
        logits = net(src, tgt_in)
        loss = F.cross_entropy(logits.reshape([-1, V]), tgt.reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::6]


def test_unet_segmentation_journey():
    """Pet-segmentation tutorial shape: down conv, up Conv2DTranspose,
    skip concat, per-pixel cross-entropy over class logits."""
    paddle.seed(5)

    class TinyUNet(nn.Layer):
        def __init__(self, nclass=3):
            super().__init__()
            self.d1 = nn.Conv2D(3, 8, 3, padding=1)
            self.pool = nn.MaxPool2D(2, 2)
            self.d2 = nn.Conv2D(8, 16, 3, padding=1)
            self.up = nn.Conv2DTranspose(16, 8, 2, stride=2)
            self.mix = nn.Conv2D(16, nclass, 3, padding=1)

        def forward(self, x):
            a = F.relu(self.d1(x))            # [B,8,H,W]
            b = F.relu(self.d2(self.pool(a)))  # [B,16,H/2,W/2]
            u = self.up(b)                    # [B,8,H,W]
            cat = paddle.concat([a, u], axis=1)
            return self.mix(cat)              # [B,C,H,W]

    net = TinyUNet()
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=5e-3)
    rs = np.random.RandomState(6)
    x = paddle.to_tensor(rs.rand(2, 3, 8, 8).astype('float32'))
    y = paddle.to_tensor(rs.randint(0, 3, (2, 8, 8)).astype('int64'))
    losses = []
    for _ in range(15):
        logits = net(x)                       # [B,C,H,W]
        # tutorial computes per-pixel CE with axis=1 class dim
        loss = F.cross_entropy(logits.transpose([0, 2, 3, 1])
                               .reshape([-1, 3]), y.reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_hapi_earlystop_checkpoint_resume_journey(tmp_path):
    """hapi tutorial: Model.fit with EarlyStopping + ModelCheckpoint,
    then a fresh Model.load resumes and predicts."""
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.callbacks import EarlyStopping, ModelCheckpoint
    from paddle_tpu.metric import Accuracy
    paddle.seed(7)

    rs = np.random.RandomState(8)
    xs = rs.rand(32, 8).astype('float32')
    ys = (xs.sum(1) > 4).astype('int64')

    class DS(paddle.io.Dataset):
        def __len__(self):
            return len(xs)

        def __getitem__(self, i):
            return xs[i], ys[i]

    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = Model(net)
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
    model.prepare(opt, nn.CrossEntropyLoss(), Accuracy())
    loader = paddle.io.DataLoader(DS(), batch_size=8, shuffle=True)
    ckpt_dir = str(tmp_path / 'ck')
    model.fit(loader, eval_data=loader, epochs=4, verbose=0,
              callbacks=[EarlyStopping('loss', patience=10),
                         ModelCheckpoint(save_dir=ckpt_dir)])

    net2 = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model2 = Model(net2)
    model2.prepare(metrics=Accuracy())
    model2.load(ckpt_dir + '/final')
    out = model2.predict_batch([xs[:4]])
    pred = np.asarray(out[0]) if not isinstance(out[0], np.ndarray) else out[0]
    assert pred.shape == (4, 2)
    # loaded net agrees with trained net
    want = np.asarray(net(paddle.to_tensor(xs[:4]))._value)
    np.testing.assert_allclose(pred, want, atol=1e-6)


def test_reduce_on_plateau_resume_journey(tmp_path):
    """LR-scheduling tutorial: ReduceOnPlateau drops LR on a stuck metric;
    scheduler state (incl. patience counters) survives save/resume."""
    paddle.seed(9)
    net = nn.Linear(4, 1)
    sched = paddle.optimizer.lr.ReduceOnPlateau(
        learning_rate=0.1, factor=0.5, patience=2, verbose=False)
    opt = paddle.optimizer.SGD(parameters=net.parameters(),
                               learning_rate=sched)
    # stuck metric: after patience epochs the LR must halve
    for _ in range(4):
        sched.step(1.0)
    assert abs(sched.get_lr() - 0.05) < 1e-9, sched.get_lr()

    state = sched.state_dict()
    sched2 = paddle.optimizer.lr.ReduceOnPlateau(
        learning_rate=0.1, factor=0.5, patience=2, verbose=False)
    sched2.set_state_dict(state)
    assert abs(sched2.get_lr() - 0.05) < 1e-9
    # two more stuck epochs on the RESUMED scheduler: halves again
    # (patience counter must have survived the round-trip)
    for _ in range(3):
        sched2.step(1.0)
    assert abs(sched2.get_lr() - 0.025) < 1e-9, sched2.get_lr()


def test_recommender_two_tower_journey():
    """Movielens-style tutorial: user/item embedding towers joined by
    cosine similarity, square loss on ratings; trains and ranks."""
    paddle.seed(11)

    class Tower(nn.Layer):
        def __init__(self, n, dim=8):
            super().__init__()
            self.emb = nn.Embedding(n, dim)
            self.fc = nn.Linear(dim, dim)

        def forward(self, ids):
            return F.relu(self.fc(self.emb(ids)))

    class Rec(nn.Layer):
        def __init__(self):
            super().__init__()
            self.user, self.item = Tower(10), Tower(15)

        def forward(self, u, i):
            eu, ei = self.user(u), self.item(i)
            return F.cosine_similarity(eu, ei, axis=-1)

    net = Rec()
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=5e-3)
    rs = np.random.RandomState(12)
    u = paddle.to_tensor(rs.randint(0, 10, (32,)).astype('int64'))
    i = paddle.to_tensor(rs.randint(0, 15, (32,)).astype('int64'))
    y = paddle.to_tensor(((np.asarray(u._value) + np.asarray(i._value))
                          % 2).astype('float32') * 2 - 1)   # ±1 targets
    losses = []
    for _ in range(30):
        sim = net(u, i)
        loss = F.mse_loss(sim, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses[::8]


def test_weighted_random_sampler_journey():
    """Class-imbalance tutorial: WeightedRandomSampler oversamples the
    rare class to roughly balance batches."""
    ys = np.array([0] * 90 + [1] * 10)
    weights = np.where(ys == 1, 9.0, 1.0)
    sampler = paddle.io.WeightedRandomSampler(weights.tolist(), 200,
                                              replacement=True)
    idx = list(iter(sampler))
    assert len(idx) == 200
    frac_rare = np.mean(ys[np.asarray(idx)] == 1)
    assert 0.3 < frac_rare < 0.7, frac_rare


def test_text_classifier_padding_journey():
    """Sentiment tutorial: ragged token lists -> pad to max len, Embedding
    with padding_idx, mask-aware mean pool, Linear head. padding_idx rows
    must stay zero AND receive no gradient."""
    paddle.seed(13)
    V, H, PAD = 30, 16, 0
    seqs = [[3, 5, 7], [9, 2], [4, 6, 8, 10], [11]]
    maxlen = max(len(s) for s in seqs)
    padded = np.full((len(seqs), maxlen), PAD, np.int64)
    for r, s in enumerate(seqs):
        padded[r, :len(s)] = s
    emb = nn.Embedding(V, H, padding_idx=PAD)
    fc = nn.Linear(H, 2)
    params = list(emb.parameters()) + list(fc.parameters())
    opt = paddle.optimizer.Adam(parameters=params, learning_rate=1e-2)
    x = paddle.to_tensor(padded)
    y = paddle.to_tensor(np.array([0, 1, 0, 1], np.int64))
    mask = paddle.cast(x != PAD, 'float32')

    for _ in range(5):
        e = emb(x)                                    # [B, L, H]
        pooled = (e * mask.unsqueeze(-1)).sum(axis=1) \
            / mask.sum(axis=1, keepdim=True)
        loss = F.cross_entropy(fc(pooled), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    pad_row = np.asarray(emb.weight._value)[PAD]
    np.testing.assert_allclose(pad_row, np.zeros(H), atol=1e-7,
                               err_msg='padding_idx row trained')


def test_gradient_accumulation_journey():
    """Manual micro-batch accumulation (the pre-fleet idiom): 4 backward()
    calls then one step == one big-batch step."""
    paddle.seed(14)
    rs = np.random.RandomState(15)
    xs = rs.rand(16, 6).astype('float32')
    ys = rs.rand(16, 1).astype('float32')

    def fresh():
        paddle.seed(14)
        net = nn.Linear(6, 1)
        opt = paddle.optimizer.SGD(parameters=net.parameters(),
                                   learning_rate=0.1)
        return net, opt

    # accumulated: mean over micro losses => divide each by n_micro
    net_a, opt_a = fresh()
    for mb in range(4):
        x = paddle.to_tensor(xs[mb * 4:(mb + 1) * 4])
        y = paddle.to_tensor(ys[mb * 4:(mb + 1) * 4])
        loss = F.mse_loss(net_a(x), y) / 4.0
        loss.backward()
    opt_a.step()
    opt_a.clear_grad()

    net_b, opt_b = fresh()
    loss = F.mse_loss(net_b(paddle.to_tensor(xs)), paddle.to_tensor(ys))
    loss.backward()
    opt_b.step()
    opt_b.clear_grad()

    np.testing.assert_allclose(np.asarray(net_a.weight._value),
                               np.asarray(net_b.weight._value),
                               rtol=1e-5, atol=1e-6)


def test_param_attr_initializer_journey():
    """Reference idiom: weight_attr=ParamAttr(initializer=..., 
    regularizer=..., learning_rate=...) on Linear/Conv; the initializer
    must actually be applied."""
    from paddle_tpu import ParamAttr
    import paddle_tpu.nn.initializer as I
    paddle.seed(16)
    fc = nn.Linear(4, 3,
                   weight_attr=ParamAttr(initializer=I.Constant(0.5)),
                   bias_attr=ParamAttr(initializer=I.Constant(-1.0)))
    np.testing.assert_allclose(np.asarray(fc.weight._value), 0.5)
    np.testing.assert_allclose(np.asarray(fc.bias._value), -1.0)

    conv = nn.Conv2D(2, 3, 3,
                     weight_attr=ParamAttr(initializer=I.KaimingNormal()))
    w = np.asarray(conv.weight._value)
    assert w.std() > 0 and abs(w.mean()) < 0.5


def test_spectral_norm_gan_discriminator_journey():
    """SN-GAN idiom: nn.utils.spectral_norm on D's Linear; the effective
    weight's top singular value ~1 and training still works."""
    paddle.seed(17)
    fc = nn.Linear(8, 8)
    with paddle.no_grad():
        fc.weight.set_value(paddle.to_tensor(
            (np.random.RandomState(18).randn(8, 8) * 3).astype('float32')))
    snfc = paddle.nn.utils.spectral_norm(fc)
    x = paddle.to_tensor(
        np.random.RandomState(19).rand(4, 8).astype('float32'))
    for _ in range(5):           # power iteration refines u/v across calls
        out = snfc(x)
    # effective weight: out = x @ W_sn ; recover via unit basis
    eye = paddle.to_tensor(np.eye(8, dtype='float32'))
    w_sn = np.asarray(snfc(eye)._value)
    sv = np.linalg.svd(w_sn, compute_uv=False)
    assert sv[0] < 1.6, sv[:3]   # ~1 up to power-iteration error
    loss = out.sum()
    loss.backward()
    assert fc.weight.grad is not None or any(
        p.grad is not None for p in snfc.parameters())


def test_clip_grad_in_optimizer_ctor_journey():
    """grad_clip=ClipGradByGlobalNorm passed to the optimizer constructor
    (the documented pattern) actually clips."""
    paddle.seed(20)
    net = nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(
        parameters=net.parameters(), learning_rate=1.0,
        grad_clip=nn.ClipGradByGlobalNorm(0.01))
    x = paddle.to_tensor(
        (np.random.RandomState(21).rand(8, 4) * 100).astype('float32'))
    y = paddle.to_tensor(np.zeros((8, 1), np.float32))
    w0 = np.asarray(net.weight._value).copy()
    loss = F.mse_loss(net(x), y)
    loss.backward()
    opt.step()
    delta = np.linalg.norm(np.asarray(net.weight._value) - w0)
    # lr=1, global grad norm clipped to 0.01 => total update norm <= ~0.01
    assert delta <= 0.0101 + 1e-6, delta


def test_jit_save_load_finetune_journey(tmp_path):
    """Deploy-then-finetune tutorial: jit.save a raw layer with a
    tensor-dependent branch, jit.load it elsewhere, run inference AND
    continue training the loaded layer's parameters."""
    paddle.seed(22)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(4, 8)
            self.fc2 = nn.Linear(8, 2)

        def forward(self, x):
            y = F.relu(self.fc1(x))
            if paddle.mean(y) > 0.5:     # tensor-dependent branch
                y = y * 2.0
            return self.fc2(y)

    net = Net()
    p = str(tmp_path / 'm')
    paddle.jit.save(net, p,
                    input_spec=[paddle.static.InputSpec([None, 4],
                                                        'float32')])
    loaded = paddle.jit.load(p)
    x = paddle.to_tensor(
        np.random.RandomState(23).rand(3, 4).astype('float32'))
    out = loaded(x)
    want = net(x)
    np.testing.assert_allclose(np.asarray(out._value),
                               np.asarray(want._value), atol=1e-5)

    params = list(loaded.parameters())
    assert params, 'loaded layer exposes no trainable parameters'
    opt = paddle.optimizer.Adam(parameters=params, learning_rate=1e-2)
    y = paddle.to_tensor(np.array([0, 1, 0], 'int64'))
    losses = []
    for _ in range(5):
        loss = F.cross_entropy(loaded(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_jit_save_load_dict_output_journey(tmp_path):
    """A forward returning a dict must round-trip through jit.save ->
    TranslatedLayer with the pytree structure intact (review r4b)."""
    paddle.seed(24)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, x):
            h = self.fc(x)
            return {'logits': h, 'probs': F.softmax(h, axis=-1)}

    net = Net()
    p = str(tmp_path / 'd')
    paddle.jit.save(net, p,
                    input_spec=[paddle.static.InputSpec([None, 4],
                                                        'float32')])
    loaded = paddle.jit.load(p)
    x = paddle.to_tensor(
        np.random.RandomState(25).rand(3, 4).astype('float32'))
    out = loaded(x)
    assert set(out) == {'logits', 'probs'}
    np.testing.assert_allclose(np.asarray(out['probs']._value).sum(-1),
                               np.ones(3), atol=1e-5)
    # and grads flow through a dict member
    loss = out['logits'].sum()
    loss.backward()
    g = loaded.parameters()[0].grad
    assert g is not None


def test_vision_quickstart_journey():
    """The 2.1 quickstart: MNIST + Compose(ToTensor, Normalize) + LeNet +
    hapi Model.fit/evaluate/predict_batch (synthetic MNIST fallback)."""
    from paddle_tpu.vision import transforms, datasets
    from paddle_tpu.hapi import Model
    from paddle_tpu.metric import Accuracy
    from paddle_tpu.vision.models import LeNet
    paddle.seed(26)

    tf = transforms.Compose([transforms.ToTensor(),
                             transforms.Normalize(mean=[0.5], std=[0.5])])
    train = datasets.MNIST(mode='train', transform=tf, backend='cv2')
    x0, _ = train[0]
    assert np.asarray(x0).shape == (1, 28, 28)
    net = LeNet()
    m = Model(net)
    m.prepare(paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=1e-3),
              nn.CrossEntropyLoss(), Accuracy())
    loader = paddle.io.DataLoader(train, batch_size=16, shuffle=True)
    m.fit(loader, epochs=1, verbose=0, num_iters=4)
    res = m.evaluate(loader, verbose=0, num_iters=2)
    assert 'acc' in res and 'loss' in res
    pred = m.predict_batch(
        [np.stack([np.asarray(train[i][0]) for i in range(4)])])
    assert np.asarray(pred[0]).shape == (4, 10)


def test_jit_load_name_collision_roundtrip(tmp_path):
    """Review r4b: program-side names 'a__weight' and 'a.weight' must NOT
    alias after jit.load's attribute-name flattening."""
    paddle.seed(27)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            from paddle_tpu.nn.layer_base import Parameter
            self.a = nn.Linear(4, 4)
            self.add_parameter('a__weight', Parameter(
                paddle.ones([4])._value * 3.0))

        def forward(self, x):
            return self.a(x) * self.a__weight

    net = Net()
    p = str(tmp_path / 'c')
    paddle.jit.save(net, p,
                    input_spec=[paddle.static.InputSpec([None, 4],
                                                        'float32')])
    loaded = paddle.jit.load(p)
    assert len(loaded.parameters()) == len(net.parameters())
    x = paddle.to_tensor(
        np.random.RandomState(28).rand(2, 4).astype('float32'))
    np.testing.assert_allclose(np.asarray(loaded(x)._value),
                               np.asarray(net(x)._value), atol=1e-5)
    sd = loaded.state_dict(structured_name_prefix='m.')
    assert 'm.a__weight' in sd and 'm.a.weight' in sd


def test_attention_dropout_actually_applied():
    """Journey r4b: MultiHeadAttention(dropout=0.3) in train mode must
    actually sample attention dropout (it was silently ignored), keep the
    inverted-dropout expectation, share the mask between forward and
    backward, and turn OFF in eval mode."""
    paddle.seed(31)
    import paddle_tpu.nn.functional as F2
    rs = np.random.RandomState(32)
    q = paddle.to_tensor(rs.rand(2, 8, 2, 16).astype('float32'))

    def run(training):
        paddle.seed(99)
        return np.asarray(F2.scaled_dot_product_attention(
            q, q, q, dropout_p=0.5, training=training)._value)

    a, b = run(True), run(True)
    np.testing.assert_allclose(a, b, atol=0)      # same seed -> same mask
    paddle.seed(99)
    c = np.asarray(F2.scaled_dot_product_attention(
        q, q, q, dropout_p=0.5, training=False)._value)
    assert not np.allclose(a, c), 'dropout had no effect in train mode'
    d = np.asarray(F2.scaled_dot_product_attention(
        q, q, q, dropout_p=0.0, training=True)._value)
    np.testing.assert_allclose(c, d, atol=1e-6)   # eval == p0

    # backward shares the forward's mask: grad of sum wrt q is finite and
    # reproducible under the same seed
    def gradrun():
        paddle.seed(7)
        qq = paddle.to_tensor(rs.rand(2, 8, 2, 16).astype('float32') * 0
                              + np.asarray(q._value))
        qq.stop_gradient = False
        out = F2.scaled_dot_product_attention(qq, qq, qq, dropout_p=0.5,
                                              training=True)
        out.sum().backward()
        return np.asarray(qq.grad)

    g1, g2 = gradrun(), gradrun()
    np.testing.assert_allclose(g1, g2, atol=0)

    mha = nn.MultiHeadAttention(32, 2, dropout=0.5)
    x = paddle.to_tensor(rs.rand(2, 8, 32).astype('float32'))
    paddle.seed(5)
    o1 = np.asarray(mha(x)._value)
    paddle.seed(5)
    mha.eval()
    o2 = np.asarray(mha(x)._value)
    assert not np.allclose(o1, o2), 'MHA train-mode dropout inert'


def test_gpt_scan_unroll_equivalence():
    """scan_unroll is a pure scheduling knob: numerics must be identical."""
    from paddle_tpu.models import gpt
    import jax
    import jax.numpy as jnp
    c1 = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                       num_heads=2, max_seq_len=32, dtype='float32',
                       use_flash=False, remat=False)
    c2 = gpt.GPTConfig(**{**c1.__dict__, 'scan_unroll': 2})
    p = gpt.init_params(c1, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    assert jnp.allclose(gpt.forward(p, t, c1), gpt.forward(p, t, c2),
                        atol=1e-6)


def test_optimizer_scheduler_resume_exactness(tmp_path):
    """Reference save/load contract: net.state_dict + opt.state_dict (which
    carries the LR scheduler state) must make 3+resume+3 EXACTLY equal 6
    straight steps, scheduler epoch included."""
    def build():
        paddle.seed(40)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        sched = paddle.optimizer.lr.StepDecay(learning_rate=0.05,
                                              step_size=2, gamma=0.5)
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=sched)
        return net, opt, sched

    rs = np.random.RandomState(41)
    xs = paddle.to_tensor(rs.rand(16, 8).astype('float32'))
    ys = paddle.to_tensor(rs.rand(16, 1).astype('float32'))

    def step(net, opt, sched):
        loss = F.mse_loss(net(xs), ys)
        loss.backward()
        opt.step()
        opt.clear_grad()
        sched.step()

    net_a, opt_a, sched_a = build()
    for _ in range(6):
        step(net_a, opt_a, sched_a)

    net_b, opt_b, sched_b = build()
    for _ in range(3):
        step(net_b, opt_b, sched_b)
    paddle.save(net_b.state_dict(), str(tmp_path / 'n.pdparams'))
    paddle.save(opt_b.state_dict(), str(tmp_path / 'o.pdopt'))
    net_c, opt_c, sched_c = build()
    net_c.set_state_dict(paddle.load(str(tmp_path / 'n.pdparams')))
    opt_c.set_state_dict(paddle.load(str(tmp_path / 'o.pdopt')))
    for _ in range(3):
        step(net_c, opt_c, sched_c)

    np.testing.assert_allclose(np.asarray(net_a[2].weight._value),
                               np.asarray(net_c[2].weight._value), atol=1e-7)
    assert abs(sched_c.get_lr() - sched_a.get_lr()) < 1e-12


def test_fleet_zero2_amp_clip_journey():
    """DistributedStrategy combo: sharding stage-2 + amp + global-norm clip
    through fleet.distributed_optimizer trains on the 8-device mesh."""
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs = {'stage': 2}
    strategy.amp = True
    strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1,
                               'pp_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)

    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-2,
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0))
    opt = fleet.distributed_optimizer(opt)
    model = fleet.distributed_model(net)

    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.rand(16, 16).astype('float32'))
    y = paddle.to_tensor(rs.randint(0, 4, (16,)).astype('int64'))
    losses = []
    for _ in range(5):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_interpolate_mode_parity():
    """Journey r4b: align_corners=True, bicubic (a=-0.75 kernel), and
    'area' (adaptive-pool bins) previously diverged from the reference
    semantics; all modes now match the torch/paddle conventions."""
    torch = pytest.importorskip('torch')
    import torch.nn.functional as TF
    import paddle_tpu.nn.functional as F2

    x = np.random.RandomState(0).rand(2, 3, 5, 7).astype('float32')
    cases = [('nearest', None), ('bilinear', False), ('bilinear', True),
             ('bicubic', False), ('bicubic', True), ('area', None)]
    for size in ([10, 14], [3, 4]):
        for mode, ac in cases:
            kw = {} if ac is None else {'align_corners': ac}
            ours = np.asarray(F2.interpolate(paddle.to_tensor(x), size=size,
                                             mode=mode, **kw)._value)
            theirs = TF.interpolate(torch.from_numpy(x), size=tuple(size),
                                    mode=mode, **kw).numpy()
            np.testing.assert_allclose(ours, theirs, atol=2e-6,
                                       err_msg=f'{mode} ac={ac} {size}')
    # grads flow through the weight-matrix path
    xp = paddle.to_tensor(x)
    xp.stop_gradient = False
    F2.interpolate(xp, size=[10, 14], mode='bicubic',
                   align_corners=True).sum().backward()
    assert np.isfinite(np.asarray(xp.grad)).all()

    # align_mode=1 (src = i*in/out — the PaddleDetection convention), up
    # and down, vs a hand reference
    def ref_mode1_1d(v, n_out):
        n_in = len(v)
        out = np.zeros(n_out)
        for i in range(n_out):
            s = i * n_in / n_out
            s0 = min(int(np.floor(s)), n_in - 1)
            s1 = min(s0 + 1, n_in - 1)
            f = s - s0
            out[i] = v[s0] * (1 - f) + v[s1] * f
        return out

    v = np.random.RandomState(3).rand(7).astype('float32')
    x1 = paddle.to_tensor(v.reshape(1, 1, 7))
    for n_out in (12, 4):
        o = np.asarray(F2.interpolate(x1, size=[n_out], mode='linear',
                                      align_mode=1,
                                      data_format='NCW')._value).ravel()
        np.testing.assert_allclose(o, ref_mode1_1d(v, n_out), atol=1e-6,
                                   err_msg=f'align_mode=1 size {n_out}')


def test_batchnorm_near_constant_channel_no_nan():
    """Journey r4b (deterministic replay of a real ResNet-18 NaN): a
    channel that is near-constant with a large mean makes the one-pass
    E[x^2]-mean^2 variance NEGATIVE under f32 cancellation (true var
    ~1e-6 computed as -1.5e-5, beating eps=1e-5) -> rsqrt(neg) = NaN.
    The two-pass form must stay finite, forward and backward."""
    bn = nn.BatchNorm2D(2)
    rs = np.random.RandomState(0)
    # channel 0: large mean, tiny spread (the killer); channel 1: normal
    c0 = 80.0 + rs.rand(2, 1, 4, 4).astype('float32') * 3e-3
    c1 = rs.rand(2, 1, 4, 4).astype('float32')
    x = paddle.to_tensor(np.concatenate([c0, c1], axis=1))
    x.stop_gradient = False
    out = bn(x)
    a = np.asarray(out._value)
    assert np.isfinite(a).all(), 'BN forward NaN on near-constant channel'
    out.sum().backward()
    assert np.isfinite(np.asarray(x.grad)).all()
    # and the running stats stayed finite/sane
    assert np.isfinite(np.asarray(bn._variance._value)).all()
    assert (np.asarray(bn._variance._value) >= 0).all()


def test_categorical_reference_semantics():
    """Reference distribution.py quirk, matched exactly: sample() and
    probs()/log_prob() treat `logits` as unnormalized probability WEIGHTS
    (multinomial semantics, normalized by sum), while entropy()/
    kl_divergence() use softmax."""
    from paddle_tpu.distribution import Categorical
    paddle.seed(0)
    w = np.array([0.1, 0.2, 0.7], np.float32)
    c = Categorical(paddle.to_tensor(w))
    s = np.asarray(c.sample([30000])._value)
    freq = np.bincount(s.astype(int), minlength=3) / 30000
    np.testing.assert_allclose(freq, w, atol=0.02)
    np.testing.assert_allclose(
        np.asarray(c.probs(paddle.to_tensor(np.array([0, 1, 2])))._value),
        w, atol=1e-6)
    np.testing.assert_allclose(
        float(np.asarray(c.log_prob(
            paddle.to_tensor(np.array([2])))._value)[0]),
        np.log(0.7), atol=1e-6)
    # entropy/kl stay softmax-based (the reference's own asymmetry)
    p_sm = np.exp(w) / np.exp(w).sum()
    np.testing.assert_allclose(float(c.entropy()),
                               -(p_sm * np.log(p_sm)).sum(), atol=1e-6)
