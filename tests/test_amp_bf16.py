"""VERDICT r2 #5: the code path the training cells execute — bf16 GPT with
remat and flash attention — is CI-covered on CPU, and GradScaler's dynamic
loss-scaling reacts correctly to injected inf gradients."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import gpt

fa = importlib.import_module('paddle_tpu.ops.flash_attention')


def test_bench_gpt_config_three_steps_cpu():
    """GPTConfig(dtype='bfloat16', remat=True, use_flash=True) — the bench
    config — runs 3 train steps through the pallas kernels (interpret mode)
    with finite, decreasing loss."""
    fa.set_interpret(True)
    try:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=2, max_seq_len=256, dtype='bfloat16',
                            remat=True, use_flash=True)
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        opt = paddle.optimizer.AdamW(learning_rate=2e-3, weight_decay=0.01)
        opt_state = opt.functional_init(params)
        step = gpt.make_train_step(cfg, opt)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 512)
        lr = jnp.asarray(2e-3)
        losses = []
        for i in range(3):
            loss, params, opt_state = step(params, opt_state,
                                           jax.random.PRNGKey(2 + i), lr,
                                           toks, toks)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses
    finally:
        fa.set_interpret(False)


def _quad_net():
    net = paddle.nn.Linear(4, 4)
    x = paddle.to_tensor(np.random.RandomState(0).rand(8, 4).astype('float32'))
    return net, x


def test_gradscaler_skips_step_on_inf_grads():
    from paddle_tpu import amp
    net, x = _quad_net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    scaler = amp.GradScaler(init_loss_scaling=1024.0,
                            decr_every_n_nan_or_inf=1)
    w_before = np.asarray(net.weight.numpy()).copy()

    loss = scaler.scale(net(x).mean())
    loss.backward()
    # inject an overflow the way bf16 training produces one
    net.weight.grad._replace_value(
        jnp.full_like(net.weight.grad._value, jnp.inf))
    scaler.step(opt)
    opt.clear_grad()

    # step skipped: params untouched; dynamic scale halved immediately
    np.testing.assert_array_equal(np.asarray(net.weight.numpy()), w_before)
    assert scaler.get_loss_scaling() == 512.0


def test_gradscaler_steps_and_grows_on_finite_grads():
    from paddle_tpu import amp
    net, x = _quad_net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    scaler = amp.GradScaler(init_loss_scaling=8.0, incr_every_n_steps=2,
                            incr_ratio=2.0)
    w_before = np.asarray(net.weight.numpy()).copy()
    for _ in range(2):
        loss = scaler.scale(net(x).mean())
        loss.backward()
        scaler.step(opt)
        opt.clear_grad()
    assert not np.allclose(np.asarray(net.weight.numpy()), w_before)
    assert scaler.get_loss_scaling() == 16.0   # grew after 2 good steps


def test_gradscaler_unscales_before_apply():
    """The parameter update must use grad/scale, not the scaled grad."""
    from paddle_tpu import amp
    rng = np.random.RandomState(1)
    xv = rng.rand(8, 4).astype('float32')

    def train(scaling):
        paddle.seed(7)
        net = paddle.nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        scaler = amp.GradScaler(init_loss_scaling=scaling,
                                use_dynamic_loss_scaling=False)
        loss = scaler.scale(net(paddle.to_tensor(xv)).mean())
        loss.backward()
        scaler.step(opt)
        return np.asarray(net.weight.numpy())

    np.testing.assert_allclose(train(1.0), train(4096.0), rtol=1e-5)


def test_o2_eager_full_training_step():
    """O2 auto_cast in EAGER mode with scaler + clip + scheduler (advisor-
    style journey; r4: the cast hook used to recurse on its own cast op)."""
    import paddle_tpu.nn as nn
    net = nn.Linear(8, 8)
    sched = paddle.optimizer.lr.CosineAnnealingDecay(learning_rate=0.1,
                                                     T_max=10)
    opt = paddle.optimizer.AdamW(learning_rate=sched,
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0),
                                 parameters=net.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024)
    x = paddle.to_tensor(np.random.RandomState(0).rand(4, 8).astype('f4'))
    for _ in range(3):
        with paddle.amp.auto_cast(level='O2'):
            out = net(x)
            assert out.dtype == 'bfloat16' or 'bfloat16' in str(out.dtype)
            loss = (out.astype('float32') ** 2).mean()
        scaler.scale(loss).backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()
        sched.step()
    assert np.isfinite(float(loss._value))
