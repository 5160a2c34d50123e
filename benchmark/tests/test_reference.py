"""The plain reference against the program, tiny and on the CPU: the seeded
weights are the program's own initialisation bit for bit, and at float32 the
two losses and three AdamW steps agree to rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import util
from benchmark.harness import manifest

SHAPE = dict(util.TINY_MODEL)
HYPER = {'lr': 3e-5, 'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8,
         'weight_decay': 0.01}


@pytest.fixture(scope='module')
def ref():
    return manifest.load_module('reference', 'gpt')


def test_seeded_weights_are_the_programs_initialisation(ref):
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(**SHAPE, dtype='float32', use_flash=False)
    key = jax.random.PRNGKey(2 ** 31 - 5)
    ours, theirs = ref.init_params(SHAPE, key), gpt.init_params(cfg, key)
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def test_loss_and_three_steps_agree_with_the_program_at_float32(ref):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import HybridTopology
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(**SHAPE, dtype='float32', use_flash=False,
                        xent_chunk=128)
    key = jax.random.PRNGKey(3)
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, SHAPE['vocab_size'], (4, 33)).astype(np.int32)
               for _ in range(3)]
    want = ref.train_three_steps(SHAPE, key, batches, HYPER,
                                 jax.devices()[:1], True)
    mesh = HybridTopology(devices=jax.devices()[:1]).mesh
    opt = paddle.optimizer.AdamW(learning_rate=HYPER['lr'],
                                 weight_decay=HYPER['weight_decay'])
    p = gpt.place_params(ref.init_params(SHAPE, key), cfg, mesh)
    s = opt.functional_init(p)
    step = gpt.make_train_step(cfg, opt, mesh)
    got = []
    for b in batches:
        loss, p, s = step(p, s, key, jnp.float32(HYPER['lr']),
                          jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:]))
        got.append(float(loss))
    assert got == pytest.approx(want['loss'], rel=1e-5)
    delta = np.asarray(ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, ref.init_params(SHAPE, key))))
    assert delta == pytest.approx(want['delta_norm'], rel=1e-3)
    assert len(want['leaves']) == len(delta) == 16
