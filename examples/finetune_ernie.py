"""Fine-tune an ERNIE/BERT encoder for sequence classification (the
reference ecosystem's text-classification recipe: encoder + pooled [CLS]
head, AdamW with linear warmup, padded batches with attention masks).

JAX_PLATFORMS=cpu python examples/finetune_ernie.py --steps 10 --hidden 64 \
    --layers 2 --heads 2
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp


import paddle_tpu as paddle
from paddle_tpu.models import ernie


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=30)
    p.add_argument('--batch', type=int, default=8)
    p.add_argument('--seq', type=int, default=64)
    p.add_argument('--hidden', type=int, default=128)
    p.add_argument('--layers', type=int, default=4)
    p.add_argument('--heads', type=int, default=4)
    p.add_argument('--classes', type=int, default=2)
    p.add_argument('--lr', type=float, default=3e-4)
    args = p.parse_args()

    cfg = ernie.ErnieConfig(vocab_size=1024, hidden_size=args.hidden,
                            num_layers=args.layers, num_heads=args.heads,
                            max_seq_len=args.seq)
    params = ernie.init_params(cfg, jax.random.PRNGKey(0))
    # classification head on the pooled [CLS]
    key = jax.random.PRNGKey(1)
    params['cls_w'] = (0.02 * jax.random.normal(
        key, (args.hidden, args.classes))).astype(jnp.float32)
    params['cls_b'] = jnp.zeros((args.classes,), jnp.float32)

    sched = paddle.optimizer.lr.LinearWarmup(
        paddle.optimizer.lr.PolynomialDecay(args.lr, decay_steps=args.steps),
        warmup_steps=max(args.steps // 10, 1), start_lr=0.0, end_lr=args.lr)
    opt = paddle.optimizer.AdamW(learning_rate=args.lr, weight_decay=0.01)

    def loss_fn(params, toks, mask, labels):
        h = ernie.encode(params, toks, attn_mask=mask, config=cfg)
        pooled = jnp.tanh(h[:, 0] @ params['pool_w'] + params['pool_b'])
        logits = pooled @ params['cls_w'] + params['cls_b']
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, acc

    @jax.jit
    def step(params, opt_state, lr, toks, mask, labels):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, toks, mask, labels)
        params, opt_state = opt.functional_apply(params, grads, opt_state, lr)
        return loss, acc, params, opt_state

    opt_state = opt.functional_init(params)
    rng = np.random.RandomState(0)
    t0 = time.time()
    for i in range(args.steps):
        # synthetic classification data: label = parity of the token sum
        lengths = rng.randint(args.seq // 2, args.seq + 1, args.batch)
        toks = rng.randint(5, 1024, (args.batch, args.seq))
        mask = (np.arange(args.seq)[None] < lengths[:, None])
        toks = np.where(mask, toks, 0)
        labels = (toks.sum(1) % 2).astype(np.int32)
        loss, acc, params, opt_state = step(
            params, opt_state, jnp.asarray(sched()),
            jnp.asarray(toks, jnp.int32), jnp.asarray(mask),
            jnp.asarray(labels))
        sched.step()
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f'step {i} loss {float(loss):.4f} acc {float(acc):.2f} '
                  f'lr {sched():.2e}', flush=True)
    print(f'done in {time.time() - t0:.1f}s')


if __name__ == '__main__':
    main()
