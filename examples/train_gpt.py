"""Train a GPT LM with hybrid parallelism and the native C++ data pipeline.

Single chip:      python examples/train_gpt.py --steps 50
Off-chip (CPU):   JAX_PLATFORMS=cpu python examples/train_gpt.py --steps 5
Virtual 8-dev:    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  JAX_PLATFORMS=cpu python examples/train_gpt.py \
                  --dp 2 --mp 2 --pp 2 --hidden 64 --layers 4 --steps 5
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.io.native_loader import LMTokenLoader
from paddle_tpu.models import gpt
from paddle_tpu.optimizer import lr as lr_mod
from paddle_tpu.utils.checkpoint import auto_resume


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=50)
    p.add_argument('--batch', type=int, default=8)
    p.add_argument('--seq', type=int, default=512)
    p.add_argument('--hidden', type=int, default=512)
    p.add_argument('--layers', type=int, default=8)
    p.add_argument('--heads', type=int, default=8)
    p.add_argument('--vocab', type=int, default=32768)
    p.add_argument('--dp', type=int, default=1)
    p.add_argument('--mp', type=int, default=1)
    p.add_argument('--pp', type=int, default=1)
    p.add_argument('--sp', type=int, default=1)
    p.add_argument('--lr', type=float, default=3e-4)
    p.add_argument('--ckpt', default=None)
    args = p.parse_args()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': args.dp, 'mp_degree': args.mp,
                               'pp_degree': args.pp, 'sp_degree': args.sp}
    topo = fleet.init(is_collective=True, strategy=strategy)
    print('mesh:', dict(topo.mesh.shape))

    cfg = gpt.GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                        num_layers=args.layers, num_heads=args.heads,
                        max_seq_len=args.seq, mp=args.mp, pp=args.pp,
                        sp=args.sp, n_microbatches=2 if args.pp > 1 else 1)
    opt = paddle.optimizer.AdamW(learning_rate=args.lr, weight_decay=0.01)
    sched = lr_mod.CosineAnnealingDecay(args.lr, T_max=max(args.steps, 2))

    def init_state():
        params = gpt.place_params(gpt.init_params(cfg, jax.random.PRNGKey(0)),
                                  cfg, topo.mesh)
        return {'params': params, 'opt': opt.functional_init(params)}

    if args.ckpt:
        state, start = auto_resume(args.ckpt, init_state)
    else:
        state, start = init_state(), 0
    params, opt_state = state['params'], state['opt']
    paddle.warmup.ensure_persistent_cache()    # the step compile is long
    step_fn = gpt.make_train_step(cfg, opt, topo.mesh)

    # synthetic token stream through the C++ GIL-free batcher
    stream = np.random.randint(0, args.vocab, 4_000_000).astype(np.int32)
    loader = LMTokenLoader(stream, args.batch, args.seq + 1, n_workers=2)

    t0 = time.perf_counter()
    for step in range(start, args.steps):
        batch = loader.next_batch()
        toks = jnp.asarray(batch[:, :-1].astype(np.int32))
        tgts = jnp.asarray(batch[:, 1:].astype(np.int32))
        loss, params, opt_state = step_fn(
            params, opt_state, jax.random.PRNGKey(step),
            jnp.asarray(sched(), jnp.float32), toks, tgts)
        sched.step()
        if step % 10 == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            tps = args.batch * args.seq * (step - start + 1) / dt
            print(f'step {step} loss {float(loss):.4f} '
                  f'({tps:,.0f} tok/s)')
    loader.close()
    if args.ckpt:
        from paddle_tpu.utils.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.ckpt)
        mgr.save(args.steps, {'params': params, 'opt': opt_state}, wait=True)
        mgr.close()


if __name__ == '__main__':
    main()
