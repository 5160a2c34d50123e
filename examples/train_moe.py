"""Train a Mixtral-style MoE LM (top-2 GShard gating, expert parallel).

Single chip:      python examples/train_moe.py --steps 20
Off-chip (CPU):   JAX_PLATFORMS=cpu python examples/train_moe.py --steps 3 \
                  --hidden 64 --layers 2 --heads 2 --experts 4 --vocab 256
Virtual 8-dev EP: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  JAX_PLATFORMS=cpu python examples/train_moe.py --ep 2 \
                  --hidden 64 --layers 2 --heads 2 --experts 4 --steps 3

Reference capability: the fleet expert-parallel / incubate moe stack
(alltoall dispatch). TPU-native: expert-axis shard_map + all_to_all.
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
from paddle_tpu.models import moe_gpt


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--batch', type=int, default=8)
    p.add_argument('--seq', type=int, default=256)
    p.add_argument('--hidden', type=int, default=256)
    p.add_argument('--layers', type=int, default=4)
    p.add_argument('--heads', type=int, default=4)
    p.add_argument('--experts', type=int, default=8)
    p.add_argument('--vocab', type=int, default=8192)
    p.add_argument('--lr', type=float, default=3e-4)
    p.add_argument('--ep', type=int, default=1,
                   help='expert-parallel degree (shard experts over mesh)')
    args = p.parse_args()

    cfg = moe_gpt.MoEConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.heads,
        n_experts=args.experts, max_seq_len=args.seq,
        dtype='bfloat16' if jax.devices()[0].platform == 'tpu'
        else 'float32')

    mesh = None
    if args.ep > 1:
        from jax.sharding import Mesh
        devs = np.array(jax.devices()[:args.ep])
        mesh = Mesh(devs.reshape(args.ep), ('ep',))

    params = moe_gpt.init_params(cfg, jax.random.PRNGKey(0))
    if mesh is not None:
        # actually shard the expert banks over the 'ep' axis — without this
        # the mesh is decoration and every device holds every expert
        params = moe_gpt.place_params(params, cfg, mesh)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f'{n_params/1e6:.1f}M params, {args.experts} experts, '
          f'ep={args.ep}')
    opt = paddle.optimizer.AdamW(learning_rate=args.lr, weight_decay=0.01)
    opt_state = opt.functional_init(params)
    step = moe_gpt.make_train_step(cfg, opt, mesh)

    rs = np.random.RandomState(0)
    key = jax.random.PRNGKey(1)
    for i in range(args.steps):
        toks = jnp.asarray(rs.randint(0, args.vocab,
                                      (args.batch, args.seq)), jnp.int32)
        t0 = time.perf_counter()
        # per-step key: dropout (when configured) must draw a fresh mask
        # each step, not train a fixed pruned subnetwork
        loss, params, opt_state = step(params, opt_state,
                                       jax.random.fold_in(key, i),
                                       jnp.asarray(args.lr), toks, toks)
        loss = float(loss)
        dt = time.perf_counter() - t0
        print(f'step {i} loss {loss:.4f} '
              f'({args.batch * args.seq / dt:.0f} tok/s)')


if __name__ == '__main__':
    main()
