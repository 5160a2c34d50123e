"""Autoregressive text generation with the KV-cache decode path.

python examples/generate_gpt.py --tokens 64 --temperature 0.8 --top-k 40 --top-p 0.95

Loads (or initializes) a GPT checkpoint, prefills the prompt once, then
decodes through ONE compiled single-token step (donated cache buffers) —
see models/gpt.py make_decode_fns.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import numpy as np

import jax


import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--ckpt', default=None, help='state_dict path (.pdparams)')
    p.add_argument('--tokens', type=int, default=64)
    p.add_argument('--temperature', type=float, default=0.8)
    p.add_argument('--top-k', type=int, default=40)
    p.add_argument('--top-p', type=float, default=None,
                   help='nucleus sampling threshold (e.g. 0.95)')
    p.add_argument('--batch', type=int, default=1)
    p.add_argument('--hidden', type=int, default=256)
    p.add_argument('--layers', type=int, default=4)
    p.add_argument('--int8', action='store_true',
                   help='weight-only int8 decode (halved weight HBM bytes)')
    p.add_argument('--int8-kv', action='store_true',
                   help='int8 KV cache (per-row scales; int8 decode kernel)')
    p.add_argument('--stream', action='store_true',
                   help='serve through the continuous-batching '
                        'GenerationEngine and print tokens as each decode '
                        'iteration emits them')
    args = p.parse_args()
    if args.hidden < 64 or args.hidden % 64:
        p.error('--hidden must be a positive multiple of 64 (head_dim=64)')

    cfg = GPTConfig(vocab_size=32768, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.hidden // 64,
                    max_seq_len=1024, dtype='bfloat16', remat=False,
                    kv_cache_int8=args.int8_kv)
    model = GPTForCausalLM(cfg)
    if args.ckpt:
        model.set_state_dict(paddle.load(args.ckpt))
    model.eval()
    if args.int8:
        model.enable_int8_decode()   # weight snapshot quantizes lazily

    prompt = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size,
                          (args.batch, 16)).astype('int32'))
    if args.stream:
        # continuous batching: every prompt is its own request; the engine
        # interleaves them at the decode-iteration level and each future's
        # stream() yields tokens the moment their iteration completes
        from paddle_tpu.serving import GenerationEngine
        engine = GenerationEngine(
            model, num_slots=max(args.batch, 2),
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p)
        engine.warmup()            # both executables built before traffic
        rows = np.asarray(prompt.numpy(), dtype=np.int32)
        t0 = time.perf_counter()
        futs = [engine.submit(rows[b], max_new_tokens=args.tokens, seed=b)
                for b in range(args.batch)]
        n_out = 0
        for b, fut in enumerate(futs):
            sys.stdout.write(f'seq {b}: ')
            for tok in fut.stream(timeout=600):
                sys.stdout.write(f'{tok} ')
                sys.stdout.flush()
                n_out += 1
            sys.stdout.write('\n')
        dt = time.perf_counter() - t0
        engine.shutdown()
        print(f'streamed {n_out} tokens in {dt:.2f}s '
              f'({n_out / dt:,.1f} tok/s); stats: '
              f'{ {k: engine.stats()[k] for k in ("steps", "evictions", "traces")} }')
        return
    # warm the prefill+step compiles
    model.generate(prompt, max_new_tokens=2, temperature=0)
    t0 = time.perf_counter()
    out = model.generate(prompt, max_new_tokens=args.tokens,
                         temperature=args.temperature, top_k=args.top_k,
                         top_p=args.top_p)
    toks = out.numpy()                       # host read fences the chain
    dt = time.perf_counter() - t0
    print(f'generated {args.batch}x{args.tokens} tokens in {dt:.2f}s '
          f'({args.batch * args.tokens / dt:,.1f} tok/s)')
    print('first sequence:', toks[0, -args.tokens:].tolist()[:16], '...')


if __name__ == '__main__':
    main()
