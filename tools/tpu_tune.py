"""TPU throughput sweep for the bench GPT config.

Runs each (batch, seq, flash, flash-block, remat) variant in a bounded
subprocess (a Mosaic failure or OOM costs one variant, not the sweep) and
prints a ranked table. Use on the real chip to pick the headline bench
config; timing uses the same host-read fence as bench.py. The parent never
touches jax, so each child gets the chip to itself.

  python tools/tpu_tune.py            # full sweep
  python tools/tpu_tune.py --quick    # 3 variants
"""
import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(cfg):
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import perf

    batch, seq = cfg['batch'], cfg['seq']
    gcfg = gpt.GPTConfig(vocab_size=32768,
                         hidden_size=cfg.get('hidden', 1024),
                         num_layers=cfg.get('layers', 24),
                         num_heads=16, max_seq_len=seq, dtype='bfloat16',
                         param_dtype=cfg.get('param_dtype', 'float32'),
                         remat=cfg['remat'], use_flash=cfg['flash'],
                         remat_policy=cfg.get('policy', 'full'),
                         scan_unroll=cfg.get('unroll', 1),
                         xent_chunk=cfg.get('xent_chunk', 8192))
    params = gpt.init_params(gcfg, jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    opt = paddle.optimizer.AdamW(learning_rate=2e-4, weight_decay=0.01)
    opt_state = opt.functional_init(params)
    step = gpt.make_train_step(gcfg, opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, 32768)
    key, lr = jax.random.PRNGKey(2), jnp.asarray(2e-4)

    fence_fn = jax.jit(lambda l, *ls: sum(
        (x.ravel()[0].astype(jnp.float32) for x in ls), l.astype(jnp.float32)))

    def fence(l, p, s):
        return float(fence_fn(l, *jax.tree_util.tree_leaves((p, s))))

    t0 = time.perf_counter()
    loss, params, opt_state = step(params, opt_state, key, lr, toks, toks)
    fence(loss, params, opt_state)
    compile_s = time.perf_counter() - t0
    iters = cfg.get('iters', 10)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt_state = step(params, opt_state, key, lr, toks, toks)
    fence(loss, params, opt_state)
    dt = time.perf_counter() - t0
    tps = batch * seq * iters / dt
    print(json.dumps({'tokens_per_sec': tps, 'n_params': n_params,
                      'compile_s': compile_s, 'step_ms': dt / iters * 1e3,
                      'loss': float(loss),
                      'device_kind': jax.devices()[0].device_kind,
                      'peak_flops': perf.peaks()[0]}))


def main():
    quick = '--quick' in sys.argv
    round2 = '--round2' in sys.argv
    variants = []
    for batch, seq in ((8, 1024), (16, 1024), (32, 1024), (4, 2048), (8, 2048)):
        variants.append(dict(batch=batch, seq=seq, flash=True, remat=True))
    variants += [
        # remat=False @350M/batch8 is a measured HBM OOM on v5e (scan carries
        # bf16[24,8,1024,1024] temps) — 'dots' selective remat is the middle
        # ground: matmul outputs saved, elementwise recomputed
        dict(batch=8, seq=1024, flash=True, remat=True, policy='dots'),
        dict(batch=16, seq=1024, flash=True, remat=True, policy='dots'),
        dict(batch=8, seq=1024, flash=False, remat=True),
        dict(batch=8, seq=1024, flash=True, remat=True, bq=512, bk=256),
        dict(batch=8, seq=1024, flash=True, remat=True, bq=512, bk=512),
        dict(batch=8, seq=1024, flash=True, remat=True, bq=128, bk=128),
    ]
    if round2:
        # measured r4 on-chip: bq512/bk512 won round 1 at 34.0k tok/s
        # (+13% over 256/256). All round-2 variants run policy='dots' so
        # the table varies ONE dimension (review r4: the first pass
        # confounded block size with remat policy, and bk>bq variants were
        # silently clamped to bk=bq by _pick_blocks — both dropped/fixed;
        # same-policy pass still showed bq1024 < bq512 at 'full').
        variants = [
            dict(batch=8, seq=1024, flash=True, remat=True, bq=512, bk=512,
                 policy='dots'),
            dict(batch=8, seq=1024, flash=True, remat=True, bq=1024, bk=512,
                 policy='dots'),
            dict(batch=8, seq=1024, flash=True, remat=True, bq=1024,
                 bk=1024, policy='dots'),
            dict(batch=8, seq=1024, flash=True, remat=True, bq=256, bk=256,
                 policy='dots'),
            dict(batch=16, seq=1024, flash=True, remat=True, bq=512, bk=512,
                 policy='dots'),
        ]
    if '--round3' in sys.argv:
        # scan-unroll rung at the r4 winning config (512-blocks + dots)
        variants = [
            dict(batch=8, seq=1024, flash=True, remat=True, bq=512, bk=512,
                 policy='dots', unroll=u) for u in (1, 2, 4)
        ]
    if '--r5' in sys.argv:
        # the >=1B rung (VERDICT r5 #1): GPT-1.3B (hidden 2048, bf16
        # params+moments). Levers: batch, remat policy, flash blocks,
        # scan unroll, blockwise-vs-naive xent — bigger GEMMs than the
        # 337M config, so the winning blocks may differ from r4's 512s.
        b13 = dict(seq=1024, hidden=2048, flash=True, remat=True,
                   param_dtype='bfloat16')
        variants = [
            dict(b13, batch=8, policy='full'),
            dict(b13, batch=8, policy='dots'),
            dict(b13, batch=16, policy='full'),
            dict(b13, batch=4, policy='full'),
            dict(b13, batch=8, policy='full', bq=512, bk=512),
            dict(b13, batch=8, policy='full', bq=256, bk=256),
            dict(b13, batch=8, policy='full', unroll=2),
            dict(b13, batch=8, policy='full', xent_chunk=0),
            dict(b13, batch=8, seq=2048, policy='full'),
            # the 337M scan-unroll rungs queued since r4 (never ran on
            # chip)
            dict(batch=8, seq=1024, flash=True, remat=True, policy='dots',
                 bq=512, bk=512, unroll=2),
            dict(batch=8, seq=1024, flash=True, remat=True, policy='dots',
                 bq=512, bk=512, unroll=4),
        ]
    if quick:
        variants = variants[:3]
    results = []
    for cfg in variants:
        env = dict(os.environ)
        if cfg.get('bq'):
            env['PADDLE_TPU_FLASH_BQ'] = str(cfg['bq'])
            env['PADDLE_TPU_FLASH_BK'] = str(cfg['bk'])
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), '--child',
                 json.dumps(cfg)],
                capture_output=True, text=True, timeout=1200, env=env)
        except subprocess.TimeoutExpired:
            print(f'{cfg}: TIMEOUT', flush=True)
            continue
        line = None
        for ln in reversed((p.stdout or '').strip().splitlines()):
            try:
                line = json.loads(ln)
                break
            except ValueError:
                continue
        if p.returncode or line is None:
            tail = (p.stderr or '').strip()[-400:]
            print(f'{cfg}: FAILED rc={p.returncode}: {tail}', flush=True)
            continue
        line['cfg'] = cfg
        results.append(line)
        mfu = (6.0 * line['n_params'] * line['tokens_per_sec']
               / line['peak_flops'])
        print(f"{cfg}: {line['tokens_per_sec']:,.0f} tok/s  "
              f"step={line['step_ms']:.1f}ms  "
              f"mfu({line['device_kind']})={mfu:.1%}  "
              f"compile={line['compile_s']:.0f}s", flush=True)
    results.sort(key=lambda r: -r['tokens_per_sec'])
    print('\nBEST:', json.dumps(results[0]) if results else 'none')


if __name__ == '__main__':
    if len(sys.argv) > 2 and sys.argv[1] == '--child':
        child(json.loads(sys.argv[2]))
    else:
        main()
