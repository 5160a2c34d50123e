"""Sharding/wire audit: resolve every param group through the partitioner
rules table and account the dp gradient collective bytes per mode.

Prints ONE line of JSON:

  {"mesh": {...}, "params": {group: spec}, "replicated_unintended": [],
   "bytes": {f32/bf16/int8/int4 + reduction ratios},
   "serving": {...}, "ok": true}

and exits non-zero when any check fails:

  - unintended replication: a >= min_size param whose logical axes name a
    live (>1-degree) mesh axis with a divisible dim must actually shard,
  - wire reduction: the quantized dp all-reduce must cut >= 3.5x bytes
    vs the native f32 gradient wire,
  - serving audit (--serving-mp N): a live mesh-sharded GenerationEngine's
    paged-KV pool planes must carry the 'mp' mesh axis on their kv_heads
    dim (in the committed arrays AND in the AOT decode executable's input
    shardings), decode-state inputs (tokens/positions/page tables/seeds)
    must stay replicated — the page allocator is host-side and
    mesh-agnostic — and no placement may have silently fallen back to
    replicated except the ones the rules table pins on purpose.

  python tools/shard_check.py                 # dp=2 x mp=4 on 8 CPU devs
  python tools/shard_check.py --dp 8 --mp 1 --mode int4
  python tools/shard_check.py --serving-mp 0  # skip the serving audit
"""
import argparse
import json
import os
import sys

os.environ.setdefault('XLA_FLAGS',
                      '--xla_force_host_platform_device_count=8')
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _spec_list(sharding):
    spec = getattr(sharding, 'spec', None)
    if spec is None:
        return None
    return [list(ax) if isinstance(ax, tuple) else ax for ax in spec]


def _is_replicated(sharding):
    spec = getattr(sharding, 'spec', ())
    return all(ax is None for ax in spec)


def serving_audit(mp):
    """Audit the mesh-sharded serving path on a live engine: returns the
    JSON sub-report plus a list of failures (empty = pass)."""
    import jax
    from paddle_tpu.models import gpt
    from paddle_tpu.ops.paged_kv import POOL_LOGICAL_AXES
    from paddle_tpu.parallel.mesh_engine import mesh_of
    from paddle_tpu.serving import sharded_generation_engine

    cfg = gpt.GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64, dtype='float32',
                        use_flash=False, remat=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    engine = sharded_generation_engine(params, cfg, mp=mp, num_slots=4,
                                       page_size=16, prefill_width=32)
    bad = []
    try:
        engine.warmup()
        ctx = mesh_of(engine)
        heads_dim = POOL_LOGICAL_AXES.index('kv_heads')

        def check_pool_plane(label, sharding):
            spec = tuple(getattr(sharding, 'spec', ()))
            if len(spec) <= heads_dim or spec[heads_dim] != 'mp':
                bad.append(f'{label}: kv_heads dim not sharded over mp '
                           f'(spec={list(spec)})')

        # 1. the committed pool arrays carry the heads mesh axis
        pool_specs = {}
        for name, plane in engine._pool.items():
            planes = plane.items() if isinstance(plane, dict) \
                else [('', plane)]
            for sub, arr in planes:
                label = f'pool.{name}.{sub}' if sub else f'pool.{name}'
                pool_specs[label] = _spec_list(arr.sharding)
                check_pool_plane(label, arr.sharding)

        # 2. the AOT decode executable agrees: pool inputs sharded on
        # heads, decode-state inputs (tok/pos/table/seeds) replicated
        compiled = engine._aot.get('gen_decode')
        exec_state = {}
        if compiled is None:
            bad.append('gen_decode: no AOT executable after warmup')
        else:
            args_sh = compiled.input_shardings[0]
            (p_sh, pool_sh, prev_sh, tok_sh, fresh_sh, pos_sh, table_sh,
             seeds_sh) = args_sh
            for name, sh in pool_sh.items():
                subs = sh.items() if isinstance(sh, dict) else [('', sh)]
                for sub, s in subs:
                    label = (f'gen_decode.pool.{name}.{sub}' if sub
                             else f'gen_decode.pool.{name}')
                    check_pool_plane(label, s)
            for label, sh in (('fed_back_tokens', prev_sh),
                              ('tokens', tok_sh), ('fresh', fresh_sh),
                              ('positions', pos_sh),
                              ('page_table', table_sh), ('seeds', seeds_sh)):
                exec_state[label] = _spec_list(sh)
                if not _is_replicated(sh):
                    bad.append(f'gen_decode.{label}: decode-state input '
                               f'must stay replicated (host-side '
                               f'allocator), got {_spec_list(sh)}')
            n_sharded = sum(
                0 if _is_replicated(s) else 1
                for s in jax.tree_util.tree_leaves(
                    p_sh, is_leaf=lambda x: hasattr(x, 'spec')))
            if n_sharded == 0:
                bad.append('gen_decode.params: every param input is '
                           'replicated — model placement did not reach '
                           'the executable')

        # 3. placement fallbacks: divisible tiny-model dims should all
        # have resolved; anything recorded here replicated by accident
        for f in ctx.fallbacks:
            bad.append(f"fallback: {f['tensor']}: {f['reason']}")

        return {'mp': mp, 'pool': pool_specs,
                'decode_state': exec_state,
                'fallbacks': list(ctx.fallbacks),
                'failures': bad, 'ok': not bad}, bad
    finally:
        engine.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--dp', type=int, default=2)
    ap.add_argument('--mp', type=int, default=4)
    ap.add_argument('--mode', default='int8',
                    choices=('bf16', 'int8', 'int4', 'fp8'))
    ap.add_argument('--min-reduction', type=float, default=3.5)
    ap.add_argument('--hidden', type=int, default=256)
    ap.add_argument('--layers', type=int, default=4)
    ap.add_argument('--vocab', type=int, default=1024)
    ap.add_argument('--serving-mp', type=int, default=2,
                    help='mesh degree for the serving-path audit '
                         '(0 skips it)')
    args = ap.parse_args()

    import jax
    jax.config.update('jax_platforms', 'cpu')
    from paddle_tpu.distributed import quant_collectives as qc
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.models import gpt

    topo = topo_mod.set_topology(
        topo_mod.HybridTopology(dp=args.dp, mp=args.mp))
    cfg = gpt.GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                        num_layers=args.layers, num_heads=4,
                        max_seq_len=128, dtype='float32', use_flash=False,
                        remat=False, mp=args.mp)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    specs = gpt.param_specs(cfg)

    flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    flat_s = dict(jax.tree_util.tree_flatten_with_path(specs)[0])
    flat_l = dict(jax.tree_util.tree_flatten_with_path(
        gpt.LOGICAL_AXES,
        is_leaf=lambda x: x is None or isinstance(x, tuple))[0])
    mesh_shape = dict(topo.mesh.shape)
    rules = dict(gpt._partitioner(cfg, explicit=False).rules)

    def _live(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        return ax is not None and all(mesh_shape.get(a, 1) > 1 for a in axes)

    resolved, replicated_bad = {}, []
    for path, p in sorted(flat_p.items(), key=lambda kv: str(kv[0])):
        name = jax.tree_util.keystr(path)
        spec = flat_s[path]
        resolved[name] = [list(ax) if isinstance(ax, tuple) else ax
                          for ax in spec]
        if p.size < qc.DEFAULT_MIN_SIZE:
            continue
        # unintended replication: a dim whose LOGICAL name maps to a live
        # mesh axis in the rules table, with a divisible size, must have
        # actually resolved sharded ('positions'/'embed' style names that
        # the table deliberately leaves unmapped never trigger this)
        logical = flat_l[path]
        for d, lname in enumerate(logical):
            ax = rules.get(lname)
            if not _live(ax):
                continue
            deg = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                deg *= mesh_shape.get(a, 1)
            if p.shape[d] % deg == 0 and spec[d] is None:
                replicated_bad.append(f'{name}[{lname}]')

    n_ranks = args.dp
    rep = qc.bytes_report(params, n_ranks=max(n_ranks, 2),
                          modes=('f32', 'bf16', args.mode))
    red_key = f'reduction_{args.mode}_vs_f32'
    reduction = rep.get(red_key, 0.0)

    serving, serving_bad = None, []
    if args.serving_mp > 1:
        serving, serving_bad = serving_audit(args.serving_mp)

    ok = (not replicated_bad and reduction >= args.min_reduction
          and not serving_bad)
    out = {
        'mesh': mesh_shape,
        'grad_quant': args.mode,
        'n_ranks': n_ranks,
        'params': resolved,
        'replicated_unintended': replicated_bad,
        'bytes': rep,
        'min_reduction': args.min_reduction,
        'serving': serving,
        'ok': ok,
    }
    print(json.dumps(out))
    if replicated_bad:
        print(f'FAIL: unintended replication: {replicated_bad}',
              file=sys.stderr)
    if reduction < args.min_reduction:
        print(f'FAIL: {red_key} = {reduction} < {args.min_reduction}',
              file=sys.stderr)
    for msg in serving_bad:
        print(f'FAIL: serving audit: {msg}', file=sys.stderr)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
