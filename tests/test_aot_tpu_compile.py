"""What only the chip's compiler can refuse, checked without the chip.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (a ``v5e:2x2`` topology): nothing runs, so this says
nothing about results or speed, but it raises what Mosaic and the SPMD
partitioner would raise on the machine — which interpret mode never did.
Two refusals got past every interpret-mode test before PR 21: the in-kernel
dropout (``Unsupported cast: uint32 -> float32``) and any kernel under a
mesh (``Mosaic kernels cannot be automatically partitioned``). Each compile
takes a second or two. Skipped where the topology cannot be described.

All the compiles run in ONE child process (this file, run as a script),
once per test session: describing a TPU loads libtpu, and a test process
that has loaded it records profiler traces differently
(tests/test_devtime.py). Each case is still its own test.

Code that asks ``jax.devices()`` still sees the CPU, so the child steers the
kernels' platform gate itself (``_platform_ok``) — the program has no
option for it.

Also here, because they are about the same machine: one process per chip
(imports initialise no backend; the launcher refuses to share chips) and
one compilation per train step.
"""
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the child: every described-chip compile, one JSON object out
# ---------------------------------------------------------------------------

def _cases(devices):
    """name -> thunk returning the compiled program's text, or the
    compiled program itself (an engine's: its temporaries are read too)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    pa = importlib.import_module('paddle_tpu.ops.paged_attention')
    paged_kv = importlib.import_module('paddle_tpu.ops.paged_kv')
    mesh_kernel = importlib.import_module('paddle_tpu.ops.mesh_kernel')
    fa._platform_ok = lambda: True       # the kernels' TPU branch
    one = SingleDeviceSharding(devices[0])
    bf16 = jnp.bfloat16

    def S(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def text(fn, *args, mesh=None, donate=()):
        def scoped(*a):
            with mesh_kernel.kernel_mesh(mesh):
                return fn(*a)
        return jax.jit(scoped, donate_argnums=donate).lower(
            *args).compile().as_text()

    def flash_fwd_bwd(dropout=0.0):
        def f(q, k, v):
            def loss(q, k, v):
                out = fa.flash_attention(
                    q, k, v, causal=True, dropout_rate=dropout,
                    dropout_seed=jnp.uint32(7) if dropout else None)
                return jnp.sum(out.astype(jnp.float32) ** 2)
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return f

    def flash(seq, d, kv_heads, dropout, seq_q=None):
        q = S((2, seq_q or seq, 8, d), bf16)
        kv = S((2, seq, kv_heads, d), bf16)
        return lambda: text(flash_fwd_bwd(dropout), q, kv, kv)

    def pool(d, int8, sharding=one):
        pages = (65, 16, 128, d)    # the 337M engine's pool, head-major
        if not int8:
            return S(pages, bf16, sharding)
        return {'int8': S(pages, jnp.int8, sharding),
                'scale': S(pages[:3], jnp.float32, sharding)}

    def paged(d, int8, t=1, heads=16):
        q = S((8, t, heads, d), bf16)
        assert pa.paged_attention_available(q, pool(d, False))
        kernel = pa.paged_flash_decode_int8 if int8 else pa.paged_flash_decode
        return lambda: text(kernel, q, pool(d, int8), pool(d, int8),
                            S((8, 8), jnp.int32), S((8,), jnp.int32))

    def paged_at(slots, heads, kv_heads, p_max, pages):
        planes = S((pages, kv_heads, 128, 128), bf16)
        return lambda: text(
            pa.paged_flash_decode, S((slots, 1, heads, 128), bf16), planes,
            planes, S((slots, p_max), jnp.int32), S((slots,), jnp.int32))

    def decode(int8):
        q, cache = S((8, 1, 16, 64), bf16), (8, 1024, 16, 64)
        bank = ({'int8': S(cache, jnp.int8), 'scale': S(cache[:3],
                                                        jnp.float32)}
                if int8 else S(cache, bf16))
        kernel = fa.flash_decode_int8 if int8 else fa.flash_decode
        return lambda: text(kernel, q, bank, bank, S((), jnp.int32))

    dpmp = Mesh(np.array(devices).reshape(2, 2), ('dp', 'mp'))
    qm = S((4, 1024, 8, 64), bf16,
           NamedSharding(dpmp, P('dp', None, 'mp', None)))
    mp4 = Mesh(np.array(devices), ('mp',))
    heads = NamedSharding(mp4, P(None, None, 'mp', None))    # q's heads
    pool_heads = NamedSharding(mp4, P(None, 'mp', None, None))
    rep = NamedSharding(mp4, P())

    # a decode step's row a slot into a plane as a served cell carries it
    # (PR 41): alone, and under the engine's mesh, where the heads that
    # split over 'mp' are split and the others stay whole on every chip
    def row_write(pages, h, slots, p_max, mesh=None):
        def at(*spec):
            if mesh is None:
                return one
            return NamedSharding(
                mesh, P(*spec) if h % mesh.shape['mp'] == 0 else P())
        return lambda: text(
            paged_kv.paged_write,
            S((pages, h, 128, 128), bf16, at(None, 'mp', None, None)),
            S((slots, 1, h, 128), bf16, at(None, None, 'mp', None)),
            S((slots, p_max), jnp.int32, at()), S((slots,), jnp.int32, at()),
            mesh=mesh, donate=(0,))     # the plane, as the engine's pool is

    # the engine's two WHOLE executables (PR 28), built as the engine
    # builds them (``GenerationEngine._build_fns``: sampling, the logits
    # row, the pool donated) from abstract weights and an abstract pool
    def engine_program(which, cfg, slots, pages, ps=128, width=None,
                       as_given=False):
        import types
        from paddle_tpu.models import family as _family
        from paddle_tpu.serving.generation import GenerationEngine
        fam = _family.family_of(cfg)
        # the parameters as the engine holds them (PR 32); ``as_given``:
        # as it held them before, float32 under a bfloat16 program
        held = (fam.serve_params if fam.serve_params and not as_given
                else lambda params, cfg: params)
        prefill, step = GenerationEngine._build_fns(types.SimpleNamespace(
            config=cfg, _forward_fn=fam.forward_with_cache, temperature=0.0,
            top_k=0, top_p=1.0, _trace_count=0, _mesh_ctx=None))
        model = importlib.import_module(type(cfg).__module__)

        def abstract(make):
            return jax.tree_util.tree_map(
                lambda a: S(a.shape, a.dtype), jax.eval_shape(make))
        params = abstract(lambda: held(
            model.init_params(cfg, jax.random.PRNGKey(0)), cfg))
        pool = abstract(lambda: fam.init_pool(cfg, pages, ps))
        i32 = lambda *shape: S(shape, jnp.int32)            # noqa: E731
        p_max = -(-cfg.max_seq_len // ps)

        def table(rows):        # a table a kind where the family names them
            if fam.page_kinds is None:
                return i32(rows, p_max)
            return {k.name: i32(rows) if k.per_slot else i32(rows, p_max)
                    for k in fam.page_kinds(cfg)}
        if which == 'step':
            # the previous step's tokens, the host's, which rows take the
            # host's, positions, tables, seeds
            return lambda: step.lower(
                params, pool, i32(slots), i32(slots), S((slots,), jnp.bool_),
                i32(slots), table(slots), i32(slots)).compile()
        return lambda: prefill.lower(
            params, pool, i32(1, width or cfg.max_seq_len), i32(1), i32(1),
            table(1), i32(1)).compile()

    from paddle_tpu.models import gpt, moe_gpt

    def split_folded(program):
        """``program`` with a cached block's q, k, v made as the train
        block makes them, and as it made them before PR 44: the head
        split left for the compiler to fold into the product."""
        def compile_it():
            kept = gpt._cached_qkv
            gpt._cached_qkv = lambda bp, y, cfg, cdt: gpt._block_qkv(
                bp, y, cfg.num_heads, cfg.head_dim, cdt, cfg.kv_heads)
            try:
                return program()
            finally:
                gpt._cached_qkv = kept
        return compile_it

    # benchmark/configs/gpt-1.3b-serve.json: 24 layers, 129 pages of 128
    # rows, 16 heads of 128, 16 slots, bf16 over float32 weights
    xl = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
              num_heads=16, max_seq_len=1024, dtype='bfloat16',
              param_dtype='float32')
    moe = moe_gpt.MoEConfig(vocab_size=50304, hidden_size=1024, num_layers=4,
                            num_heads=8, n_experts=4, max_seq_len=1024,
                            dtype='bfloat16')

    # the latent family's kernels at dots-vlm1-ep16-serve's own shapes
    # (PR 27): 64 slots of 128 heads against the headless pool, the grouped
    # expert product at a decode step's and a prefill's tile sizes, and the
    # prefill's attention at widths 192 / 128 through the flash forward
    pla = importlib.import_module('paddle_tpu.ops.paged_latent_attention')
    gmm = importlib.import_module('paddle_tpu.ops.expert_grouped_matmul')

    def latent(width):
        return lambda: text(
            lambda q, pool, pt, pos: pla.paged_latent_attention(
                q, pool, pt, pos, 2, scale=0.135, rank=512),
            S((64, 128, width), bf16), S((5, 1025, 128, width), bf16),
            S((64, 16), jnp.int32), S((64,), jnp.int32))

    def grouped(m, tm, k, n):
        return lambda: text(
            lambda x, w, te, nt: gmm.expert_grouped_matmul(x, w, te, nt,
                                                           tm=tm),
            S((m, k), bf16), S((16, k, n), bf16), S((m // tm,), jnp.int32),
            S((1,), jnp.int32))

    # benchmark/configs/trinity-large-ep8-serve.json (PR 31): window and
    # full attention over a pool of two kinds of plane, 48 query heads on 8
    # KV heads of 128, experts 0-31 of 256, 24 slots of 16,384 rows
    from paddle_tpu.models import afmoe
    trinity = afmoe.AfmoeConfig(
        vocab_size=25024, num_hidden_layers=5, num_dense_layers=1,
        layer_types=('sliding_attention',) * 4 + ('full_attention',),
        held=(0, 32), max_position_embeddings=16384)
    trinity_pages = {'full': 24 * 128 + 1, 'window': 24 * 33 + 1}

    def windowed_flash(seq, window):
        q, kv = S((1, seq, 48, 128), bf16), S((1, seq, 8, 128), bf16)
        return lambda: text(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window), q, kv, kv)

    def windowed_paged(window):
        pages = S((3173, 8, 128, 128), bf16)    # four window layers' planes
        return lambda: text(
            lambda q, k, v, pt, pos: pa.paged_flash_decode(
                q, k, v, pt, pos, window=window),
            S((24, 1, 48, 128), bf16), pages, pages,
            S((24, 128), jnp.int32), S((24,), jnp.int32))

    # benchmark/configs/granite-4.0-h-micro-serve.json (PR 34): 36
    # state-space layers whose state is a row a slot beside 4 attention
    # layers' pages, 64 slots of 2,048 rows, all 40 layers, scanned a period
    from paddle_tpu.models import granite_hybrid
    granite = granite_hybrid.GraniteHybridConfig(max_position_embeddings=2048)
    granite_units = {'kv': 64 * 16 + 1, 'state': 64}

    # benchmark/configs/dots-vlm1-ep16-serve.json (PR 27): the whole step of
    # the latent family as one chip of sixteen holds it
    from paddle_tpu.models import latent_moe
    dots = latent_moe.LatentMoEConfig(
        num_hidden_layers=5, first_k_dense_replace=1, vocab_size=16160,
        max_position_embeddings=2048, held=(0, 16))

    # benchmark/configs/zaya1-8b-pp2-serve.json (PR 40): 20 layers of
    # compressed convolutional attention and 16 experts, one a token, 48
    # slots of 3,072 rows, the tails a slot beside K and V pages, the
    # stack scanned with the router's state in the carry
    from paddle_tpu.models import zaya
    zaya1 = zaya.ZayaConfig(num_hidden_layers=20,
                            max_position_embeddings=3072)
    zaya_units = {'kv': 48 * 24 + 1, 'tail': 48}

    # benchmark/configs/brumby-14b-pp5-serve.json (PR 42): 8 layers of power
    # retention, 16 slots whose matrix state (4.36 GB) is a row a slot, no
    # page pool at all
    from paddle_tpu.models import brumby
    brumby14 = brumby.BrumbyConfig(num_hidden_layers=8,
                                   max_position_embeddings=3072)
    brumby_units = {'state': 16}

    # the four served cells' planes, flat over their layers: GPT-3 XL's,
    # zaya's, trinity's full layer's, granite's (8 KV heads of 64, two a row)
    rows = {'gpt_xl': (3096, 16, 16, 8), 'zaya': (23060, 2, 48, 24),
            'trinity': (3073, 8, 24, 128), 'granite': (4100, 4, 64, 16)}
    return {
        **{f'row_write_{k}': row_write(*v) for k, v in rows.items()},
        **{f'row_write_{k}_mp4': row_write(*v, mesh=mp4)
           for k, v in rows.items()},
        'zaya_step': engine_program('step', zaya1, 48, zaya_units),
        'zaya_prefill': engine_program('prefill', zaya1, 48, zaya_units,
                                       width=1024),
        'latent_step': engine_program('step', dots, 64, 1025),
        'brumby_step': engine_program('step', brumby14, 16, brumby_units),
        'brumby_prefill': engine_program('prefill', brumby14, 16,
                                         brumby_units, width=1024),
        # a prompt of two chunks (no cell's engine is that wide yet)
        'brumby_prefill_2048': engine_program('prefill', brumby14, 16,
                                              brumby_units, width=2048),
        'granite_step': engine_program('step', granite, 64, granite_units),
        'granite_prefill': engine_program('prefill', granite, 64,
                                          granite_units, width=768),
        'afmoe_step': engine_program('step', trinity, 24, trinity_pages),
        'afmoe_prefill_1024': engine_program('prefill', trinity, 24,
                                             trinity_pages, width=1024),
        # widths that are no multiple of the MLP half's 4,096-row pieces,
        # beside the widest
        'afmoe_prefill_6144': engine_program('prefill', trinity, 24,
                                             trinity_pages, width=6144),
        'afmoe_prefill_14336': engine_program('prefill', trinity, 24,
                                              trinity_pages, width=14336),
        'afmoe_prefill_16384': engine_program('prefill', trinity, 24,
                                              trinity_pages, width=16384),
        'flash_window_s16384_gqa6': windowed_flash(16384, 4096),
        'flash_full_s16384_gqa6': windowed_flash(16384, None),
        'paged_gqa6_window': windowed_paged(4096),
        'gpt_xl_step': engine_program('step', gpt.GPTConfig(**xl), 16, 129),
        'gpt_xl_prefill': engine_program('prefill', gpt.GPTConfig(**xl),
                                         16, 129),
        'gpt_xl_step_int8_kv': engine_program(
            'step', gpt.GPTConfig(kv_cache_int8=True, **xl), 16, 129),
        'gpt_xl_step_params_as_given': engine_program(
            'step', gpt.GPTConfig(**xl), 16, 129, as_given=True),
        'gpt_xl_step_split_folded': split_folded(engine_program(
            'step', gpt.GPTConfig(**xl), 16, 129)),
        'moe_gpt_step': engine_program('step', moe, 16, 129),
        'latent_decode_w640': latent(640),
        'latent_decode_w576': latent(576),
        'grouped_decode_up': grouped(768, 16, 7168, 2048),
        'grouped_decode_down': grouped(768, 16, 2048, 7168),
        'grouped_prefill_up': grouped(10240, 128, 7168, 2048),
        'grouped_prefill_down': grouped(10240, 128, 2048, 7168),
        'latent_prefill_192_128': lambda: text(
            lambda q, k, v: pla.latent_prefill_attention(q, k, v,
                                                         scale=0.135),
            S((1, 1024, 128, 192), bf16), S((1, 1024, 128, 192), bf16),
            S((1, 1024, 128, 128), bf16)),
        'flash_s1024_d64': flash(1024, 64, 8, 0.0),
        'flash_s2048_d128': flash(2048, 128, 8, 0.0),
        'flash_gqa': flash(1024, 64, 2, 0.0),
        'flash_dropout': flash(1024, 64, 8, 0.1),
        # what the causal tile schedule makes new (PR 25): a diagonal that
        # does not start at the first key, blocks that fall to 128 rows,
        # and a cut last k/v block
        'flash_cross_q512_k1024': flash(1024, 64, 8, 0.0, seq_q=512),
        'flash_s1152_blocks_128': flash(1152, 64, 8, 0.0),
        'flash_s1100_kv_valid': flash(1100, 64, 8, 0.0),
        'paged_bf16_d64': paged(64, False),
        'paged_int8_d64': paged(64, True),
        'paged_bf16_d128': paged(128, False),
        'paged_int8_d128': paged(128, True),
        # what PR 30 makes new: a KV group's query heads as rows of one
        # block (64 heads over the pool's 16), a tail call's rows among
        # them (the q row of a score is its row modulo T), 128 rows a head
        'paged_gqa4_tail5': paged(128, False, t=5, heads=64),
        'paged_int8_tail128': paged(64, True, t=128),
        # what PR 43 makes new: a grid whose bound is read on the device,
        # at the shape it pays most: zaya1-8b-pp2-serve's 48 slots of 24
        # pages, 8 query heads on 2 KV heads of 128, one layer's plane
        'paged_zaya_48x24': paged_at(48, 8, 2, 24, 1153),
        'decode_bf16': decode(False),
        'decode_int8': decode(True),
        'flash_dropout_dp2_mp2': lambda: text(flash_fwd_bwd(0.1), qm, qm, qm,
                                              mesh=dpmp),
        'flash_dp2_mp2_no_mesh_named': lambda: text(flash_fwd_bwd(), qm, qm,
                                                    qm),
        'paged_mp4': lambda: text(
            pa.paged_flash_decode, S((8, 1, 16, 64), bf16, heads),
            pool(64, False, pool_heads), pool(64, False, pool_heads),
            S((8, 8), jnp.int32, rep), S((8,), jnp.int32, rep), mesh=mp4),
    }


# a pool, one layer's plane, or either flattened over layers and pages
# ([3096,...] is [24*129,...]); the scales of an int8 bank among them
_POOL = (r'(bf16|s8|f32)\[(?:5,1025,128,\d+|24,129,16,128(?:,128)?'
         r'|3096,16,128(?:,128)?|129,16,128(?:,128)?'
         r'|4,129,8,128,128|516,8,128,128|129,8,128,128'
         # trinity-large-ep8-serve: one full layer's planes, four window
         # layers', each flattened over its layers, and one layer's
         r'|1,3073,8,128,128|3073,8,128,128|4,793,8,128,128'
         r'|3172,8,128,128|3173,8,128,128|793,8,128,128'
         # granite-4.0-h-micro-serve: the state and the convolution's tail
         # a slot, K and V pages of two heads a row; whole and flat
         r'|36,64,128,32,128|2304,128,32,128|36,64,13056|2304,13056'
         r'|4,1025,4,128,128|4100,4,128,128|1025,4,128,128'
         # zaya1-8b-pp2-serve: K and V pages of two heads of 128, whole
         # and flat (the tails a slot, 5 MB of all layers', are scanned:
         # every slot's row is rewritten in every step anyway)
         r'|20,1153,2,128,128|23060,2,128,128|1153,2,128,128'
         # brumby-14b-pp5-serve: the matrix state a slot, whole and flat
         # over its layers (4.40 GB; the normaliser's plane beside it is
         # 34 MB, and the compiler takes that one through fast memory)
         r'|8,16,8,128,8320|128,8,128,8320)\]')


# a step's slots and the heads of a page of its planes
_STEP_PAGES = {'gpt_xl_step': (16, 16), 'moe_gpt_step': (16, 8),
               'zaya_step': (48, 2), 'afmoe_step': (24, 8),
               'granite_step': (64, 4)}


# the line a computation of a compiled program's text opens with
_COMPUTATION = re.compile(r'(?:ENTRY )?(%\S+) \(.*\{$')


def _pool_copies(text):
    """The operations of a compiled program whose RESULT is pool-shaped
    and that move it: everything but the parameter, views of it (a
    bitcast, an element of the loop's state) and the update in place (a
    scatter or dynamic-update-slice, or the fusion whose computation ends
    in one: the compiler aliases that fusion's result to its operand; or a
    kernel whose result IS its operand's buffer, ``paged_row_write``)."""
    result = re.compile(r'(%\S+) = ' + _POOL + r'\S* ([\w\-]+)\(')
    views = ('parameter', 'bitcast', 'get-tuple-element')
    updates = ('scatter', 'dynamic-update-slice')
    in_place, computation = set(), None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
        m = result.search(line)
        if m and 'ROOT' in line and m.group(3) in updates:
            in_place.add(computation)
    moved = []
    for line in text.splitlines():
        m = result.search(line)
        if not m or m.group(3) in views + updates:
            continue
        calls = re.search(r'calls=(%[\w.\-]+)', line)
        if m.group(3) == 'fusion' and calls and calls.group(1) in in_place:
            continue
        if m.group(3) == 'custom-call' and 'output_to_operand_aliasing' in line:
            continue
        moved.append(f'{m.group(3)} of {m.group(2)}')
    return moved


def _schedule(text):
    """Where the paged calls of a compiled program get their schedule
    (``ops/paged_attention.page_schedule``: the grid's bound, operand 0 of
    the call, and the steps' slots and pages, operands 3 and 4, behind the
    table and the positions) -> ``{'calls': paged calls in the program,
    'in_loop': whether one lies in a ``while`` body, 'ops': the distinct
    instructions, views and copies between memories aside, between those
    operands and their computation's parameters}``: what is computed in EVERY pass of the
    body a call lies in. A schedule handed in through the loop's state
    reads 0."""
    bodies = set(re.findall(r'body=(%[\w.\-]+)', text))
    calls, ops, in_loop, defs, computation = 0, set(), False, {}, None
    pending = []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation, defs, pending = head.group(1), {}, []
            continue
        if line.startswith('}'):
            for operands in pending:
                calls += 1
                in_loop = in_loop or computation in bodies
                todo = [operands[i] for i in (0, 3, 4)]
                while todo:
                    name = todo.pop()
                    op, args = defs.get(name, ('parameter', []))
                    if op in ('parameter', 'constant') or (
                            computation, name) in ops:
                        continue
                    if op not in ('get-tuple-element', 'bitcast', 'copy',
                                  'copy-start', 'copy-done'):
                        ops.add((computation, name))
                    todo += args
            pending = []
            continue
        m = re.match(r'\s*(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(([^)]*)\)',
                     line)
        if not m:
            continue
        args = re.findall(r'%[\w.\-]+', m.group(3))
        defs[m.group(1)] = (m.group(2), args)
        if re.match(r'%paged_(latent_)?attention(_window)?[.\d]*$',
                    m.group(1)) and m.group(2) == 'custom-call':
            pending.append(args)
    return {'calls': calls, 'in_loop': in_loop, 'ops': len(ops)}


def _block_matrices(text):
    """-> ({stacked block matrix: the dtype the program takes it in},
    [the operations that re-make one], [those that re-make ONE LAYER of
    one]). A stacked matrix is an entry parameter
    ``params['blocks'][<name ending in _w, w_in, w_out>]``; an operation
    re-makes it if its result has the matrix's shape, in any dtype, and is
    no view of it: before PR 32 a ``convert`` of each of the float32 stacks
    to bfloat16, in every call. It re-makes a layer if its result is
    ``[1, <the layer's axes, the last two in either order>]`` in any
    layout, is no view, and is an instruction of the entry or of a loop's
    body: before PR 44 a slice of ``qkv_w`` into fast memory and a
    transposing copy of it, in every layer of every call. A
    ``dynamic-slice`` INSIDE a fused computation is the wanted form, the
    product reading its layer where the stack holds it, and does not
    count."""
    entry = text[text.index('ENTRY '):]
    taken = {}
    for name, dtype, dims in re.findall(
            r'%params__blocks____(\w+?)__[.\d]* = (\w+)\[([\d,]+)\]\S* '
            r'parameter\(', entry):
        if name.endswith('_w') or name in ('w_in', 'w_out'):
            taken[name] = (dtype, dims)
    if not taken:       # a kernel's program: no model under it
        return {}, [], []
    views = ('parameter', 'bitcast', 'get-tuple-element')

    def results(shapes, lines):
        made = re.compile(r'= (\w+)\[(' + '|'.join(sorted(map(
            re.escape, shapes))) + r')\]\S* ([\w\-]+)\(')
        return sorted(f'{op} of {dtype}[{dims}]' for line in lines
                      for dtype, dims, op in made.findall(line)
                      if op not in views)

    layers = set()
    for _, dims in taken.values():
        *lead, a, b = dims.split(',')[1:]
        layers |= {','.join(['1', *lead, a, b]), ','.join(['1', *lead, b, a])}
    fused = set(re.findall(r' fusion\(.*?calls=(%[\w.\-]+)', text))
    unfused, computation = [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
        elif computation not in fused:
            unfused.append(line)
    return ({k: dtype for k, (dtype, _) in taken.items()},
            results({dims for _, dims in taken.values()}, text.splitlines()),
            results(layers, unfused))


def _child():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    # off: an entry written for a described chip cannot be read back
    jax.config.update('jax_enable_compilation_cache', False)
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        return {'skip': f'cannot describe a v5e:2x2 topology here: {e!r}'}
    out = {}
    for name, compile_text in _cases(list(topo.devices)).items():
        try:
            program = compile_text()
            text = program if isinstance(program, str) else program.as_text()
            moved = _pool_copies(text)
            taken, remade, layers_remade = _block_matrices(text)
            out[name] = {
                'temp_bytes': None if isinstance(program, str) else int(
                    program.memory_analysis().temp_size_in_bytes),
                'block_matrices': taken, 'block_matrices_remade': remade,
                'block_layers_remade': layers_remade,
                'kernels': text.count('tpu_custom_call'),
                'pool_copies': len(moved), 'moved': sorted(set(moved)),
                # q as the kernel took it before PR 30: 16 slots x 16
                # heads, each head's one row padded to 128
                # (8 heads in the moe_gpt case)
                'padded_q': bool(re.search(r'bf16\[(256|128),128,128\]',
                                           text)),
                'names': sorted(set(re.findall(
                    r'%((?:paged_attention|flash_fwd)(?:_window)?'
                    r'|ssm_state_update|retention_state_update)[.\d]* = ',
                    text))),
                # the in-place row writes of a decode step (PR 41), and
                # what the page form made around them: a page a slot read,
                # rebuilt and scattered back, ``[slots, H, 128, 128]``, and
                # a page-long buffer a slot, ``[slots, 128, H, 128]``
                'row_writes': len(re.findall(
                    r'%paged_row_write[.\d]* = ', text)),
                'page_buffers': sorted(set(re.findall(
                    r'= (bf16\[(?:{0},{1},128,128|{0},128,{1},128)\])'.format(
                        *_STEP_PAGES[name]), text)))
                if name in _STEP_PAGES else None,
                # what a program makes of a scanned stack's experts beside
                # views of them
                'expert_stacks_moved': sorted(set(re.findall(
                    r'= \w+\[(?:320|20,16|16),2048,2048\]\S* '
                    r'(?!parameter|bitcast|get-tuple-element)([\w\-]+)\(',
                    text))),
                # the entry parameter a step takes the previous step's
                # tokens in, where that step left them on the device
                'fed_back': re.findall(
                    r'%prev[.\d]* = (s32\[\d+\])\S* parameter\(',
                    text[text.index('ENTRY '):]),
                'schedule': _schedule(text),
                'collectives': [c for c in (
                    'all-reduce', 'all-gather', 'all-to-all',
                    'collective-permute') if c in text]}
        except Exception as e:   # noqa: BLE001 — the refusal IS the result
            out[name] = {'error': f'{type(e).__name__}: {e}'[:400]}
    return out


if __name__ == '__main__':
    sys.path.insert(0, REPO)
    print(json.dumps(_child()))
    sys.exit(0)


# ---------------------------------------------------------------------------
# the tests: one per compiled program
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def compiled():
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], cwd=REPO,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if 'skip' in result:
        pytest.skip(result['skip'])
    return result


def _summary(case):
    return {k: case[k] for k in ('kernels', 'pool_copies', 'collectives')}


@pytest.mark.parametrize('case,kernels', [
    ('flash_s1024_d64', 3),            # fwd, dq, dk/dv
    ('flash_s2048_d128', 3),
    ('flash_gqa', 3),
    ('flash_dropout', 3),              # refused before PR 21: u32->f32 cast
    ('flash_cross_q512_k1024', 3), ('flash_s1152_blocks_128', 3),
    ('flash_s1100_kv_valid', 3),
    ('paged_bf16_d64', 1), ('paged_int8_d64', 1),
    ('paged_bf16_d128', 1), ('paged_int8_d128', 1),
    ('paged_gqa4_tail5', 1), ('paged_int8_tail128', 1),
    ('paged_zaya_48x24', 1),
    ('decode_bf16', 1), ('decode_int8', 1),
])
def test_kernel_compiles_for_v5e(compiled, case, kernels):
    """The paged cases at D 64 and D 128 take ONE path: a page is stored
    head-major, so the kernel's ``[heads, page_size, D]`` block (every
    head of the page since PR 30) is read out of the pool as it lies
    whatever the head size; ``pool_copies`` 0 says the wrapper re-lays
    nothing in front of the call (before PR 28 it transposed every plane
    it was handed). The form that copies its own pages out of HBM
    (``memory_space=ANY``, ``make_async_copy``) compiles at D 128 and is
    refused at D 64 (a 64-wide row lies in 128 lanes and Mosaic slices no
    such array), which is why the pipeline fetches the pages."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]


@pytest.mark.parametrize('case,kernels', [
    # the paged kernel and the K and V row writes before it (PR 41), once
    # in the layers' loop
    ('gpt_xl_step', 1 + 2),
    ('gpt_xl_prefill', 0),    # a tail prefill gathers; no kernel under it
    ('moe_gpt_step', 1 + 2),
])
def test_engine_program_never_remakes_its_pool(compiled, case, kernels):
    """The engine's WHOLE decode and prefill executables at
    ``gpt-1.3b-serve``'s shapes (and ``moe_gpt``'s step through the same
    driver): no operation's result is the pool, a layer's plane or their
    flattened forms except views and the in-place row write. Before PR 28
    the scan's stacked outputs and the kernel's transposes made nine a
    step (PERF.md section 6)."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]


@pytest.mark.parametrize('remade', ['block_matrices_remade',
                                    'block_layers_remade'],
                         ids=['no_stack_remade', 'no_layer_remade'])
@pytest.mark.parametrize('case,matrices', [
    ('gpt_xl_step', ('fc_w', 'out_w', 'proj_w', 'qkv_w')),
    ('gpt_xl_prefill', ('fc_w', 'out_w', 'proj_w', 'qkv_w')),
    ('gpt_xl_step_int8_kv', ('fc_w', 'out_w', 'proj_w', 'qkv_w')),
    ('moe_gpt_step', ('gate_w', 'proj_w', 'qkv_w', 'w_in', 'w_out')),
])
def test_engine_program_takes_its_block_matrices_in_the_compute_dtype(
        compiled, case, matrices, remade):
    """The engine hands its executables the family's product operands in
    bfloat16 (``family.serve_params``, cast once when the engine is built),
    and no operation of the program has a stacked matrix's shape but the
    parameter and views of it: nothing re-makes the weights in a call. Nor
    (PR 44) does an instruction of the layers' loop have ONE LAYER's shape:
    every product takes the stack and the layer's index and reads its
    matrix where the stack holds it."""
    assert compiled[case]['block_matrices'] == dict.fromkeys(
        matrices, 'bf16'), compiled[case]
    assert compiled[case][remade] == [], compiled[case]


def test_a_head_split_folded_into_the_qkv_product_slices_and_transposes_its_layer(
        compiled):
    """What PR 44 took out, and that the reading above can see it: left to
    fold ``reshape(B, T, kvh, g + 2, hd)`` into the product, the compiler
    makes a convolution over the ``16 x 3`` window that wants its weight
    ``[16, 3, 128, 2048]``, contraction axis minor; the stack holds
    ``[24, 2048, 6144]``, so every layer of every step copies its 25 MB
    out into fast memory and transposes the copy (25 % of chat's busy
    time, 15 % of doc's: ledger, PR 43). Behind
    ``jax.lax.optimization_barrier`` the product's result is
    ``[16, 1, 6144]`` and neither is there."""
    case = compiled['gpt_xl_step_split_folded']
    assert case['block_matrices_remade'] == [], case
    assert case['block_layers_remade'] == [
        'copy of bf16[1,2048,6144]', 'fusion of bf16[1,2048,6144]'], case


def test_a_step_over_float32_matrices_converts_every_one_in_every_call(
        compiled):
    """What PR 32 took out, and that the reading above can see it: handed
    the parameters as given (float32 under a bfloat16 program), the step
    converts the four stacks, 1.2 G parameters, before its first layer:
    4.8 GB read and 2.4 GB written, half of a decode step's device time on
    the chip (PERF.md section 6)."""
    case = compiled['gpt_xl_step_params_as_given']
    assert case['block_matrices'] == dict.fromkeys(
        ('fc_w', 'out_w', 'proj_w', 'qkv_w'), 'f32'), case
    assert case['block_matrices_remade'] == [
        'convert of bf16[24,2048,2048]', 'convert of bf16[24,2048,6144]',
        'convert of bf16[24,2048,8192]', 'convert of bf16[24,8192,2048]']


@pytest.mark.parametrize('case', ['gpt_xl_step', 'gpt_xl_step_int8_kv',
                                  'moe_gpt_step'])
def test_step_program_pads_no_q_to_128_rows(compiled, case):
    """Before PR 30 every layer of every step made q ``[256, 128, 128]``
    by a pad (8.4 MB at GPT-3 XL's 16 slots x 16 heads for 16 KB of rows),
    the kernel read it, wrote as much, and a slice took the 16 KB back.
    The kernel takes a head's rows padded to one sublane tile (16 in
    bf16): nothing in the step has that shape."""
    assert compiled[case]['padded_q'] is False, compiled[case]


def test_int8_kv_step_moves_only_its_scales(compiled):
    """With int8 KV banks the int8 planes stay where they lie too. What
    the compiler does move, once a step and not once a layer, is the two
    25 MB float32 scale planes into its fast memory and back (``S(1)`` in
    the layouts): its own choice, not a re-layout. Since PR 32 (bfloat16
    block matrices, so no converted copies of them to make room for) it
    brings them in by slices and joins those (a ``ConcatBitcast``
    custom-call)."""
    case = compiled['gpt_xl_step_int8_kv']
    assert case['kernels'] == 1 and case['collectives'] == [], case
    assert set(case['moved']) <= {'copy-done of f32',
                                  'custom-call of f32'}, case
    assert case['pool_copies'] <= 4, case


@pytest.mark.parametrize('case,kernels,names', [
    # a step: five paged calls (four window, one full), each behind its K
    # and V row writes (PR 41), and the three grouped products of each of
    # four routed layers
    ('afmoe_step', 5 + 10 + 12, ['paged_attention', 'paged_attention_window']),
    # one prefill body (1,024 rows): five flash forwards and the products
    ('afmoe_prefill_1024', 5 + 12, ['flash_fwd', 'flash_fwd_window']),
])
def test_window_and_full_engine_programs_leave_their_pools_where_they_lie(
        compiled, case, kernels, names):
    """``trinity-large-ep8-serve``'s WHOLE decode step and a prefill body
    at its published widths: both kinds of plane are carried through the
    layers and written in place (no operation's result is a pool, a kind's
    planes or a layer's plane but views and the in-place write: PRs 27 and
    28 taught what to look for), and the window layers' call is the paged
    kernel's (the flash forward's) one body under a name of its own."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]
    assert compiled[case]['names'] == names


@pytest.mark.parametrize('case', ['afmoe_prefill_6144',
                                  'afmoe_prefill_14336'])
def test_a_width_of_no_whole_pieces_keeps_its_temporaries_a_pieces(
        compiled, case):
    """6,144 rows (one of the engine's widths at 16,384) and 14,336 are no
    multiple of the 4,096 rows the MLP half takes at a time: what is left
    over goes through as a last, shorter
    piece, so such a prefill's temporaries stay under the widest body's
    (1.58 GB, PR 31; a 14,336-row dense layer taken whole would hold a
    [14336, 12288] float32 pair, 1.4 GB, beside them), its five flash
    forwards take the width as they take the others, and no pool is
    copied."""
    widest = compiled['afmoe_prefill_16384']
    assert 'error' not in widest and 'error' not in compiled[case], (
        compiled[case], widest)
    assert 0 < compiled[case]['temp_bytes'] <= widest['temp_bytes'] < 1.7e9
    # five flash forwards; four routed layers' three grouped products in
    # the whole pieces' loop, and again in the last piece
    assert _summary(compiled[case]) == {
        'kernels': 5 + 2 * 12, 'pool_copies': 0, 'collectives': []}
    assert compiled[case]['names'] == ['flash_fwd', 'flash_fwd_window']


@pytest.mark.parametrize('case,kernels,names', [
    # a period's body, compiled once: nine state updates, the paged call and
    # the K and V row writes before it (PR 41)
    ('granite_step', 9 + 1 + 2, ['paged_attention', 'ssm_state_update']),
    # the widest prefill (768 rows): a period's one flash forward
    ('granite_prefill', 1, ['flash_fwd']),
])
def test_state_beside_pages_engine_programs_leave_their_pools_where_they_lie(
        compiled, case, kernels, names):
    """``granite-4.0-h-micro-serve``'s WHOLE decode step and prefill at the
    published widths, all 40 layers: the state a slot (4.8 GB), the
    convolution's tails and the K and V pages are carried through the
    scan over periods and written in place (a decode step's state by the
    kernel whose result is its operand's buffer), and a prefill's layers
    read no pool: they leave what they made for ONE write after the scan
    (a pool that passed through a conditional was copied whole, 18 GB
    asked of 16, when the widths were bodies of a ``lax.switch``; the
    engine now calls the prefill at the width it chose)."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]
    assert compiled[case]['names'] == names


@pytest.mark.parametrize('case,kernels,names,temp', [
    # the scanned layer's body, compiled once: the one state update
    ('brumby_step', 1, ['retention_state_update'], 1.5e9),
    # the widest prefill (1,024 rows): ONE chunk, the attention form and the
    # state it leaves, all plain products (1.13 GB of temporaries)
    ('brumby_prefill', 0, [], 1.5e9),
    # two chunks: the second reads the state the first left, a query head
    # of every group at a time (2.09 GB: 14.9 GB in all)
    ('brumby_prefill_2048', 0, [], 2.5e9),
])
def test_state_alone_engine_programs_never_copy_the_state(
        compiled, case, kernels, names, temp):
    """``brumby-14b-pp5-serve``'s WHOLE decode step and a prefill at the
    published widths, 8 layers, 16 slots and NO page pool: the matrix state
    (4.40 GB beside 8.40 GB of weights: one copy and the cell does not
    fit) is carried through the scan over layers and updated in place by
    the kernel whose results are its operands' buffers; a prefill's layers
    read no pool and leave what they made for ONE write after the scan."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]
    assert compiled[case]['names'] == names
    # all of a call's temporaries beside 12.8 GB of arguments (a chunk's
    # scores and phi(k) and the eight layers' states it leaves among them)
    # keep it inside the chip's 16 GB
    assert compiled[case]['temp_bytes'] < temp


@pytest.mark.parametrize('case,kernels,names', [
    # a layer's body, compiled once: the paged call behind its K and V row
    # writes (PR 41) and the grouped product's three
    ('zaya_step', 1 + 2 + 3, ['paged_attention']),
    # the widest prefill (1,024 rows): a layer's flash forward and the same
    ('zaya_prefill', 1 + 3, ['flash_fwd']),
])
def test_tails_beside_pages_engine_programs_leave_pool_and_experts_where_they_lie(
        compiled, case, kernels, names):
    """``zaya1-8b-pp2-serve``'s WHOLE decode step and prefill at the
    published widths, 20 layers scanned with the router's state in the
    carry: the K and V pages (3 GB) are carried through the scan and
    written in place, and the experts' stacks (8 GB) reach the grouped
    product as they lie: the kernel takes the whole stack and the layer as
    an offset, so no operation's result is a layer's experts or all of
    them (a layer's slice handed to a custom call is a copy of it: 400 MB
    a layer a step)."""
    assert _summary(compiled[case]) == {
        'kernels': kernels, 'pool_copies': 0, 'collectives': []}, compiled[case]
    assert compiled[case]['names'] == names
    assert compiled[case]['expert_stacks_moved'] == []


def test_a_whole_step_of_twenty_layers_needs_no_temporary_worth_naming(
        compiled):
    """12.4 GB of arguments on a chip of 16: what a step and the widest
    prefill allocate beside them stays under 0.1 GB."""
    for case in ('zaya_step', 'zaya_prefill'):
        assert compiled[case]['temp_bytes'] < 1e8, compiled[case]


@pytest.mark.parametrize('case,slots,copies', [
    ('gpt_xl_step', 16, 0), ('moe_gpt_step', 16, 0), ('latent_step', 64, 0),
    ('afmoe_step', 24, 0), ('granite_step', 64, 0), ('zaya_step', 48, 0),
    # an int8 bank's two float32 scale planes are moved once a step, as
    # before (test_int8_kv_step_moves_only_its_scales)
    ('gpt_xl_step_int8_kv', 16, 4)])
def test_a_step_takes_the_previous_steps_tokens_where_they_lie(
        compiled, case, slots, copies):
    """The decode loop runs one step ahead of its read-back (PR 36): every
    family's WHOLE step at its cell's widths takes the tokens the previous
    step sampled as a parameter of its own (``prev``, beside the host's
    tokens and the mask that chooses between them), and feeding them back
    re-lays no pool: the step is the program it was."""
    assert compiled[case].get('fed_back') == [f's32[{slots}]'], compiled[case]
    assert compiled[case]['pool_copies'] <= copies, compiled[case]


@pytest.mark.parametrize('case,calls,in_loop,ops', [
    # a scanned stack: one call in the layers' ``while`` body
    ('gpt_xl_step', 1, True, 5), ('zaya_step', 1, True, 5),
    ('granite_step', 1, True, 5),
    # layers unrolled: five calls share one schedule (trinity two: the
    # window layers' and the full layer's, over one count of pages held)
    ('latent_step', 5, False, 5), ('afmoe_step', 5, False, 9)])
def test_where_a_steps_paged_calls_get_their_schedule(compiled, case, calls,
                                                      in_loop, ops):
    """``page_schedule`` hangs on the positions alone, so it is the same
    in every layer of a step (PR 43, item 3). Where the layers are
    unrolled the compiler keeps ONE: five small operations a step (the
    pages held, their running sum, the starts, the steps' slots and pages,
    the bound). Where they are a ``while`` it does NOT lift them out of the
    body: the same five run in every layer, beside the call that reads
    them (PERF.md section 6, PR 43, says what they cost on the chip). A
    schedule handed in through the loop's state would read 0 here."""
    assert compiled[case]['schedule'] == {
        'calls': calls, 'in_loop': in_loop, 'ops': ops}, compiled[case]


@pytest.mark.parametrize('mesh', ['', '_mp4'], ids=['one_chip', 'mp4'])
@pytest.mark.parametrize('cell', ['gpt_xl', 'zaya', 'trinity', 'granite'])
def test_row_write_compiles_for_v5e_at_the_served_cells_shapes(
        compiled, cell, mesh):
    """``paged_write`` of a decode step's rows at the four cells' planes
    (16 heads x 16 slots, 2 x 48, 8 x 24, 4 x 64): ONE Mosaic call whose
    result is the plane it was handed (an aliased call is no copy), nothing
    beside it that is shaped like the plane. Under the engine's mesh the
    call is wrapped (``mesh_kernel.sharded_call``; bare, the partitioner
    refuses it): heads that split over four chips are split, two heads stay
    whole on each, and no collective moves a page."""
    case = compiled[f'row_write_{cell}{mesh}']
    assert _summary(case) == {'kernels': 1, 'pool_copies': 0,
                              'collectives': []}, case
    assert case['row_writes'] == 1, case


@pytest.mark.parametrize('case,row_writes', [
    ('gpt_xl_step', 2), ('moe_gpt_step', 2), ('zaya_step', 2),
    ('granite_step', 2),
    ('afmoe_step', 10),         # five layers, unrolled
    # what the row kernel does not take: an int8 bank, a headless plane
    ('gpt_xl_step_int8_kv', 0), ('latent_step', 0),
    # and a prefill's rows, a page at a time as before
    ('gpt_xl_prefill', 0), ('zaya_prefill', 0), ('granite_prefill', 0),
    ('afmoe_prefill_1024', 0)])
def test_a_step_writes_its_rows_in_place_and_builds_no_page_around_them(
        compiled, case, row_writes):
    """Every served family with head-major float pages writes a decode
    step's K and V rows through ``paged_row_write``, chosen by
    ``paged_write`` from the call's shapes, and NOTHING in its step is
    shaped like a page a slot any more: before PR 41 a step read the page
    each row fell in (``[slots, H, 128, 128]``: 8 MB at GPT-3 XL), built a
    page-long zero buffer around the row (``[slots, 128, H, 128]``), masked,
    selected and scattered the page back: nine operations a layer, 15-25 %
    of a served cell's busy time (PERF.md section 6, PR 41)."""
    assert compiled[case]['row_writes'] == row_writes, compiled[case]
    assert not compiled[case]['page_buffers'], compiled[case]


@pytest.mark.parametrize('case,name', [
    ('flash_window_s16384_gqa6', 'flash_fwd_window'),
    ('flash_full_s16384_gqa6', 'flash_fwd'),
    ('paged_gqa6_window', 'paged_attention_window'),
])
def test_windowed_kernel_compiles_for_v5e_at_the_published_widths(
        compiled, case, name):
    """A 16,384-row prefill's flash forward, six query heads a KV head:
    every key and value of a head lies in fast memory (16 MiB with both
    buffers, past what the compiler grants unasked: the call asks). The
    window call of the paged kernel over the four window layers' planes."""
    assert _summary(compiled[case]) == {'kernels': 1, 'pool_copies': 0,
                                        'collectives': []}, compiled[case]
    assert compiled[case]['names'] == [name]


@pytest.mark.parametrize('case', [
    'latent_decode_w640', 'grouped_decode_up', 'grouped_decode_down',
    'grouped_prefill_up', 'grouped_prefill_down', 'latent_prefill_192_128'])
def test_latent_family_kernel_compiles_for_v5e(compiled, case):
    """At the published widths: Mosaic takes the blocks, the grouped
    product's whole-K weight block fits the fast memory it asks for."""
    assert _summary(compiled[case]) == {'kernels': 1, 'pool_copies': 0,
                                        'collectives': []}


def test_a_latent_pool_of_whole_lanes_is_not_copied_for_the_kernel(compiled):
    """Why a pool row is 640 columns and not 576 (models/latent_moe.py
    ``pool_width``): at 576 the compiler re-tiles the whole pool (0.76 GB)
    in front of every call of the kernel; at 640 it hands it over."""
    assert compiled['latent_decode_w576']['pool_copies'] == 1
    assert compiled['latent_decode_w640']['pool_copies'] == 0


def test_flash_under_dp2_mp2_mesh_compiles_for_v5e(compiled):
    """Refused before PR 21. The kernels shard themselves over the mesh the
    program names (ops/mesh_kernel.py); with no mesh named, the partitioner's
    refusal still surfaces — it is never swallowed into a jnp fallback."""
    assert compiled['flash_dropout_dp2_mp2'].get('kernels') == 3, compiled
    refused = compiled['flash_dp2_mp2_no_mesh_named'].get('error', '')
    assert refused.startswith('NotImplementedError') \
        and 'shard_map' in refused, compiled


def test_paged_decode_under_mp4_mesh_compiles_for_v5e(compiled):
    """The mesh-sharded engine's decode kernel: pool and heads split over
    'mp', page table and positions whole on every chip. Heads stay where
    the pool put them: no page crosses chips."""
    assert _summary(compiled['paged_mp4']) == {
        'kernels': 1, 'pool_copies': 0, 'collectives': []}


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_imports_initialise_no_backend():
    """A parent that imports the package (a launcher, a benchmark's parent)
    must not take the chip from the child that needs it."""
    code = ('import paddle_tpu, paddle_tpu.serving, paddle_tpu.models, '
            'paddle_tpu.distributed.launch\n'
            'from jax._src import xla_bridge\n'
            'assert not xla_bridge.backends_are_initialized()\n')
    proc = subprocess.run([sys.executable, '-c', code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_launch_refuses_to_share_tpu_chips(monkeypatch):
    # the package binds the name ``launch`` to a function: get the module
    launch = importlib.import_module('paddle_tpu.distributed.launch')
    monkeypatch.setattr(launch, '_local_tpu_chips', lambda: 4)
    monkeypatch.setenv('JAX_PLATFORMS', '')
    launch._refuse_shared_chips(1)                 # one process: the layout
    with pytest.raises(SystemExit, match='one process'):
        launch._refuse_shared_chips(2)
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')     # CPU emulation is fine
    launch._refuse_shared_chips(2)
    monkeypatch.setenv('JAX_PLATFORMS', '')
    monkeypatch.setattr(launch, '_local_tpu_chips', lambda: 0)
    launch._refuse_shared_chips(2)                 # no chips to fight over


# ---------------------------------------------------------------------------
# one compilation per train step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('placed', [True, False], ids=['placed', 'unplaced'])
def test_train_step_traces_once(placed):
    """The state a step returns goes back in as it came out: same layout,
    same types, no second trace (parallel/train_jit.py)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import HybridTopology
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16, use_flash=False)
    mesh = HybridTopology(devices=jax.devices()[:1]).mesh
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    if placed:
        params = gpt.place_params(params, cfg, mesh)
    opt_state = opt.functional_init(params)
    traces = []
    real_loss = gpt.loss_fn

    def counting_loss(*a, **k):
        traces.append(1)
        return real_loss(*a, **k)
    step = gpt.make_train_step(cfg, opt, mesh)
    toks = jnp.zeros((2, 16), jnp.int32)
    gpt.loss_fn = counting_loss
    try:
        shardings = None
        for i in range(3):
            loss, params, opt_state = step(
                params, opt_state, jax.random.PRNGKey(i),
                jnp.asarray(1e-3, jnp.float32), toks, toks)
            now = [x.sharding for x in
                   jax.tree_util.tree_leaves((params, opt_state))]
            assert shardings is None or now == shardings
            shardings = now
    finally:
        gpt.loss_fn = real_loss
    assert np.isfinite(float(loss))
    assert len(traces) == 1, f'train step traced {len(traces)} times'
