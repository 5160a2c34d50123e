"""Mixtral-style MoE causal LM with expert parallelism.

Reference capability: Fleet MoE expert-parallel via alltoall over NCCL
(python/paddle/distributed/collective.py:alltoall + incubate MoE layers).
TPU-first: experts sharded over the 'ep' mesh axis via GSPMD — the capacity-
bucketed dispatch einsums (paddle_tpu.parallel.moe) lower to all-to-all on
ICI automatically from the shardings.
"""
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.weight_only import is_weight_only, wo_lm_head, wo_matmul, wo_take
from ..parallel.moe import moe_ffn
from . import family as _family
from .gpt import (_layer_norm, _attention, _block_qkv, _cached_qkv, _mm,
                  cached_attention, init_paged_kv_cache,
                  serve_params as _gpt_serve_params, validate_gqa)


def _c(w, cdt):
    """Cast a raw weight to the compute dtype; weight-only int8 dicts pass
    through (their consumers cast in the matmul epilogue)."""
    return w if is_weight_only(w) else w.astype(cdt)


@dataclasses.dataclass
class MoEConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    n_experts: int = 8
    # GQA/MQA (0 = MHA); must divide num_heads — see gpt.GPTConfig
    num_kv_heads: int = 0
    ffn_mult: int = 4
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    max_seq_len: int = 1024
    # attention dropout (train-time; sampled IN-KERNEL via gpt._attention
    # when the step provides a key — see gpt.GPTConfig.dropout)
    dropout: float = 0.0
    dtype: str = 'bfloat16'
    param_dtype: str = 'float32'
    remat: bool = True
    use_flash: bool = True
    sp: int = 1
    mp: int = 1
    pp: int = 1
    # blockwise LM-head cross-entropy chunk (0 disables) — see gpt.GPTConfig
    xent_chunk: int = 8192
    # serving: int8 KV cache with per-row scales — see gpt.GPTConfig
    kv_cache_int8: bool = False
    # 'fp8' runs the dense attention matmuls (qkv/proj) e4m3-fwd/e5m2-bwd
    # with delayed scaling (see gpt.GPTConfig.matmul_precision); the
    # capacity-bucketed expert einsums stay in the compute dtype — their
    # dispatch/combine contractions are not plain matmuls and per-tensor
    # scales across ragged expert loads are ill-conditioned.
    matmul_precision: str = 'none'

    def __post_init__(self):
        validate_gqa(self.num_heads, self.num_kv_heads, self.mp)
        if self.matmul_precision not in ('none', 'fp8'):
            raise ValueError(
                f"matmul_precision must be 'none' or 'fp8', "
                f"got {self.matmul_precision!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def ffn_size(self):
        return self.hidden_size * self.ffn_mult


def init_params(config: MoEConfig, key):
    h, f, v, L, E = (config.hidden_size, config.ffn_size, config.vocab_size,
                     config.num_layers, config.n_experts)
    pdt = jnp.dtype(config.param_dtype)
    ks = jax.random.split(key, 8)
    std = 0.02

    def nrm(kk, shape, scale=std):
        return (scale * jax.random.normal(kk, shape)).astype(pdt)

    qkv_cols = (config.num_heads + 2 * config.kv_heads) * config.head_dim
    blocks = {
        'ln1_g': jnp.ones((L, h), pdt), 'ln1_b': jnp.zeros((L, h), pdt),
        'qkv_w': nrm(ks[0], (L, h, qkv_cols)),
        'qkv_b': jnp.zeros((L, qkv_cols), pdt),
        'proj_w': nrm(ks[1], (L, h, h)), 'proj_b': jnp.zeros((L, h), pdt),
        'ln2_g': jnp.ones((L, h), pdt), 'ln2_b': jnp.zeros((L, h), pdt),
        'gate_w': nrm(ks[2], (L, h, E), 0.01),
        'w_in': nrm(ks[3], (L, E, h, f)),
        'w_out': nrm(ks[4], (L, E, f, h)),
    }
    return {'wte': nrm(ks[5], (v, h)), 'wpe': nrm(ks[6], (config.max_seq_len, h), 0.01),
            'blocks': blocks, 'lnf_g': jnp.ones((h,), pdt),
            'lnf_b': jnp.zeros((h,), pdt)}


# Logical axis names per parameter (parallel/partitioner.py): experts ride
# 'expert' -> 'ep', attention/FFN widths 'heads'/'mlp' -> 'mp', the tied
# embedding 'vocab' -> 'mp' — all from the same rules table gpt.py uses.
# 'router' (gate_w's expert dim) is deliberately unmapped: the gate is tiny
# and every rank routes locally.
LOGICAL_AXES = {
    'wte': ('vocab', 'embed'),
    'wpe': ('positions', 'embed'),
    'blocks': {
        'ln1_g': ('layers', 'embed'), 'ln1_b': ('layers', 'embed'),
        'qkv_w': ('layers', 'embed', 'heads'),
        'qkv_b': ('layers', 'heads'),
        'proj_w': ('layers', 'heads', 'embed'),
        'proj_b': ('layers', 'embed'),
        'ln2_g': ('layers', 'embed'), 'ln2_b': ('layers', 'embed'),
        'gate_w': ('layers', 'embed', 'router'),
        'w_in': ('layers', 'expert', 'embed', 'mlp'),
        'w_out': ('layers', 'expert', 'mlp', 'embed'),
    },
    'lnf_g': ('embed',), 'lnf_b': ('embed',),
}


def param_specs(config: MoEConfig):
    """Experts sharded over 'ep'; dense weights replicated (mp optional) —
    resolved from LOGICAL_AXES through the partitioner rules table."""
    from ..parallel.partitioner import Partitioner, model_rules
    return Partitioner(rules=model_rules(mp=config.mp)).tree_specs(
        LOGICAL_AXES)


def block_fn(bp, carry, config, drop_seed=None, fp8_meta=None):
    x, aux_acc = carry
    cdt = jnp.dtype(config.dtype)
    B, S, h = x.shape
    nh, hd = config.num_heads, config.head_dim
    fm = fp8_meta or {}
    y = _layer_norm(x, bp['ln1_g'], bp['ln1_b']).astype(cdt)
    q, k, v = _block_qkv(bp, y, nh, hd, cdt, config.kv_heads,
                         fp8_meta=fm.get('qkv'))
    a = _attention(q, k, v, config, drop_seed=drop_seed).reshape(B, S, h)
    x = (x + _mm(a, bp['proj_w'], cdt, fm.get('proj'))
         + bp['proj_b'].astype(cdt))
    y = _layer_norm(x, bp['ln2_g'], bp['ln2_b']).astype(cdt)
    ff, aux = moe_ffn(y, bp['gate_w'].astype(cdt),
                      _c(bp['w_in'], cdt), _c(bp['w_out'], cdt),
                      capacity_factor=config.capacity_factor)
    return (x + ff, aux_acc + aux), None


def forward_hidden(params, tokens, config, dropout_seed=None,
                   fp8_state=None):
    """-> (final hidden [B,S,H], aux load-balance loss). dropout_seed: see
    gpt.forward_hidden (per-layer mixed seeds; None = unchanged trace).
    fp8_state (init_fp8_state): per-layer qkv/proj delayed-scaling metas
    riding the scan xs — see gpt.forward_hidden."""
    cdt = jnp.dtype(config.dtype)
    B, S = tokens.shape
    x = (wo_take(params['wte'], tokens) +
         params['wpe'][jnp.arange(S)]).astype(cdt)
    body = partial(block_fn, config=config)
    if config.remat:
        body = jax.checkpoint(body)
    carry0 = (x, jnp.zeros((), jnp.float32))
    use_drop = config.dropout > 0.0 and dropout_seed is not None
    if use_drop:
        from ..ops.flash_attention import per_layer_seeds
        seeds = per_layer_seeds(dropout_seed, config.num_layers)
    if use_drop and fp8_state is not None:
        xs = (params['blocks'], seeds, fp8_state['blocks'])

        def scan_body(c, inp):
            return body(inp[0], c, drop_seed=inp[1], fp8_meta=inp[2])
    elif use_drop:
        xs = (params['blocks'], seeds)

        def scan_body(c, inp):
            return body(inp[0], c, drop_seed=inp[1])
    elif fp8_state is not None:
        xs = (params['blocks'], fp8_state['blocks'])

        def scan_body(c, inp):
            return body(inp[0], c, fp8_meta=inp[1])
    else:
        xs = params['blocks']

        def scan_body(c, bp):
            return body(bp, c)

    (x, aux), _ = jax.lax.scan(scan_body, carry0, xs)
    return _layer_norm(x, params['lnf_g'], params['lnf_b']).astype(cdt), aux


def forward(params, tokens, config, dropout_seed=None):
    x, aux = forward_hidden(params, tokens, config, dropout_seed)
    return wo_lm_head(x, params['wte'], x.dtype), aux


FP8_MATMULS = ('qkv', 'proj')


def init_fp8_state(config: 'MoEConfig'):
    """Delayed-scaling state for matmul_precision='fp8' (dense qkv/proj
    matmuls only — see MoEConfig). Same contract as gpt.init_fp8_state."""
    from ..quantization import fp8 as _fp8
    return {'blocks': {name: _fp8.init_matmul_meta(config.num_layers)
                       for name in FP8_MATMULS}}


def loss_fn(params, tokens, targets, config, dropout_key=None,
            fp8_state=None):
    seed = (jax.random.bits(dropout_key, (1,), jnp.uint32)[0]
            if config.dropout > 0.0 and dropout_key is not None else None)
    aux_scale = config.aux_weight / config.num_layers
    if (config.xent_chunk and config.mp == 1 and config.sp == 1
            and config.pp == 1
            and config.vocab_size % config.xent_chunk == 0):
        # blockwise LM-head loss (ops/xent.py): no [B,S,V] logits in HBM
        from ..ops.xent import softmax_xent_blockwise
        x, aux = forward_hidden(params, tokens, config, seed,
                                fp8_state=fp8_state)
        B, S, H = x.shape
        ce = softmax_xent_blockwise(x.reshape(B * S, H), params['wte'],
                                    targets.reshape(B * S),
                                    config.xent_chunk)
        return ce + aux_scale * aux
    x, aux = forward_hidden(params, tokens, config, seed,
                            fp8_state=fp8_state)
    logits = wo_lm_head(x, params['wte'], x.dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + aux_scale * aux


# ---------------------------------------------------------------------------
# KV-cache autoregressive decoding (same design as gpt.py: static
# [L, B, S_max, H, Dh] cache, one compiled prefill + one compiled step;
# the MoE FFN routes per TOKEN. NOTE on parity: a 1-wide decode step gives
# every token full expert capacity, while a long training/prefill sequence
# COMPETES for capacity_factor-bounded slots — decode equals the full
# forward exactly whenever no token is dropped (generous capacity), and is
# otherwise slightly BETTER-routed than training saw. The same holds of a
# served prefill's width: capacity follows the rows of the call, padding
# among them, so the engine's narrower prefill widths serve what the widest
# serves whenever no token is dropped)
# ---------------------------------------------------------------------------

def quantize_decode_params(params):
    """Weight-only int8 snapshot for serving (see gpt.quantize_decode_params
    and ops/weight_only.py): attention matrices, the per-expert FFN banks —
    the bulk of a MoE checkpoint — and the tied embedding go int8 with
    per-output-channel scales. The returned pytree drops straight into
    ``forward`` / ``generate``."""
    from ..ops.weight_only import quantize_weight
    blocks = dict(params['blocks'])
    for k, ax in (('qkv_w', 1), ('proj_w', 1), ('w_in', 2), ('w_out', 2)):
        blocks[k] = quantize_weight(blocks[k], reduce_axis=ax)
    out = dict(params)
    out['blocks'] = blocks
    out['wte'] = quantize_weight(params['wte'], reduce_axis=1)
    return out


def init_kv_cache(config: 'MoEConfig', batch):
    cdt = jnp.dtype(config.dtype)
    shape = (config.num_layers, batch, config.max_seq_len,
             config.kv_heads, config.head_dim)
    if config.kv_cache_int8:
        from ..ops.weight_only import init_kv_bank
        return {'k': init_kv_bank(shape), 'v': init_kv_bank(shape)}
    return {'k': jnp.zeros(shape, cdt), 'v': jnp.zeros(shape, cdt)}


def _cached_block(bp, x, k_cache, v_cache, pos, config, page_table=None,
                  valid=None, tail=False):
    cdt = jnp.dtype(config.dtype)
    y = _layer_norm(x, bp['ln1_g'], bp['ln1_b']).astype(cdt)
    q, k, v = _cached_qkv(bp, y, config, cdt)
    x, k_cache, v_cache = cached_attention(
        x, q, k, v, k_cache, v_cache, pos, bp['proj_w'], bp['proj_b'], cdt,
        page_table=page_table, valid=valid, tail=tail)
    y = _layer_norm(x, bp['ln2_g'], bp['ln2_b']).astype(cdt)
    ff, _ = moe_ffn(y, bp['gate_w'].astype(cdt), _c(bp['w_in'], cdt),
                    _c(bp['w_out'], cdt),
                    capacity_factor=config.capacity_factor)
    return x + ff, k_cache, v_cache


def forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None):
    """[B, T] tokens at absolute positions starting at ``pos`` (traced
    scalar) -> (logits, cache). See gpt.forward_with_cache. A paged cache
    (gpt.is_paged) routes through gpt.paged_forward_with_cache with THIS
    module's block body (MoE FFN per token; note the capacity caveat in
    the section comment above — decode slots in one batch compete for
    expert capacity, so exact dense parity needs generous
    capacity_factor). ``partitioner`` (mesh-bound, serving over an mp=N
    mesh) pins the paged pool to the ``kv_heads`` layout."""
    from .gpt import is_paged, paged_forward_with_cache
    if is_paged(cache):
        return paged_forward_with_cache(params, tokens, cache, pos, config,
                                        last_only=last_only,
                                        block=_cached_block,
                                        partitioner=partitioner)
    cdt = jnp.dtype(config.dtype)
    B, T = tokens.shape
    ppos = pos + jnp.arange(T)
    x = (wo_take(params['wte'], tokens)
         + jnp.take(params['wpe'], ppos, axis=0)).astype(cdt)

    def scan_body(carry, inp):
        xx = carry
        bp, kc, vc = inp
        xx, kc, vc = _cached_block(bp, xx, kc, vc, pos, config)
        return xx, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        scan_body, x, (params['blocks'], cache['k'], cache['v']))
    if last_only:
        x = x[:, -1:]
    x = _layer_norm(x, params['lnf_g'], params['lnf_b']).astype(cdt)
    return wo_lm_head(x, params['wte'], cdt), {'k': k_new, 'v': v_new}


# what ``_cached_block`` reads only as ``.astype(cdt)`` in front of a
# product: the attention's two matrices (gpt's ``wo_matmul``), the router
# (its logits are widened AFTER the product) and the experts' banks (``_c``)
PRODUCT_OPERANDS = ('qkv_w', 'proj_w', 'gate_w', 'w_in', 'w_out')


def serve_params(params, config):
    """``gpt.serve_params`` over this family's product operands."""
    return _gpt_serve_params(params, config, operands=PRODUCT_OPERANDS)


_family.register(MoEConfig, _family.GenerationFamily(
    name='moe_gpt', init_pool=init_paged_kv_cache,
    forward_with_cache=forward_with_cache, logical_axes=LOGICAL_AXES,
    quantize_decode_params=quantize_decode_params,
    serve_params=serve_params, prefill_pages=2))     # as gpt's


def make_decode_fns(config):
    """-> (prefill, step) jitted with donated caches (see gpt.py)."""
    @partial(jax.jit, donate_argnums=(2,))
    def prefill(params, prompt, cache):
        logits, cache = forward_with_cache(params, prompt, cache,
                                           jnp.int32(0), config,
                                           last_only=True)
        return logits[:, -1], cache

    @partial(jax.jit, donate_argnums=(3,))
    def step(params, tok, pos, cache):
        logits, cache = forward_with_cache(params, tok[:, None], cache, pos,
                                           config)
        return logits[:, 0], cache

    return prefill, step


from .decode_cache import DecodeFnCache

_decode_fns_cache = DecodeFnCache(name='moe_gpt.decode_fns')


def _decode_fns_for(config):
    """Memoize per config (bounded LRU — see models/decode_cache.py):
    repeated generate() calls must not rebuild the jit closures (and so
    recompile prefill/step) every time, and abandoned configs must not pin
    their executables forever."""
    cfg_key = tuple(sorted(dataclasses.asdict(config).items()))
    return _decode_fns_cache.get(cfg_key, lambda: make_decode_fns(config))


def generate(params, config, prompt, max_new_tokens, temperature=0.0,
             top_k=None, key=None, *, top_p=None):
    """Functional greedy/sampled generation over the KV cache. ``prompt``:
    [B, T0] int32 with T0 < max_seq_len; generation is capped at the cache
    window (T0 + n <= max_seq_len + 1). ``key`` makes sampling
    reproducible (split per step); otherwise the global stream is used."""
    from .gpt import _sample
    B, T0 = prompt.shape
    if T0 >= config.max_seq_len:
        raise ValueError(
            f'prompt length {T0} >= max_seq_len {config.max_seq_len}: the '
            'KV cache cannot hold it — truncate the prompt or raise '
            'max_seq_len')
    n = min(max_new_tokens, config.max_seq_len - T0 + 1)
    if n < max_new_tokens:
        import warnings
        warnings.warn(
            f'generate: max_new_tokens={max_new_tokens} exceeds the KV-cache '
            f'window (max_seq_len={config.max_seq_len}, prompt={T0}); only '
            f'{n} tokens will be generated. Raise max_seq_len or use '
            'gpt.GPTForCausalLM.generate for sliding-window continuation.')
    prefill, step = _decode_fns_for(config)
    cache = init_kv_cache(config, B)
    logits, cache = prefill(params, jnp.asarray(prompt, jnp.int32), cache)
    if key is None and temperature != 0:
        # greedy never consumes randomness: the global stream must not
        # advance (seeded-script reproducibility — review r5g)
        from ..tensor.random import next_key
        key = next_key()
    if key is not None:
        key, first_key = jax.random.split(key)
    else:
        first_key = None
    first = _sample(logits, temperature, top_k, top_p, key=first_key)
    pieces = [jnp.asarray(prompt, jnp.int32), first[:, None]]
    if n > 1:
        # remaining tokens run ON DEVICE in one dispatch (see
        # gpt.make_generate_loop)
        loop = _generate_loop_for(config, temperature, top_k, top_p)
        new, _ = loop(params, first, jnp.int32(T0), cache,
                      key if key is not None else jax.random.PRNGKey(0),
                      n - 1)
        pieces.append(new)
    return jnp.concatenate(pieces, axis=1)


_GEN_LOOPS = DecodeFnCache(name='moe_gpt.gen_loops')


def _generate_loop_for(config, temperature, top_k, top_p):
    """Memoized on-device decode loop — gpt.make_generate_loop with THIS
    module's cached forward (one loop implementation for both models; a
    fresh jit wrapper per generate() call would recompile the scanned
    program every time — review r5g). Bounded LRU: see decode_cache.py."""
    import dataclasses
    from .gpt import make_generate_loop
    cache_key = (dataclasses.astuple(config), temperature, top_k, top_p)
    return _GEN_LOOPS.get(cache_key, lambda: make_generate_loop(
        config, temperature, top_k, top_p, forward_fn=forward_with_cache))


def make_train_step(config, optimizer, mesh=None):
    from ..distributed.topology import get_mesh
    from ..parallel.train_jit import jit_train_step
    mesh = mesh or get_mesh()

    if getattr(config, 'matmul_precision', 'none') == 'fp8':
        # fp8 step: delayed-scaling state (init_fp8_state) is an extra
        # donated carry; its "gradient" IS the updated state (see
        # quantization/fp8.py), so one backward pass yields both.
        def fp8_step(params, opt_state, fp8_state, key, lr, tokens, targets):
            loss, (grads, new_fp8) = jax.value_and_grad(
                lambda p, f8: loss_fn(p, tokens, targets, config,
                                      key if config.dropout > 0.0 else None,
                                      fp8_state=f8),
                argnums=(0, 1))(params, fp8_state)
            new_p, new_s = optimizer.functional_apply(params, grads,
                                                      opt_state, lr)
            return loss, new_p, new_s, new_fp8
        return jit_train_step(fp8_step, mesh, n_state=3)

    def step(params, opt_state, key, lr, tokens, targets):
        # the step key drives attention dropout when configured
        # (config.dropout == 0 leaves the trace unchanged — see gpt)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, targets, config,
            key if config.dropout > 0.0 else None)
        new_p, new_s = optimizer.functional_apply(params, grads, opt_state, lr)
        return loss, new_p, new_s
    return jit_train_step(step, mesh, n_state=2)


def place_params(params, config, mesh):
    """device_put every leaf to its param_specs sharding on ``mesh``; a
    leaf that cannot be placed raises."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(config))
