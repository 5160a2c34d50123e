"""GPT-style causal LM — the flagship training model.

Reference capability: PaddleNLP/Fleet GPT-3 hybrid parallel (the reference
repo's fleet meta_parallel stack, e.g.
python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py
used by PaddleNLP gpt modeling). Re-designed TPU-first:

  - functional core: params are a pytree with transformer blocks STACKED on a
    leading layer dim and the forward a lax.scan over layers → one compiled
    block body regardless of depth (fast compiles, XLA-friendly)
  - bf16 activations/params option; fused QKV GEMM feeding the MXU
  - attention: Pallas flash attention on TPU (paddle_tpu.ops), XLA softmax
    fallback elsewhere
  - parallelism: dp (batch), mp (Megatron-style column/row sharding expressed
    as PartitionSpecs — XLA inserts the TP collectives), sp (ring attention
    over the sequence axis via shard_map), pp (GPipe microbatch pipeline via
    shard_map + ppermute), ZeRO opt-state sharding over dp
  - jax.checkpoint (remat) per block for memory at scale

The nn.Layer wrapper (GPTForCausalLM) exposes the paddle-style stateful API
over the same functional core.
"""
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..nn.layer_base import Layer, Parameter
from ..ops.weight_only import wo_lm_head, wo_matmul, wo_take
from . import family as _family


def validate_gqa(num_heads, num_kv_heads, mp):
    """Shared GQA/tensor-parallel config contract (GPT + MoE configs)."""
    kvh = num_kv_heads or num_heads
    if num_heads % kvh != 0:
        raise ValueError(
            f'num_kv_heads={kvh} must divide num_heads={num_heads}')
    if mp > 1 and (kvh % mp != 0 or num_heads % mp != 0):
        raise ValueError(
            f'mp={mp} must divide both num_heads={num_heads} and '
            f'num_kv_heads={kvh} (each tensor-parallel rank owns whole kv '
            'heads with their query groups)')


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    # GQA/MQA: kv heads (0 = MHA, one kv head per query head). Must divide
    # num_heads; with tensor parallel, mp must divide it too. The flash
    # kernels serve each kv head to its query group without repeating KV,
    # and the decode cache shrinks by num_heads/num_kv_heads.
    num_kv_heads: int = 0
    ffn_mult: int = 4
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: str = 'bfloat16'
    param_dtype: str = 'float32'
    remat: bool = True
    # 'full': recompute everything (min memory); 'dots': save matmul/flash
    # outputs, recompute only cheap elementwise (near-full speed, ~matmul
    # activations memory) — the TPU sweet spot since MXU results are the
    # expensive thing to recompute and HBM is better spent on them. The
    # 345M training cell runs 'dots', the 1.3B one 'full' to fit its chips
    # (benchmark/configs); the two were not measured against each other on
    # this installation.
    remat_policy: str = 'dots'
    use_flash: bool = True
    # parallel degrees (must multiply to the mesh size together with dp)
    mp: int = 1
    pp: int = 1
    sp: int = 1
    n_microbatches: int = 1
    # 'gpipe': fwd scan + autodiff reverse pipeline (stores O(m) stage inputs)
    # '1f1b':  fused fwd/bwd schedule, O(pp) in-flight activations
    pp_schedule: str = 'gpipe'
    # blockwise LM-head cross-entropy chunk (0 disables): the loss streams
    # vocab chunks with an online logsumexp instead of materializing
    # [B,S,V] f32 logits (ops/xent.py). Auto-falls back when the vocab
    # doesn't tile or under mp/sp/pp sharded losses.
    xent_chunk: int = 8192
    # serving: store the KV cache as int8 with per-row scales — at long
    # context the cache, not the weights, is the decode step's biggest HBM
    # stream (ops/weight_only.quantize_kv; int8 flash decode kernel)
    kv_cache_int8: bool = False
    # lax.scan unroll over the layer stack (single-chip path): >1 lets XLA
    # software-pipeline across layer boundaries at the cost of program
    # size. Numerics are unchanged (tests/test_user_journeys2.py); its
    # throughput was not measured on this installation: no cell sets it.
    scan_unroll: int = 1
    # quantized dp-gradient all-reduce (distributed/quant_collectives,
    # EQuARX-style): 'none' keeps the full-width reduction; 'bf16' is the
    # cast fallback knob; 'int8'/'int4' move a block-scaled payload with
    # stochastic rounding; 'fp8' when the jax build has float8. Any value
    # but 'none' routes the train step through the explicit-collective
    # (shard_map) path so the reduction is addressable.
    grad_quant: str = 'none'
    # compute precision of the four block matmuls (qkv/proj/fc/out):
    # 'fp8' runs them e4m3-fwd/e5m2-bwd with per-tensor delayed scaling
    # (quantization/fp8.py); the train step then threads an fp8_state arg
    # (init_fp8_state) through the jitted step. Embedding, LM head and
    # norms stay full precision — they are a sliver of the FLOPs and the
    # loss is disproportionately sensitive to them.
    matmul_precision: str = 'none'

    def __post_init__(self):
        validate_gqa(self.num_heads, self.num_kv_heads, self.mp)
        if self.grad_quant not in ('none', 'bf16', 'int8', 'int4', 'fp8'):
            raise ValueError(
                f"grad_quant must be one of 'none'/'bf16'/'int8'/'int4'/"
                f"'fp8', got {self.grad_quant!r}")
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


def _split(key, n):
    return jax.random.split(key, n)


def init_params(config: GPTConfig, key):
    """Stacked-block param pytree."""
    h, f, v, L = (config.hidden_size, config.ffn_size, config.vocab_size,
                  config.num_layers)
    pdt = jnp.dtype(config.param_dtype)
    k = iter(_split(key, 8))
    std = 0.02

    def nrm(kk, shape, scale=std):
        return (scale * jax.random.normal(kk, shape)).astype(pdt)

    kb = _split(next(k), 6)
    # GQA: per-kv-head packing [q_0..q_{g-1}|k|v] -> (g+2)*kv_heads*hd cols
    qkv_cols = (config.num_heads + 2 * config.kv_heads) * config.head_dim
    blocks = {
        'ln1_g': jnp.ones((L, h), pdt), 'ln1_b': jnp.zeros((L, h), pdt),
        'qkv_w': nrm(kb[0], (L, h, qkv_cols)),
        'qkv_b': jnp.zeros((L, qkv_cols), pdt),
        'proj_w': nrm(kb[1], (L, h, h), std / math.sqrt(2 * L)),
        'proj_b': jnp.zeros((L, h), pdt),
        'ln2_g': jnp.ones((L, h), pdt), 'ln2_b': jnp.zeros((L, h), pdt),
        'fc_w': nrm(kb[2], (L, h, f)), 'fc_b': jnp.zeros((L, f), pdt),
        'out_w': nrm(kb[3], (L, f, h), std / math.sqrt(2 * L)),
        'out_b': jnp.zeros((L, h), pdt),
    }
    return {
        'wte': nrm(next(k), (v, h)),
        'wpe': nrm(next(k), (config.max_seq_len, h), 0.01),
        'blocks': blocks,
        'lnf_g': jnp.ones((h,), pdt), 'lnf_b': jnp.zeros((h,), pdt),
    }


# Logical axis names per parameter (parallel/partitioner.py): the Megatron
# column/row/pipeline layout is no longer written here as PartitionSpec
# literals — it falls out of one rules table ('heads'/'mlp' -> 'mp',
# 'layers' -> 'pp', 'vocab' -> 'mp' on the GSPMD path). 'positions' is
# deliberately unmapped: every sp rank slices its own rows from a full wpe.
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
        'fc_w': ('layers', 'embed', 'mlp'), 'fc_b': ('layers', 'mlp'),
        'out_w': ('layers', 'mlp', 'embed'), 'out_b': ('layers', 'embed'),
    },
    'lnf_g': ('embed',), 'lnf_b': ('embed',),
}


def _partitioner(config: GPTConfig, explicit):
    from ..parallel.partitioner import Partitioner, model_rules
    return Partitioner(rules=model_rules(
        mp=config.mp, pp=config.pp, sp=config.sp, explicit=explicit))


def param_specs(config: GPTConfig):
    """PartitionSpecs for the GSPMD (jit + propagation) path, resolved from
    LOGICAL_AXES through the partitioner rules table."""
    return _partitioner(config, explicit=False).tree_specs(LOGICAL_AXES)


def _remat(body, config):
    """Apply the configured rematerialisation policy to a block body."""
    if config.remat_policy == 'dots':
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _layer_norm(x, g, b, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _attention(q, k, v, config, mesh=None, drop_seed=None):
    """q: [B, S, H, D]; k/v: [B, S, H_kv, D] (GQA: H_kv divides H). The
    flash kernels serve kv groups natively; the ring and einsum fallbacks
    repeat kv heads.

    drop_seed (traced u32, train-time only): config.dropout is sampled
    IN-KERNEL on the flash path (ops/flash_attention counter-hash; the
    jnp fallback applies the identical mask), so attention dropout never
    forces the XLA path (VERDICT r4 weak #8)."""
    # getattr: other configs sharing this attention core may predate the
    # dropout field (MoEConfig has it since r5; defensive for any future
    # config class)
    if getattr(config, 'dropout', 0.0) > 0.0 and drop_seed is not None:
        if config.sp > 1:
            from ..parallel.ring_attention import (ring_flash_attention,
                                                   ring_flash_available)
            if config.use_flash and ring_flash_available(q, k):
                # per-ring-pair masks regenerated in the backward sweep
                return ring_flash_attention(q, k, v, axis_name='sp',
                                            causal=True,
                                            drop_rate=config.dropout,
                                            seed=drop_seed)
            raise NotImplementedError(
                'attention dropout under sequence parallelism needs the '
                'ring flash path (use_flash=True, 128-multiple local '
                'shard) — or set dropout=0')
        if config.use_flash:
            from ..ops.flash_attention import flash_attention
            # falls back to the jnp path (same hash mask) on shapes or
            # platforms the kernels decline, so this is always safe
            return flash_attention(q, k, v, causal=True,
                                   dropout_rate=config.dropout,
                                   dropout_seed=drop_seed)
        from ..ops.flash_attention import _jnp_attention
        # use_flash=False is honored under dropout too (review r5f): the
        # jnp path samples the IDENTICAL counter-hash mask
        return _jnp_attention(q, k, v, True, None,
                              drop_rate=config.dropout, seed=drop_seed)
    if config.sp > 1:
        from ..parallel.ring_attention import (ring_attention,
                                               ring_flash_available,
                                               ring_flash_attention)
        if config.use_flash and ring_flash_available(q, k):
            # pallas kernels per ring pair: no S_local x S_local scores in
            # HBM, forward or backward; GQA kv blocks rotate un-repeated
            return ring_flash_attention(q, k, v, axis_name='sp', causal=True)
        from ..ops.flash_attention import repeat_kv
        k, v = repeat_kv(k, v, int(q.shape[2]))
        return ring_attention(q, k, v, axis_name='sp', causal=True)
    from ..ops.flash_attention import (flash_attention,
                                       flash_attention_available, repeat_kv)
    if config.use_flash and flash_attention_available(q, k, v, None):
        return flash_attention(q, k, v, causal=True)
    k, v = repeat_kv(k, v, int(q.shape[2]))
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def _mm(y, w, cdt, fp8_meta=None):
    """One block matmul: raw/weight-only via wo_matmul, or — when the
    caller threads an fp8 scaling meta — the e4m3/e5m2 delayed-scaling
    primitive (quantization/fp8.py)."""
    if fp8_meta is None:
        return wo_matmul(y, w, cdt)
    from ..quantization import fp8 as _fp8
    return _fp8.fp8_matmul(y, w.astype(cdt), fp8_meta)


def _qkv_product(bp, y, cdt, fp8_meta=None):
    """The fused QKV projection: ``[B, S, h] x [h, kvh * (g + 2) * hd]``,
    bias added. Packing is per KV HEAD: [q_0..q_{g-1}|k|v] (g = query
    group size; g=1 is classic head-major MHA) — an 'mp' column shard is
    then exactly that rank's kv heads with their query groups (contiguous
    [Q|K|V] thirds would hand each rank a mix of Q and K columns)."""
    return _mm(y, bp['qkv_w'], cdt, fp8_meta) + bp['qkv_b'].astype(cdt)


def _qkv_split(qkv, nh, hd, kvh):
    """q ``[B, S, nh, hd]``, k and v ``[B, S, kvh, hd]`` out of the
    product's columns (``_qkv_product``'s packing)."""
    B, S, _ = qkv.shape
    g = nh // kvh
    qkv = qkv.reshape(B, S, kvh, g + 2, hd)
    q = qkv[..., :g, :].reshape(B, S, nh, hd)
    return q, qkv[..., g, :], qkv[..., g + 1, :]


def _block_qkv(bp, y, nh, hd, cdt, kvh=None, fp8_meta=None):
    """q, k, v of the train block: the product and the split with nothing
    between them (the compiler may fold one into the other, and the
    backward with them)."""
    return _qkv_split(_qkv_product(bp, y, cdt, fp8_meta), nh, hd,
                      nh if kvh is None else kvh)


def _cached_qkv(bp, y, config, cdt):
    """q, k, v of a KV-cache block (this module's and moe_gpt's), the head
    split kept OUT of the product. Left to fold the split in, the TPU
    compiler makes the product a convolution over the ``kvh x (g + 2)``
    window, which wants its weight with the contraction axis minor: every
    layer of every call it then slices its ``qkv_w`` out of the stack into
    fast memory and transposes the slice, twice the matrix's own bandwidth
    price for nothing (a quarter of a GPT-3 XL decode step's busy time,
    PERF.md section 6, PR 44). Behind the barrier the product is a plain
    ``[rows, h] x [h, n]`` one that reads its layer where the stack holds
    it, as the other three block matrices' products do
    (tests/test_aot_tpu_compile.py reads the compiled programs for it)."""
    qkv = jax.lax.optimization_barrier(_qkv_product(bp, y, cdt))
    return _qkv_split(qkv, config.num_heads, config.head_dim,
                      config.kv_heads)


def _block_mlp(bp, y, cdt, fp8_fc=None, fp8_out=None):
    """fc -> gelu -> out projection (bias added by the caller after the
    mp all-reduce)."""
    y = jax.nn.gelu(_mm(y, bp['fc_w'], cdt, fp8_fc) + bp['fc_b'].astype(cdt))
    return _mm(y, bp['out_w'], cdt, fp8_out)


def block_fn(bp, x, config, explicit_mp=False, drop_seed=None,
             fp8_meta=None):
    """One transformer block. bp: this layer's params (no leading L dim).
    x: [B, S, H]. With ``explicit_mp`` (inside shard_map), qkv/fc weights are
    the local 'mp' shards and the two row-parallel matmuls psum over 'mp' —
    Megatron exactly as the reference's mp_layers, but via XLA collectives.
    """
    cdt = jnp.dtype(config.dtype)
    B, S, h = x.shape
    mp = config.mp if explicit_mp else 1
    nh, hd = config.num_heads // mp, config.head_dim
    kvh = config.kv_heads // mp

    if mp > 1:
        from ..parallel.tp_ad import f_identity, g_allreduce

    fm = fp8_meta or {}
    # the profiler reads these scopes (PERF.md section 3); jax adds the
    # phase itself: jvp, transpose(jvp), checkpoint/rematted_computation
    with jax.named_scope('gpt.block'):
        with jax.named_scope('attn'):
            y = _layer_norm(x, bp['ln1_g'], bp['ln1_b']).astype(cdt)
            if mp > 1:
                y = f_identity(y, 'mp')
            q, k, v = _block_qkv(bp, y, nh, hd, cdt, kvh,
                                 fp8_meta=fm.get('qkv'))
            a = _attention(q, k, v, config,
                           drop_seed=drop_seed).reshape(B, S, h // mp)
            a = _mm(a, bp['proj_w'], cdt, fm.get('proj'))
            if mp > 1:
                a = g_allreduce(a, 'mp')
            x = x + a + bp['proj_b'].astype(cdt)
        with jax.named_scope('mlp'):
            y = _layer_norm(x, bp['ln2_g'], bp['ln2_b']).astype(cdt)
            if mp > 1:
                y = f_identity(y, 'mp')
            y = _block_mlp(bp, y, cdt, fp8_fc=fm.get('fc'),
                           fp8_out=fm.get('out'))
            if mp > 1:
                y = g_allreduce(y, 'mp')
            x = x + y + bp['out_b'].astype(cdt)
    return x


def forward_hidden(params, tokens, config: GPTConfig, dropout_seed=None,
                   fp8_state=None):
    """tokens: [B, S] int32 -> final hidden states [B, S, H] (pre-LM-head).
    dropout_seed (traced u32 scalar, training only): enables
    config.dropout attention dropout with a distinct derived seed per
    layer; None (the serving/eval default) disables it with an UNCHANGED
    trace. fp8_state (init_fp8_state, training only): per-layer delayed
    scaling metas riding the scan xs next to the stacked block params —
    grads w.r.t. it are the UPDATED state (quantization/fp8.py)."""
    cdt = jnp.dtype(config.dtype)
    B, S = tokens.shape
    with jax.named_scope('gpt.embed'):
        pos = jnp.arange(S)
        x = wo_take(params['wte'], tokens) + params['wpe'][pos]
        x = x.astype(cdt)

    body = partial(block_fn, config=config)
    if config.remat:
        body = _remat(body, config)

    use_drop = config.dropout > 0.0 and dropout_seed is not None
    if use_drop:
        # one derived seed per layer, riding the scan as an extra xs — the
        # scan call and epilogue below are shared with the no-dropout path
        from ..ops.flash_attention import per_layer_seeds
        seeds = per_layer_seeds(dropout_seed, config.num_layers)
    if use_drop and fp8_state is not None:
        xs = (params['blocks'], seeds, fp8_state['blocks'])

        def scan_body(carry, inp):
            bp, sd, fm = inp
            return body(bp, carry, drop_seed=sd, fp8_meta=fm), None
    elif use_drop:
        xs = (params['blocks'], seeds)

        def scan_body(carry, inp):
            bp, sd = inp
            return body(bp, carry, drop_seed=sd), None
    elif fp8_state is not None:
        xs = (params['blocks'], fp8_state['blocks'])

        def scan_body(carry, inp):
            bp, fm = inp
            return body(bp, carry, fp8_meta=fm), None
    else:
        xs = params['blocks']

        def scan_body(carry, bp):
            return body(bp, carry), None

    # gpt.layers: the scan's own work (slicing the stacked parameters,
    # stacking saved activations and weight gradients) beside gpt.block's
    with jax.named_scope('gpt.layers'):
        x, _ = jax.lax.scan(scan_body, x, xs,
                            unroll=max(1, int(config.scan_unroll)))
    with jax.named_scope('gpt.head'):
        return _layer_norm(x, params['lnf_g'], params['lnf_b']).astype(cdt)


def forward(params, tokens, config: GPTConfig, dropout_seed=None):
    """tokens: [B, S] int32 -> logits [B, S, V]. lax.scan over stacked blocks."""
    x = forward_hidden(params, tokens, config, dropout_seed=dropout_seed)
    with jax.named_scope('gpt.head'):
        return wo_lm_head(x, params['wte'], x.dtype)


def loss_fn(params, tokens, targets, config: GPTConfig, dropout_key=None,
            fp8_state=None):
    """dropout_key: PRNG key (train step's ``key``) — consumed only when
    config.dropout > 0 (the trace is unchanged otherwise). fp8_state: see
    forward_hidden."""
    seed = (jax.random.bits(dropout_key, (1,), jnp.uint32)[0]
            if config.dropout > 0.0 and dropout_key is not None else None)
    if (config.xent_chunk and config.mp == 1 and config.sp == 1
            and config.pp == 1
            and config.vocab_size % config.xent_chunk == 0):
        # blockwise LM-head loss: never materializes [B,S,V] logits (the
        # other HBM hog besides attention) — see ops/xent.py
        from ..ops.xent import softmax_xent_blockwise
        x = forward_hidden(params, tokens, config, dropout_seed=seed,
                           fp8_state=fp8_state)
        B, S, H = x.shape
        with jax.named_scope('gpt.head'):
            return softmax_xent_blockwise(
                x.reshape(B * S, H), params['wte'], targets.reshape(B * S),
                config.xent_chunk)
    x = forward_hidden(params, tokens, config, dropout_seed=seed,
                       fp8_state=fp8_state)
    with jax.named_scope('gpt.head'):
        return _xent(wo_lm_head(x, params['wte'], x.dtype), targets)


def _xent(logits, targets):
    """Mean token cross-entropy of whole [..., V] logits, in float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# fp8 training state -------------------------------------------------------

FP8_MATMULS = ('qkv', 'proj', 'fc', 'out')


def init_fp8_state(config: GPTConfig):
    """Delayed-scaling state for matmul_precision='fp8': one
    {x, w, g} x {scale, amax-history} meta per block matmul, stacked on
    the layer dim so it scans alongside params['blocks']. Pass it to the
    fp8 train step (make_train_step) as the third argument; the step
    returns the updated state in the same structure (donation-safe)."""
    from ..quantization import fp8 as _fp8
    return {'blocks': {name: _fp8.init_matmul_meta(config.num_layers)
                       for name in FP8_MATMULS}}


# ---------------------------------------------------------------------------
# KV-cache autoregressive decoding (serving path)
#
# TPU-native design: the cache is pre-allocated at [L, B, S_max, H, Dh]
# (static shapes — XLA compiles ONE prefill program and ONE decode-step
# program), each step writes its k/v row via lax.dynamic_update_slice and
# attends over the full cache with a position mask. Per-token cost is
# O(S_max * d) instead of the O(S^2 * d) full-context recompute, and the
# whole generate loop is a single lax.while-free python loop over ONE
# compiled step (no per-length retracing).
# ---------------------------------------------------------------------------

def quantize_decode_params(params):
    """Weight-only int8 snapshot of a GPT param pytree for serving (see
    ops/weight_only.py): the four block matrices and the tied embedding go
    int8 with per-output-channel (per-vocab-row for ``wte``) f32 scales;
    biases, norms and ``wpe`` stay as-is. The quantized pytree drops
    straight into ``forward`` / ``forward_with_cache`` — every weight
    consumer routes through the wo_* helpers — halving the HBM bytes the
    bandwidth-bound decode step must stream per token."""
    from ..ops.weight_only import quantize_weight
    blocks = dict(params['blocks'])
    for k in ('qkv_w', 'proj_w', 'fc_w', 'out_w'):
        blocks[k] = quantize_weight(blocks[k], reduce_axis=1)
    out = dict(params)
    out['blocks'] = blocks
    out['wte'] = quantize_weight(params['wte'], reduce_axis=1)
    return out


# the stacked block matrices: the cached forward reads each only as
# ``w.astype(cdt)``, the right-hand operand of a product (``wo_matmul``)
PRODUCT_OPERANDS = ('qkv_w', 'proj_w', 'fc_w', 'out_w')


def serve_params(params, config, operands=PRODUCT_OPERANDS):
    """The parameters as a serving engine holds them (models/family.py):
    ``blocks[name]`` for each of ``operands`` in the compute dtype, rounded
    once here and not in every call of the engine's executables (at GPT-3
    XL four float32 matrices of 1.2 G parameters: 7 GB read and written a
    call, half a decode step's device time, PERF.md section 6, PR 32).
    Every product gets the operand it got, so every logit is the same to
    the last bit. ``wte`` (the embedding sum reads it as given), ``wpe``,
    norms and biases stay as given; so does a weight-only leaf, and a leaf
    already in the compute dtype is the leaf that comes back."""
    from ..ops.weight_only import is_weight_only
    cdt = jnp.dtype(config.dtype)
    blocks = params['blocks']
    cast = {k: blocks[k].astype(cdt) for k in operands
            if not is_weight_only(blocks[k]) and blocks[k].dtype != cdt}
    if not cast:
        return params
    return {**params, 'blocks': {**blocks, **cast}}


def init_kv_cache(config: GPTConfig, batch):
    """-> {'k','v': [L, B, S_max, H_kv, Dh] in the compute dtype}, or with
    ``config.kv_cache_int8`` each of k/v is ``{'int8': that shape int8,
    'scale': [L, B, S_max, H_kv] f32}`` (per-row quantization)."""
    cdt = jnp.dtype(config.dtype)
    shape = (config.num_layers, batch, config.max_seq_len,
             config.kv_heads, config.head_dim)
    if config.kv_cache_int8:
        from ..ops.weight_only import init_kv_bank
        return {'k': init_kv_bank(shape), 'v': init_kv_bank(shape)}
    return {'k': jnp.zeros(shape, cdt), 'v': jnp.zeros(shape, cdt)}


def is_paged(cache):
    """True when ``cache`` is a paged decode cache: ``{'k','v'}`` page
    pools (ops/paged_kv) plus a ``'page_table'`` [B, P_max] i32 and an
    optional ``'valid'`` [B] i32 (prefill: per-slot real prompt lengths,
    padding rows past it route to the trash page)."""
    return isinstance(cache, dict) and 'page_table' in cache


def init_paged_kv_cache(config, num_pages, page_size):
    """Shared page pool for the continuous-batching decode path:
    ``{'k','v': [L, num_pages, H_kv, page_size, Dh]}`` (int8 banks with
    ``config.kv_cache_int8``). Pair with a per-slot page table + ``pos``
    vector to form the paged cache ``forward_with_cache`` accepts; the
    dense ``init_kv_cache`` remains the default for ``generate()``."""
    from ..ops.paged_kv import init_paged_pool
    return init_paged_pool(config.num_layers, num_pages, page_size,
                           config.kv_heads, config.head_dim,
                           jnp.dtype(config.dtype),
                           int8=config.kv_cache_int8)


def cached_attention(x, q, k, v, k_cache, v_cache, pos, proj_w, proj_b, cdt,
                     page_table=None, valid=None, tail=False):
    """Shared KV-cache attention core (used by gpt AND moe_gpt decode):
    writes rows [pos, pos+T) into the caches, attends each q row to cache
    positions <= its absolute index, applies the output projection +
    residual. Returns (x_new, k_cache, v_cache). Caches may be raw
    ``[B, S_max, H_kv, D]`` arrays or int8 banks (init_kv_cache with
    ``kv_cache_int8``): fresh rows quantize on write and attention runs
    the int8 flash decode kernel (or a dequantizing fallback).

    Paged mode (``page_table`` not None): the caches are page planes
    ``[N, H_kv, page_size, D]`` (or int8 banks) — one layer's, or the whole
    pool's with ``page_table`` offset to the layer's pages
    (paged_forward_with_cache) — ``pos`` is a [B] i32 vector (slots decode
    at different depths), and multi-token calls are prefills starting at
    position 0 per slot. Rows past ``valid[b]`` are prompt padding and
    reach no page of a sequence (ops/paged_kv).
    ``tail=True`` (static) marks a prefix-cache TAIL prefill: ``pos`` may
    be nonzero per slot and the q rows must attend KV already resident in
    earlier pages, so the fresh-rows causal-flash shortcut is invalid and
    attention runs over the paged cache."""
    from ..ops.weight_only import dequantize_kv, is_weight_only, quantize_kv
    B, T, h = x.shape
    if page_table is not None:
        from ..ops.paged_attention import paged_attention
        from ..ops.paged_kv import paged_write
        k_cache = paged_write(k_cache, k, page_table, pos, valid)
        v_cache = paged_write(v_cache, v, page_table, pos, valid)
        from ..ops.flash_attention import (flash_attention,
                                           flash_attention_available)
        if T > 1 and not tail and flash_attention_available(q, k, v, None):
            # multi-token paged calls are engine prefills from position 0:
            # attention over the paged cache equals causal self-attention
            # over the fresh rows (padding rows only feed padding rows,
            # which the engine discards) — run the main flash kernel
            # instead of gathering the virtual cache. A TAIL prefill
            # (tail=True) starts mid-sequence and must see the cached
            # prefix pages, so it takes the paged path below.
            a = flash_attention(q, k, v, causal=True).reshape(B, T, h)
        else:
            a = paged_attention(q, k_cache, v_cache, page_table, pos,
                                cdt).reshape(B, T, h)
        return (x + wo_matmul(a, proj_w, cdt) + proj_b.astype(cdt),
                k_cache, v_cache)
    int8_cache = is_weight_only(k_cache)
    if int8_cache:
        def write(bank, rows):
            qr, sr = quantize_kv(rows)
            return {'int8': jax.lax.dynamic_update_slice(
                        bank['int8'], qr, (0, pos, 0, 0)),
                    'scale': jax.lax.dynamic_update_slice(
                        bank['scale'], sr.astype(bank['scale'].dtype),
                        (0, pos, 0))}
        k_cache, v_cache = write(k_cache, k), write(v_cache, v)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
    from ..ops.flash_attention import (
        flash_attention, flash_attention_available, flash_decode,
        flash_decode_available, flash_decode_int8)
    k_arr = k_cache['int8'] if int8_cache else k_cache
    if (isinstance(pos, int) and pos == 0
            and flash_attention_available(q, k, v, None)):
        # prefill at a STATIC position 0: attention over the cache equals
        # causal self-attention over the fresh k/v (later cache rows are
        # masked out anyway) — run the main flash kernel
        a = flash_attention(q, k, v, causal=True).reshape(B, T, h)
    elif flash_decode_available(q, k_arr):
        # pallas decode kernel: streams only cache blocks up to ``pos``
        a = (flash_decode_int8(q, k_cache, v_cache, pos) if int8_cache
             else flash_decode(q, k_cache, v_cache, pos)).reshape(B, T, h)
    else:
        from ..ops.flash_attention import repeat_kv
        if int8_cache:
            kc = dequantize_kv(k_cache['int8'], k_cache['scale'], cdt)
            vc = dequantize_kv(v_cache['int8'], v_cache['scale'], cdt)
        else:
            kc, vc = k_cache, v_cache
        k_cache_a, v_cache_a = repeat_kv(kc, vc, int(q.shape[2]))
        S = k_arr.shape[1]
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jnp.einsum('bqhd,bkhd->bhqk', q, k_cache_a) * scale  # [B,H,T,S]
        q_pos = pos + jnp.arange(T)[:, None]                    # [T,1]
        k_pos = jnp.arange(S)[None, :]                          # [1,S]
        s = jnp.where((k_pos <= q_pos)[None, None], s.astype(jnp.float32),
                      jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1).astype(cdt)
        a = jnp.einsum('bhqk,bkhd->bqhd', p, v_cache_a).reshape(B, T, h)
    return (x + wo_matmul(a, proj_w, cdt) + proj_b.astype(cdt),
            k_cache, v_cache)


def _cached_block(bp, x, k_cache, v_cache, pos, config, page_table=None,
                  valid=None, tail=False):
    """One block over a [B, T, H] slice starting at ``pos``."""
    cdt = jnp.dtype(config.dtype)
    with jax.named_scope('gpt.block'):      # as block_fn names its halves
        with jax.named_scope('attn'):
            y = _layer_norm(x, bp['ln1_g'], bp['ln1_b']).astype(cdt)
            q, k, v = _cached_qkv(bp, y, config, cdt)
            x, k_cache, v_cache = cached_attention(
                x, q, k, v, k_cache, v_cache, pos, bp['proj_w'],
                bp['proj_b'], cdt, page_table=page_table, valid=valid,
                tail=tail)
        with jax.named_scope('mlp'):
            y = _layer_norm(x, bp['ln2_g'], bp['ln2_b']).astype(cdt)
            x = x + _block_mlp(bp, y, cdt) + bp['out_b'].astype(cdt)
    return x, k_cache, v_cache


def paged_forward_with_cache(params, tokens, cache, pos, config,
                             last_only=False, block=_cached_block,
                             partitioner=None):
    """Paged-cache twin of ``forward_with_cache``: ``cache`` carries the
    page pools + ``page_table`` (+ optional ``valid``), ``pos`` is a [B]
    i32 vector. ``block`` lets moe_gpt reuse this driver with its own
    block body. Returns (logits, cache) with the table/valid passed
    through so the caller's cache pytree keeps one structure.

    The pool is never re-made. The layers' loop carries it whole, as the
    ``[L * N, ...]`` view of its planes (a bitcast), beside the hidden
    state; layer ``l`` sees it through ``page_table + l * N``, writes its
    rows into the carried buffer and attends out of the same buffer. With
    the pool donated (serving/generation.py) the compiled program holds
    no copy of the pool or of a layer's plane
    (tests/test_aot_tpu_compile.py). The planes must not be the scan's
    scanned inputs and stacked outputs: those are different buffers, so
    every layer's plane is copied out, into the stack, and the stack onto
    the donated parameter (35-40 % of busy time, PERF.md, PR 28).

    ``partitioner`` (a mesh-bound parallel.Partitioner) makes the trace
    mesh-aware: the KV pool planes are constrained to the ``kv_heads``
    layout on entry AND exit, so GSPMD keeps pages head-sharded across the
    whole layer scan instead of resharding KV around the attention
    collectives (parallel/mesh_engine.py; a None partitioner — the mp=1
    path — traces byte-identically to before)."""
    cdt = jnp.dtype(config.dtype)
    B, T = tokens.shape
    pos_v = jnp.asarray(pos, jnp.int32).reshape(-1)
    page_table = cache['page_table']
    valid = cache.get('valid')

    def pin_pool(plane):
        # int8 pools are {'int8','scale'} banks whose scale plane drops
        # the head_dim axis — only the raw 5-d layout is pinned (banks
        # still shard correctly via input-sharding propagation)
        if partitioner is None or getattr(plane, 'ndim', 0) != 5:
            return plane
        from ..ops.paged_kv import POOL_LOGICAL_AXES
        return jax.lax.with_sharding_constraint(
            plane, partitioner.sharding(POOL_LOGICAL_AXES))

    pool = {'k': pin_pool(cache['k']), 'v': pin_pool(cache['v'])}
    # STATIC marker set by the prefix-cache tail-prefill path (the engine
    # builds the cache dict in-trace, so a plain bool survives): q rows
    # must attend KV resident in earlier pages, not just the fresh rows
    tail = bool(cache.get('tail', False))
    ppos = jnp.clip(pos_v[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :],
                    0, config.max_seq_len - 1)            # [B, T]
    x = (wo_take(params['wte'], tokens)
         + jnp.take(params['wpe'], ppos, axis=0)).astype(cdt)

    n_layers, n_pages = jax.tree_util.tree_leaves(pool)[0].shape[:2]
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers * n_pages,) + a.shape[2:]), pool)

    def scan_body(carry, inp):
        xx, kc, vc = carry
        bp, layer = inp
        xx, kc, vc = block(bp, xx, kc, vc, pos_v, config,
                           page_table=page_table + layer * n_pages,
                           valid=valid, tail=tail)
        return (xx, kc, vc), None

    (x, k_new, v_new), _ = jax.lax.scan(
        scan_body, (x, flat['k'], flat['v']),
        (params['blocks'], jnp.arange(n_layers, dtype=jnp.int32)))
    new = jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers, n_pages) + a.shape[1:]),
        {'k': k_new, 'v': v_new})
    k_new, v_new = pin_pool(new['k']), pin_pool(new['v'])
    if last_only:
        if valid is not None:
            # per-slot prompt lengths: pick each slot's last REAL row
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, T - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    x = _layer_norm(x, params['lnf_g'], params['lnf_b']).astype(cdt)
    logits = wo_lm_head(x, params['wte'], cdt)
    out = {'k': k_new, 'v': v_new, 'page_table': page_table}
    if valid is not None:
        out['valid'] = valid
    return logits, out


def forward_with_cache(params, tokens, cache, pos, config: GPTConfig,
                       last_only=False, partitioner=None):
    """Run [B, T] tokens whose absolute positions start at ``pos`` (a traced
    scalar), reading/writing the KV cache. Returns (logits, cache) — logits
    [B,T,V], or [B,1,V] with ``last_only`` (prefill skips the full-vocab
    head matmul for all but the final position: at B=8, T0=1000, V=50304
    that matmul and its ~1.6 GB logits tensor are pure waste).
    T is the static block width: the prompt length at prefill, 1 per decode
    step — each width compiles exactly once.

    A paged cache (``is_paged``: page pools + ``page_table``) routes to
    ``paged_forward_with_cache`` with ``pos`` as a per-slot [B] vector;
    the dense contiguous cache stays the default. ``partitioner`` (mesh-
    bound, serving over an mp=N mesh) pins the paged pool to the
    ``kv_heads`` layout — see paged_forward_with_cache."""
    if is_paged(cache):
        return paged_forward_with_cache(params, tokens, cache, pos, config,
                                        last_only=last_only,
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
    logits = wo_lm_head(x, params['wte'], cdt)
    return logits, {'k': k_new, 'v': v_new}


# the pair the serving engine asks for (models/family.py)
_family.register(GPTConfig, _family.GenerationFamily(
    name='gpt', init_pool=init_paged_kv_cache,
    forward_with_cache=forward_with_cache, logical_axes=LOGICAL_AXES,
    quantize_decode_params=quantize_decode_params,
    serve_params=serve_params,
    # a 1,024-row prefill at 1.3B is ~20 ms on the chip and a width ~0.3 s
    # of every process's set-up (PERF.md, PR 39): two pages at a time
    prefill_pages=2))


def _sample(logits, temperature, top_k, top_p=None, key=None):
    """Greedy / temperature / top-k / nucleus next-token draw — the ONE
    sampling rule shared by the cache path and the sliding-window
    continuation. ``key`` overrides the global PRNG stream (reproducible
    functional sampling). top_k and top_p compose (intersection), as in
    the reference generation utilities."""
    if temperature == 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        from ..tensor.random import next_key
        key = next_key()
    lg = logits.astype(jnp.float32) / temperature
    nucleus = top_p is not None and top_p < 1.0
    if top_k or nucleus:
        # ONE descending sort serves both filters (per-token decode path)
        srt = jnp.sort(lg, axis=-1)[:, ::-1]
        if top_k:
            kth = srt[:, top_k - 1][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
            srt = jnp.where(jnp.arange(srt.shape[-1]) < top_k, srt, -jnp.inf)
        if nucleus:
            # keep the smallest prefix of the sorted (already top_k-masked)
            # distribution whose cumulative prob reaches top_p; the argmax
            # is ALWAYS kept (exclusive cumsum + explicit index-0 set, so
            # top_p <= 0 degrades to greedy, not to all -inf)
            probs = jax.nn.softmax(srt, axis=-1)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            keep = keep.at[:, 0].set(True)
            cut = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                          keepdims=True)
            lg = jnp.where(lg < cut, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def make_generate_loop(config, temperature=0.0, top_k=None, top_p=None,
                       forward_fn=None):
    """On-device autoregressive generation: ONE jitted program runs
    ``n_steps`` KV-cache decode steps via lax.scan (sampling included), so
    the whole loop costs a single dispatch instead of one host round-trip
    per token.

    -> gen(params, tok0 [B] i32, pos0 i32, cache, key, n_steps static)
       returning (tokens [B, n_steps] i32, cache). ``tok0`` is consumed as
    the input of the first step; the sample drawn from each step's logits
    is both emitted and fed to the next step.

    forward_fn(params, tokens, cache, pos, config) -> (logits, cache)
    defaults to this module's forward_with_cache; moe_gpt passes its own,
    sharing this one loop implementation.
    """
    fwd = forward_fn or forward_with_cache

    def gen(params, tok0, pos0, cache, key, n_steps):
        def body(carry, step_key):
            tok, pos, cache = carry
            logits, cache = fwd(params, tok[:, None], cache, pos, config)
            lg = logits[:, 0] if logits.ndim == 3 else logits
            nxt = _sample(lg, temperature, top_k, top_p, key=step_key)
            return (nxt, pos + 1, cache), nxt

        keys = jax.random.split(key, n_steps)
        (tok, pos, cache), toks = jax.lax.scan(
            body, (tok0, pos0, cache), keys)
        return jnp.swapaxes(toks, 0, 1), cache

    return jax.jit(gen, static_argnums=(5,), donate_argnums=(3,))


def make_decode_fns(config: GPTConfig):
    """-> (prefill, step), both jitted with donated caches.

    prefill(params, prompt [B,T], cache) -> (last_logits [B,V], cache)
    step(params, tok [B], pos, cache)    -> (logits [B,V], cache)
    """
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


# ---------------------------------------------------------------------------
# Hybrid-parallel train step
# ---------------------------------------------------------------------------

def _uses_shard_map(config: GPTConfig):
    """Explicit-collective path: sp ring / pp pipeline schedules, or a
    quantized gradient all-reduce (which needs an addressable dp psum)."""
    return (config.sp > 1 or config.pp > 1
            or getattr(config, 'grad_quant', 'none') not in (None, 'none'))


def _apply(optimizer, params, grads, opt_state, lr):
    """The optimizer's update, under the scope the profiler reads."""
    with jax.named_scope('gpt.optimizer'):
        return optimizer.functional_apply(params, grads, opt_state, lr)


def make_train_step(config: GPTConfig, optimizer, mesh=None):
    """Returns jitted step(params, opt_state, key, lr, tokens, targets) ->
    (loss, params, opt_state) sharded over the mesh. Shardings:
      params per param_specs (mp/pp), batch over ('dp',), sequence over 'sp'
      (ring attention), opt state ZeRO-sharded over dp when configured.
    config.grad_quant != 'none' reduces dp gradients through
    distributed/quant_collectives (block-scaled int8/int4/fp8 or the bf16
    fallback) instead of the full-width pmean.
    """
    from ..distributed.topology import get_mesh
    from ..parallel.train_jit import jit_train_step
    mesh = mesh or get_mesh()
    quant = getattr(config, 'grad_quant', 'none') or 'none'

    use_shard_map = _uses_shard_map(config)
    if config.dropout > 0.0 and config.pp > 1:
        # the pipeline loss paths do not sample dropout; silently training
        # a different model than configured is the r4-journey bug class —
        # refuse loudly (sp rides the ring kernels' in-kernel masks; dp/mp
        # ride the GSPMD path)
        raise NotImplementedError(
            'attention dropout under pipeline parallelism is not '
            'implemented — set dropout=0, or use dp/mp/sp layouts')

    fp8 = getattr(config, 'matmul_precision', 'none') == 'fp8'
    if fp8 and use_shard_map:
        raise NotImplementedError(
            "matmul_precision='fp8' under the explicit-collective "
            '(shard_map) layouts (sp/pp/grad_quant) is not implemented — '
            'use the GSPMD dp/mp path or matmul_precision=none')

    if fp8:
        # fp8 step: the delayed-scaling state is an explicit third arg and
        # output — step(params, opt_state, fp8_state, key, lr, tokens,
        # targets) -> (loss, params, opt_state, fp8_state). The new state
        # arrives as the GRADIENT of the old one (quantization/fp8.py), so
        # one backward pass yields grads and state with no side channel,
        # no host sync, and donation-compatible buffers.
        def step(params, opt_state, fp8_state, key, lr, tokens, targets):
            loss, (grads, new_fp8) = jax.value_and_grad(
                lambda p, f8: loss_fn(p, tokens, targets, config,
                                      key if config.dropout > 0.0 else None,
                                      fp8_state=f8),
                argnums=(0, 1))(params, fp8_state)
            new_p, new_s = _apply(optimizer, params, grads, opt_state, lr)
            return loss, new_p, new_s, new_fp8
        return jit_train_step(step, mesh, n_state=3)

    if not use_shard_map:
        def step(params, opt_state, key, lr, tokens, targets):
            # the step's key drives attention dropout when configured
            # (config.dropout == 0 leaves the trace unchanged)
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, targets, config,
                key if config.dropout > 0.0 else None)
            new_p, new_s = _apply(optimizer, params, grads, opt_state, lr)
            return loss, new_p, new_s
        # GSPMD path: the flash kernels shard over this mesh themselves
        return jit_train_step(step, mesh, n_state=2)

    # Explicit-collective path (shard_map over dp/sp/pp/mp): Megatron mp via
    # psum in block_fn, ring attention over sp, GPipe microbatch over pp.
    from ..parallel.pipeline import pipeline_apply, last_stage_mask

    explicit_mp = config.mp > 1

    if config.pp > 1 and config.pp_schedule == '1f1b':
        if quant != 'none':
            raise NotImplementedError(
                'grad_quant under the fused 1F1B schedule is not '
                "implemented — use pp_schedule='gpipe' or grad_quant='none'")
        return _make_train_step_1f1b(config, optimizer, mesh, explicit_mp)

    def spmd_loss(params, tokens, targets, seed=None):
        cdt = jnp.dtype(config.dtype)
        B, S = tokens.shape
        with jax.named_scope('gpt.embed'):
            sp_idx = jax.lax.axis_index('sp') if config.sp > 1 else 0
            pos = sp_idx * S + jnp.arange(S)
            x = jnp.take(params['wte'], tokens, axis=0) + params['wpe'][pos]
            x = x.astype(cdt)

        body = partial(block_fn, config=config, explicit_mp=explicit_mp)
        if config.remat:
            body = _remat(body, config)

        if config.dropout > 0.0 and seed is not None:
            # decorrelate ranks whose kernels see identical LOCAL
            # coordinates (dp batch shards; mp head shards), then one
            # derived seed per layer (same scheme as forward_hidden); the
            # sp ring folds its own (q rank, kv rank) pair into the seed.
            # every fold is mix_seed'd — nonlinear, so index strides can
            # never alias the hash's coordinate multipliers (review r5h)
            from ..ops.flash_attention import mix_seed, per_layer_seeds
            seed_eff = mix_seed(
                jnp.asarray(seed, jnp.uint32)
                + jnp.asarray(jax.lax.axis_index('dp'), jnp.uint32)
                * jnp.uint32(0x165667B1))
            if explicit_mp:
                seed_eff = mix_seed(
                    seed_eff + jnp.asarray(jax.lax.axis_index('mp'),
                                           jnp.uint32)
                    * jnp.uint32(0xD3A2646D))
            seeds = per_layer_seeds(seed_eff, config.num_layers)
            xs = (params['blocks'], seeds)

            def scan_body(c, inp):
                bp, sd = inp
                return body(bp, c, drop_seed=sd), None
        else:
            xs = params['blocks']

            def scan_body(c, bp):
                return body(bp, c), None

        with jax.named_scope('gpt.layers'):
            if config.pp > 1:
                def stage_fn(stage_params, xx):
                    out, _ = jax.lax.scan(scan_body, xx, stage_params)
                    return out
                x = pipeline_apply(stage_fn, params['blocks'], x,
                                   config.n_microbatches, axis_name='pp')
            else:
                x, _ = jax.lax.scan(scan_body, x, xs)

        with jax.named_scope('gpt.head'):
            x = _layer_norm(x, params['lnf_g'], params['lnf_b']).astype(cdt)
            loss = _xent(x @ params['wte'].T.astype(cdt), targets)
        if config.pp > 1:
            # head/loss are only valid on the last stage; the psum over 'pp'
            # happens AFTER the vjp (in spmd_valgrad) so no collective with an
            # ambiguous transpose sits inside the differentiated region
            loss = jnp.where(last_stage_mask('pp'), loss, 0.0)
        return loss

    def spmd_valgrad(params, tokens, targets, seed=None):
        """value+grad INSIDE shard_map: the only collectives the vjp sees are
        ppermute (pipeline/ring — exact inverse-permutation transpose) and the
        custom-vjp Megatron f/g pair, so grads are exact per rank. Cross-rank
        reductions are applied explicitly afterwards — which is what makes
        the dp gradient reduction addressable for quant_collectives."""
        drop_seed = seed if config.dropout > 0.0 else None
        loss, grads = jax.value_and_grad(
            lambda p: spmd_loss(p, tokens, targets, drop_seed))(params)
        if config.pp > 1:
            # shared (non-block) params: embedding grads live on stage 0,
            # head grads on the last stage → assemble across stages
            loss = jax.lax.psum(loss, 'pp')
            grads = {k: (v if k == 'blocks' else
                         jax.tree_util.tree_map(
                             lambda g: jax.lax.psum(g, 'pp'), v))
                     for k, v in grads.items()}
        reduce_axes = ['dp'] + (['sp'] if config.sp > 1 else [])
        with jax.named_scope('gpt.grad_reduce'):
            for ax in reduce_axes:
                loss = jax.lax.pmean(loss, ax)
                if ax == 'dp' and quant != 'none':
                    from ..distributed import quant_collectives as qc
                    from ..ops.flash_attention import mix_seed
                    qseed = None
                    if seed is not None:
                        # decorrelate the rounding stream from the dropout
                        # stream sharing the same step seed
                        qseed = mix_seed(jnp.asarray(seed, jnp.uint32)
                                         ^ jnp.uint32(0xA5A5F00D))
                    grads = qc.psum_tree(grads, 'dp', mode=quant,
                                         seed=qseed,
                                         stochastic=qseed is not None,
                                         mean=True)
                else:
                    grads = jax.tree_util.tree_map(
                        lambda g, _ax=ax: jax.lax.pmean(g, _ax), grads)
        return loss, grads

    pspec_tree = train_specs(config)
    data_spec = _partitioner(config, explicit=True).spec(('batch', 'length'))

    # a seed rides the step key into shard_map when anything inside needs
    # per-step randomness: attention dropout, or stochastic rounding in the
    # quantized gradient all-reduce
    needs_seed = config.dropout > 0.0 or quant in ('int8', 'int4')
    if needs_seed:
        smapped = jax.shard_map(spmd_valgrad, mesh=mesh,
                                in_specs=(pspec_tree, data_spec, data_spec,
                                          P()),
                                out_specs=(P(), pspec_tree),
                                check_vma=False)

        def step(params, opt_state, key, lr, tokens, targets):
            seed = jax.random.bits(key, (), jnp.uint32)
            loss, grads = smapped(params, tokens, targets, seed)
            new_p, new_s = _apply(optimizer, params, grads, opt_state, lr)
            return loss, new_p, new_s

        return jit_train_step(step, mesh, n_state=2)

    smapped = jax.shard_map(spmd_valgrad, mesh=mesh,
                            in_specs=(pspec_tree, data_spec, data_spec),
                            out_specs=(P(), pspec_tree), check_vma=False)

    def step(params, opt_state, key, lr, tokens, targets):
        loss, grads = smapped(params, tokens, targets)
        new_p, new_s = _apply(optimizer, params, grads, opt_state, lr)
        return loss, new_p, new_s

    return jit_train_step(step, mesh, n_state=2)


def _make_train_step_1f1b(config: GPTConfig, optimizer, mesh, explicit_mp):
    """Fused 1F1B pipeline train step: manual fwd+bwd via
    parallel.pipeline.pipeline_train_1f1b (O(pp) in-flight activations), no
    outer jax.grad. Reference: fleet pipeline_parallel.py 1F1B scheduler."""
    from ..parallel.pipeline import pipeline_train_1f1b
    from ..parallel.train_jit import jit_train_step

    shared_keys = ('wte', 'wpe', 'lnf_g', 'lnf_b')

    def spmd_grads(params, tokens, targets):
        cdt = jnp.dtype(config.dtype)
        shared = {k: params[k] for k in shared_keys}

        def embed_fn(sh, tok):
            with jax.named_scope('gpt.embed'):
                S = tok.shape[1]
                sp_idx = jax.lax.axis_index('sp') if config.sp > 1 else 0
                pos = sp_idx * S + jnp.arange(S)
                return (jnp.take(sh['wte'], tok, axis=0)
                        + sh['wpe'][pos]).astype(cdt)

        body = partial(block_fn, config=config, explicit_mp=explicit_mp)
        if config.remat:
            body = _remat(body, config)

        def stage_fn(stage_params, xx):
            with jax.named_scope('gpt.layers'):
                out, _ = jax.lax.scan(lambda c, bp: (body(bp, c), None),
                                      xx, stage_params)
            return out

        def head_fn(sh, h, tgt):
            with jax.named_scope('gpt.head'):
                x = _layer_norm(h, sh['lnf_g'], sh['lnf_b']).astype(cdt)
                return _xent(x @ sh['wte'].T.astype(cdt), tgt)

        loss, g_blocks, g_shared = pipeline_train_1f1b(
            stage_fn, embed_fn, head_fn, params['blocks'], shared,
            tokens, targets, config.n_microbatches, axis_name='pp')

        grads = dict(g_shared)
        grads['blocks'] = g_blocks
        with jax.named_scope('gpt.grad_reduce'):
            for ax in ['dp'] + (['sp'] if config.sp > 1 else []):
                loss = jax.lax.pmean(loss, ax)
                grads = jax.tree_util.tree_map(
                    lambda g, _ax=ax: jax.lax.pmean(g, _ax), grads)
        return loss, grads

    pspec_tree = train_specs(config)
    data_spec = _partitioner(config, explicit=True).spec(('batch', 'length'))
    smapped = jax.shard_map(spmd_grads, mesh=mesh,
                            in_specs=(pspec_tree, data_spec, data_spec),
                            out_specs=(P(), pspec_tree), check_vma=False)

    def step(params, opt_state, key, lr, tokens, targets):
        loss, grads = smapped(params, tokens, targets)
        new_p, new_s = _apply(optimizer, params, grads, opt_state, lr)
        return loss, new_p, new_s

    return jit_train_step(step, mesh, n_state=2)


def train_specs(config: GPTConfig):
    """PartitionSpecs matching what make_train_step expects for params:
    the explicit-collective (shard_map) rules when the step uses that path
    — per-rank views, vocab replicated — otherwise the GSPMD rules. Both
    resolve LOGICAL_AXES through the same partitioner rules table."""
    explicit = _uses_shard_map(config)
    return _partitioner(config, explicit=explicit).tree_specs(LOGICAL_AXES)


def place_params(params, config, mesh):
    """device_put every leaf to its train_specs sharding on ``mesh``; a
    leaf that cannot be placed raises."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, train_specs(config))


# ---------------------------------------------------------------------------
# Layer-API wrapper
# ---------------------------------------------------------------------------

class GPTForCausalLM(Layer):
    """Stateful paddle-style wrapper over the functional core."""

    def __init__(self, config: GPTConfig = None, **kwargs):
        super().__init__()
        self.config = config or GPTConfig(**kwargs)
        from ..tensor.random import next_key
        raw = init_params(self.config, next_key())
        leaves, treedef = jax.tree_util.tree_flatten(raw)
        self._treedef = treedef
        self._n = len(leaves)
        for i, leaf in enumerate(leaves):
            self.add_parameter(f'p{i}', Parameter(leaf))

    def _params(self):
        return jax.tree_util.tree_unflatten(
            self._treedef, [self._parameters[f'p{i}']._value
                            for i in range(self._n)])

    def forward(self, tokens):
        from ..core.dispatch import apply_op
        cfg = self.config
        plist = [self._parameters[f'p{i}'] for i in range(self._n)]
        treedef = self._treedef

        def pure(tok, *leaves):
            params = jax.tree_util.tree_unflatten(treedef, list(leaves))
            return forward(params, jnp.asarray(tok).astype(jnp.int32), cfg)
        return apply_op(pure, tokens, *plist)

    def generate(self, tokens, max_new_tokens=32, temperature=1.0,
                 top_k=None, top_p=None):
        """KV-cache autoregressive sampling: one compiled prefill + ONE
        on-device generation loop (make_generate_loop) that runs all cached
        decode steps in a single dispatch — O(S_max d) per token, with loop
        lengths bucketed to powers of two so varying lengths reuse a small
        set of compiled programs. Tokens past the context window continue
        on the sliding-window recompute path, so the cache is used for
        every token that fits it."""
        cfg = self.config
        toks = tokens._value if isinstance(tokens, Tensor) else jnp.asarray(tokens)
        toks = toks.astype(jnp.int32)
        B, T0 = toks.shape
        # +1: the final cached step runs at pos max_seq_len-1 (filling the
        # last cache row) and its logits see the full window — identical
        # conditioning to the sliding path's first step
        n_cached = (min(max_new_tokens, cfg.max_seq_len - T0 + 1)
                    if T0 < cfg.max_seq_len else 0)
        if n_cached > 0:
            params = self._decode_params()
            prefill, step = self._decode_fns()
            cache = init_kv_cache(cfg, B)
            logits, cache = prefill(params, toks, cache)
            first = _sample(logits, temperature, top_k, top_p)
            pieces = [toks, first[:, None]]
            if n_cached > 1:
                # all remaining cached tokens run on-device in one dispatch
                # (make_generate_loop); greedy tokens are bit-identical to
                # the per-step python loop this replaces. The step count is
                # bucketed to the next power of two (excess tokens dropped)
                # so varying prompt/max_new lengths reuse a handful of
                # compiled programs instead of retracing per length; extra
                # steps may clamp at the last cache row, which only affects
                # the discarded tail.
                loop = self._generate_loop(temperature, top_k, top_p)
                n = n_cached - 1
                bucket = 1 << (n - 1).bit_length() if n > 1 else 1
                if temperature != 0:
                    from ..tensor.random import next_key
                    key = next_key()
                else:
                    # greedy never consumes randomness — a fixed key keeps
                    # the global PRNG stream untouched so seeded runs are
                    # reproducible regardless of generation length
                    key = jax.random.PRNGKey(0)
                new, cache = loop(params, first, jnp.int32(T0), cache,
                                  key, bucket)
                pieces.append(new[:, :n])
            toks = jnp.concatenate(pieces, axis=1)
        rest = max_new_tokens - n_cached
        if rest > 0:
            return self._generate_sliding(toks, rest, temperature, top_k,
                                          top_p)
        return Tensor(toks)

    def _decode_fns(self):
        if getattr(self, '_decode_cache', None) is None:
            self._decode_cache = make_decode_fns(self.config)
        return self._decode_cache

    def _generate_loop(self, temperature, top_k, top_p):
        """Per-(sampling-config) cache of the on-device generation loop —
        repeated generate() calls with the same knobs must not retrace."""
        key = (temperature, top_k, top_p)
        cache = getattr(self, '_gen_loops', None)
        if cache is None:
            from .decode_cache import DecodeFnCache
            cache = self._gen_loops = DecodeFnCache(name='gpt.gen_loops')
        return cache.get(key, lambda: make_generate_loop(
            self.config, temperature, top_k, top_p))

    def enable_int8_decode(self, enable=True):
        """Serve ``generate`` from weight-only int8 matrices (halved HBM
        traffic on the bandwidth-bound decode path; ops/weight_only.py).
        Quantization snapshots the CURRENT weights lazily at the next
        ``generate``; call again after further training to re-snapshot.
        Training and ``forward`` are untouched."""
        self._int8_decode = enable
        self._int8_params = None
        return self

    def _decode_params(self):
        if not getattr(self, '_int8_decode', False):
            return self._params()
        if getattr(self, '_int8_params', None) is None:
            self._int8_params = jax.tree_util.tree_map(
                jnp.asarray, quantize_decode_params(self._params()))
        return self._int8_params

    def _generate_sliding(self, toks, max_new_tokens, temperature, top_k,
                          top_p=None):
        """Full-context recompute with a sliding window — the continuation
        once generation outgrows the KV cache (= max_seq_len). Every window
        is full-width here, so the jitted forward compiles once."""
        cfg = self.config
        if getattr(self, '_sliding_fwd', None) is None:
            # cached like _decode_fns: repeated boundary-crossing generate()
            # calls must not recompile the full-width forward each time
            self._sliding_fwd = jax.jit(lambda p, t: forward(p, t, cfg)[:, -1])
        fwd = self._sliding_fwd
        for _ in range(max_new_tokens):
            ctx = toks[:, -cfg.max_seq_len:]
            nxt = _sample(fwd(self._decode_params(), ctx), temperature,
                          top_k, top_p)
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        return Tensor(toks)
