"""Decoder family with multi-head latent attention and routed expert layers
(the DeepSeek-V3 block, as dots.vlm1's language model has it): RMSNorm,
pre-norm residuals, YaRN rotary positions on a slice of each head, leading
dense SwiGLU layers, then layers of routed experts with one shared expert
(parallel/routed_experts.py), an untied head.

This is a SERVED family: weights in ``param_dtype`` (bfloat16), a cached
forward for ``serving.GenerationEngine`` (models/family.py), no train step.
Its cache is one plane ``latent [L, pages, page_size, 640]``: the
compressed key/value ``c`` (512) and the one rotary key all heads share
(64), padded with zeros to whole lanes, a row a token a layer, no heads
axis. A prefill runs the expanded form over its
fresh rows through the flash kernel; a decode step runs the absorbed form
over the pool (ops/paged_latent_attention.py). A prefill starts at row 0:
the family does not read a cached prefix (``tail_prefill=False``), so the
engine refuses it a prefix cache.

``held = (first, count)`` says which routed experts' weights are here; the
router scores all ``n_routed_experts``. Layers are a list, not a stacked
scan: five layers of two kinds do not stack. The unrolled step updates
the donated pool in place; so does a scan that CARRIES the pool (what
copied it in ``models/gpt.py`` before PR 28 was a scan with the planes
among its scanned inputs and stacked outputs).

Rotary dims pair half-split ([x1 | x2]); a checkpoint that interleaves
them loads with those columns of W_qb and W_kva permuted.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..ops.dense import dot as _dot, rms as _rms
from ..ops.paged_kv import paged_write
from ..ops.paged_latent_attention import (latent_prefill_attention,
                                          paged_latent_attention)
from ..parallel import routed_experts as _re
from . import family as _family

YARN = {'type': 'yarn', 'factor': 40, 'original_max_position_embeddings':
        4096, 'beta_fast': 32, 'beta_slow': 1, 'mscale': 1,
        'mscale_all_dim': 1}


@dataclasses.dataclass
class LatentMoEConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # dense layers' SwiGLU
    moe_intermediate_size: int = 2048       # one expert's
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3          # leading dense layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256             # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = dataclasses.field(default_factory=lambda: dict(YARN))
    max_position_embeddings: int = 163840
    # (first, count) of the routed experts held here; None: all of them
    held: tuple = None
    dtype: str = 'bfloat16'
    param_dtype: str = 'bfloat16'

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        self.held = tuple(int(x) for x in self.held)
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f'held {self.held} outside the '
                             f'{self.n_routed_experts} routed experts')
        if self.n_routed_experts % self.n_group:
            raise ValueError('n_group must divide n_routed_experts')

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self):
        """Columns of a pool row: ``latent_width`` padded with zeros to
        whole lanes of 128. At 576 columns the TPU compiler re-tiles (copies)
        the whole pool before every kernel call; at 640 it hands the
        parameter over as it is (PERF.md section 6, PR 27)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self):
        rs = self.rope_scaling or {}
        m = _yarn_mscale(float(rs.get('factor', 1)),
                         float(rs.get('mscale_all_dim', 0)))
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * m * m)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_inv_freq(config):
    """[rope width / 2] float32: YaRN's blend of the base frequencies and
    the interpolated ones, or the base ones without ``rope_scaling``."""
    d, base = config.qk_rope_head_dim, float(config.rope_theta)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / d)
    rs = config.rope_scaling
    if not rs:
        return f
    orig = float(rs['original_max_position_embeddings'])

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    lo = max(math.floor(dim_of(float(rs['beta_fast']))), 0)
    hi = min(math.ceil(dim_of(float(rs['beta_slow']))), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / float(rs['factor'])) * ramp


def _rope_tables(config, positions):
    """positions [B, T] -> (cos, sin) [B, T, rope width / 2] float32."""
    rs = config.rope_scaling or {}
    factor = float(rs.get('factor', 1))
    mult = (_yarn_mscale(factor, float(rs.get('mscale', 0)))
            / _yarn_mscale(factor, float(rs.get('mscale_all_dim', 0))))
    ang = positions[..., None].astype(jnp.float32) * rotary_inv_freq(config)
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def _rope(x, cos, sin):
    """x [..., d] float32, half-split pairing; cos/sin broadcast to it."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# ---- weights ---------------------------------------------------------------

def init_params(config, key):
    """Random weights, one leaf at a time (a float32 temporary of every
    layer's experts at once would not fit beside them). The structure:

        embed [V, H], head [H, V], norm_f [H],
        layers: [{attn_norm, ffn_norm, q_a, q_a_norm, q_b, kv_a, kv_a_norm,
                  kv_b, o,  and  mlp {gate, up, down}  (a dense layer)
                  or  router [E_all, H] f32, router_bias [E_all] f32,
                      experts {gate, up [held, H, F], down [held, F, H]},
                      shared {gate, up, down}}]"""
    c, pdt = config, jnp.dtype(config.param_dtype)
    h, nh = c.hidden_size, c.num_attention_heads
    dqk = c.qk_nope_head_dim + c.qk_rope_head_dim
    keys = iter(jax.random.split(key, 32 * (c.num_hidden_layers + 1)))

    def nrm(shape, fan_in, dtype=pdt):
        return (fan_in ** -0.5 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    def gain(n):
        return (1.0 + 0.1 * jax.random.normal(
            next(keys), (n,), jnp.float32)).astype(pdt)

    def swiglu(f, lead=()):
        return {'gate': nrm(lead + (h, f), h), 'up': nrm(lead + (h, f), h),
                'down': nrm(lead + (f, h), f)}

    layers = []
    for l in range(c.num_hidden_layers):
        lp = {'attn_norm': gain(h), 'ffn_norm': gain(h),
              'q_a': nrm((h, c.q_lora_rank), h),
              'q_a_norm': gain(c.q_lora_rank),
              'q_b': nrm((c.q_lora_rank, nh * dqk), c.q_lora_rank),
              'kv_a': nrm((h, c.latent_width), h),
              'kv_a_norm': gain(c.kv_lora_rank),
              'kv_b': nrm((c.kv_lora_rank,
                           nh * (c.qk_nope_head_dim + c.v_head_dim)),
                          c.kv_lora_rank),
              'o': nrm((nh * c.v_head_dim, h), nh * c.v_head_dim)}
        if l < c.first_k_dense_replace:
            lp['mlp'] = swiglu(c.intermediate_size)
        else:
            lp['router'] = nrm((c.n_routed_experts, h), h, jnp.float32)
            lp['router_bias'] = 0.002 * jax.random.normal(
                next(keys), (c.n_routed_experts,), jnp.float32)
            lp['experts'] = swiglu(c.moe_intermediate_size, (c.held[1],))
            lp['shared'] = swiglu(c.moe_intermediate_size
                                  * c.n_shared_experts)
        layers.append(lp)
    return {'embed': nrm((c.vocab_size, h), 1.0), 'norm_f': gain(h),
            'head': nrm((h, c.vocab_size), h), 'layers': layers}


def init_pool(config, num_pages, page_size):
    """The latent page pool: ``{'latent': [L, num_pages, page_size,
    pool_width]}`` in the compute dtype, a row ``[c | k_rope | zeros]``;
    page 0 is the trash page."""
    if num_pages < 2:
        raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
    return {'latent': jnp.zeros(
        (config.num_hidden_layers, num_pages, page_size,
         config.pool_width), jnp.dtype(config.dtype))}


# ---- the layers ------------------------------------------------------------

def _attention(lp, x, pool, layer, pos_v, page_table, valid, config):
    """x [B, T, H] (already normed) at rows pos_v[b].. -> (out [B, T, H]
    float32, pool with the rows written)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = x.shape
    nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                      c.qk_rope_head_dim, c.v_head_dim)
    rank, eps = c.kv_lora_rank, c.rms_norm_eps
    positions = pos_v[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    cos, sin = _rope_tables(c, positions)                  # [B, T, dr / 2]

    cq = _rms(_dot(x, lp['q_a'], cdt), lp['q_a_norm'], eps)
    q = _dot(cq, lp['q_b'], cdt).reshape(b, t, nh, dn + dr)
    q_nope = q[..., :dn]
    q_rope = _rope(q[..., dn:], cos[:, :, None], sin[:, :, None])
    kv = _dot(x, lp['kv_a'], cdt)                          # [B, T, rank+dr]
    ckv = _rms(kv[..., :rank], lp['kv_a_norm'], eps)
    k_rope = _rope(kv[..., rank:], cos, sin)
    lanes = jnp.zeros((b, t, c.pool_width - c.latent_width), jnp.float32)
    rows = jnp.concatenate([ckv, k_rope, lanes], axis=-1).astype(cdt)

    n_layers, n = pool.shape[:2]
    plane = paged_write(pool.reshape((n_layers * n,) + pool.shape[2:]),
                        rows, page_table + jnp.int32(layer * n), pos_v,
                        valid)
    pool = plane.reshape(pool.shape)

    kv_b = lp['kv_b'].astype(cdt).reshape(rank, nh, dn + dv)
    if t > 1:
        # prefill from row 0: causal attention over the fresh rows, in the
        # expanded form (k and v a head, out of the compressed rows)
        kvx = jnp.einsum('btr,rhd->bthd', rows[..., :rank], kv_b,
                         preferred_element_type=jnp.float32).astype(cdt)
        k = jnp.concatenate(
            [kvx[..., :dn], jnp.broadcast_to(
                rows[:, :, None, rank:rank + dr], (b, t, nh, dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1).astype(cdt)
        o = latent_prefill_attention(qq, k, kvx[..., dn:],
                                     scale=c.softmax_scale)
    else:
        # decode: the absorbed form over the pool; the heads' queries are
        # carried into the latent space and the result out of it
        q_lat = jnp.einsum('bhd,rhd->bhr', q_nope[:, 0].astype(cdt),
                           kv_b[..., :dn],
                           preferred_element_type=jnp.float32)
        qq = jnp.concatenate([q_lat, q_rope[:, 0], jnp.broadcast_to(
            lanes[:, :1], (b, nh, lanes.shape[-1]))], axis=-1).astype(cdt)
        o_lat = paged_latent_attention(qq, pool, page_table, pos_v, layer,
                                       scale=c.softmax_scale, rank=rank)
        o = jnp.einsum('bhr,rhd->bhd', o_lat, kv_b[..., dn:],
                       preferred_element_type=jnp.float32
                       ).astype(cdt)[:, None]
    return _dot(o.reshape(b, t, nh * dv), lp['o'], cdt), pool


def _block(lp, x, pool, layer, pos_v, page_table, valid, row_ok, config):
    """One layer over [B, T, H]. -> (x, pool, counts or None)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, h = x.shape
    with jax.named_scope('latent_moe.block'):
        with jax.named_scope('attn'):
            a, pool = _attention(
                lp, _rms(x, lp['attn_norm'], c.rms_norm_eps).astype(cdt),
                pool, layer, pos_v, page_table, valid, c)
            x = (x.astype(jnp.float32) + a).astype(cdt)
        y = _rms(x, lp['ffn_norm'], c.rms_norm_eps).astype(cdt)
        if 'mlp' in lp:
            with jax.named_scope('mlp'):
                return x + _re.swiglu(lp['mlp'], y, cdt), pool, None
        with jax.named_scope('moe'):
            out, counts = _re.routed_experts(
                lp, y.reshape(b * t, h), row_ok.reshape(b * t), held=c.held,
                top_k=c.num_experts_per_tok, n_group=c.n_group,
                topk_group=c.topk_group, scale=c.routed_scaling_factor,
                normalise=c.norm_topk_prob)
        return x + out.reshape(b, t, h), pool, counts


def _decoder(params, tokens, pool, pos_v, page_table, valid, config,
             last_only):
    """The layers and the head over [B, T] tokens. -> (logits, pool, counts
    [5] or None)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t = tokens.shape
    row_ok = (jnp.ones((b, t), bool) if valid is None else
              jnp.arange(t)[None, :] < valid.astype(jnp.int32)[:, None])
    x = jnp.take(params['embed'], tokens, axis=0).astype(cdt)
    block = functools.partial(_block, config=c)
    if t > 1:
        # a prefill is traced and lowered once for every width the engine
        # may call it at: its layers of one make (the expert layers, say)
        # are traced once and called, the layer's number an argument (a
        # decode step's kernel takes the number static)
        block = jax.jit(block)
    counted = []
    for layer, lp in enumerate(params['layers']):
        x, pool, counts = block(lp, x, pool, layer, pos_v, page_table,
                                valid, row_ok)
        if counts is not None:
            counted.append(counts)
    if last_only:
        if valid is not None:
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope('latent_moe.head'):
        y = _rms(x, params['norm_f'], c.rms_norm_eps).astype(cdt)
        logits = _dot(y, params['head'], cdt).astype(cdt)
    total = None
    if counted:
        by_layer = jnp.stack(counted)                     # [layers, 5]
        total = jnp.concatenate([jnp.sum(by_layer[:, :4], axis=0),
                                 jnp.max(by_layer[:, 4:], axis=0)])
    return logits, pool, total


def forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None):
    """[B, T] tokens at rows pos[b].. over the paged latent cache
    (``cache``: the pool's 'latent' plane, 'page_table' [B, P_max], and for
    a prefill 'valid' [B]) -> (logits, cache). T > 1 is a prefill from row
    0; T == 1 a decode step. The cache that comes back holds 'counts': the
    routed layers' ``routed_experts.COUNTS``, summed over the layers (the
    largest group's rows: the largest of any layer). Rows past ``valid``
    are padding at any ``T``: how wide a prompt is padded is the engine's
    choice (``family.prefill_widths``)."""
    del partitioner     # attention is replicated; the pool has no heads
    b, t = tokens.shape
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    page_table, valid = cache['page_table'], cache.get('valid')
    if t > 1 and valid is None:
        valid = jnp.full((b,), t, jnp.int32)
    logits, pool, counts = _decoder(
        params, tokens, cache['latent'], pos_v, page_table, valid, config,
        last_only)
    out = dict(cache, latent=pool)
    if counts is not None:
        out['counts'] = counts
    return logits, out


def forward(params, tokens, config):
    """[B, T] tokens -> [B, T, V] logits: a prefill over a throwaway pool
    of just these rows (tests and small checks; serving goes through
    ``GenerationEngine``)."""
    b, t = tokens.shape
    pages = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    cache = dict(init_pool(config, b + 1, t), page_table=pages)
    return forward_with_cache(params, tokens, cache,
                              jnp.zeros((b,), jnp.int32), config)[0]


def note_counts(counts, phase):
    """A step's routed-row counts, to the ``moe.*`` counters (the engine
    calls this with what ``forward_with_cache`` counted)."""
    labels = {'phase': phase}
    vals = dict(zip(_re.COUNTS, (int(x) for x in counts)))
    for name in _re.COUNTS[:4]:
        _obs.counter(f'moe.{name}_total', labels=labels).inc(vals[name])
    _obs.histogram('moe.group_rows_max', labels=labels).observe(
        vals['group_rows_max'])


_family.register(LatentMoEConfig, _family.GenerationFamily(
    name='latent_moe', init_pool=init_pool,
    forward_with_cache=forward_with_cache,
    pool_logical_axes=('layers', 'kv_pages', None, None),
    note_counts=note_counts, tail_prefill=False))
