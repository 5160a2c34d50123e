"""Decoder family whose every layer is compressed convolutional attention
and a top-1 routed expert half (Zyphra's ``zaya`` block, as ZAYA1-8B has
it). benchmark/reference/zaya.py is the definition of the equations; what
follows is how a served forward keeps them.

    x = E[ids];  r = 0
    layer:  x = merge_a(x, CCA(N_a(x)));  (y, r) = MoE(N_m(x), r);
            x = merge_m(x, y)
    merge(x, f) = (a_r * x + b_r) + (a_f * f + b_f)
    logits = N_f(x) E^T                         (the head is the embedding)

*CCA.* ``[q~ | k~ | v1 | v2] = u W_qkv`` (one product); ``c = [q~ | k~]``
goes through a depthwise causal convolution of two rows (``ops/ssm.py``
``causal_conv`` / ``conv_step``) and a second one of two rows that mixes a
head's 128 channels (a batched 128 x 128 product a head); the mean of the
pre-convolution q and k joins both, q and k are L2-normalised a head (k
scaled by ``exp(temp)``), rotated on half of a head's dims, and attend as
grouped-query heads of 128 through the kernels every family calls
(``flash_attention``, ``paged_attention``). The value of row t is ``[u_t
W_v1 | u_{t-1} W_v2]``. So a row depends on the two rows before it OUTSIDE
attention, and a decode step needs them.

*The expert half.* The router is this family's own (a float32 MLP over a
256-wide state that each layer hands to the next: ``_router``); it chooses
ONE of the experts or to skip them. ``parallel/routed_experts.py`` takes its
answer: a skip row meets no expert and gets ``p_skip u`` here instead.
``held = (first, count)`` says which experts' weights are here.

This is a SERVED family (models/family.py): a cached forward for
``serving.GenerationEngine``, no train step. Its pool has TWO KINDS of
plane: ``k`` / ``v`` ``[layers, pages, kv heads, page_size, 128]`` through
the engine's allocator and page table, holding K and V AS THEY ARE ATTENDED
(after the convolutions, the norm and the rotary: 1,024 B a token a layer at
the published sizes), and the per-slot kind ``tail``: ``conv`` ``[layers,
slots, 2 * C]`` (the last two PRE-convolution rows of ``c``, C = 1,280: both
convolutions together reach back two rows) and ``vtail`` ``[layers, slots,
128]`` (``u W_v2`` of the last row), a row a SLOT: the engine tells a call
which slots its sequences are (``page_table['tail']``: [B] int32). A prefill
runs from row 0 over its padded prompt and writes rows ``valid - 2``,
``valid - 1`` of ``c`` (zeros where they lie before row 0) and ``valid -
1`` of ``u W_v2`` over its slot's row; a decode step reads and rewrites
every slot's row. A prefill cannot start past row 0 (``tail_prefill=False``:
a prefix hit would need the tail at the boundary).

The stack is a ``lax.scan`` over the layers with the router's state in the
carry: one layer's body is compiled. Every leaf is stacked over the layers
(``stack_layers``); the experts' stacks are handed to the grouped product
WHOLE, ``[layers * held, ...]``, with the layer as an offset, because a
layer's slice of them would be a copy of 400 MB a layer a step.
"""
import dataclasses

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..ops import ssm as _rec
from ..ops.dense import dot as _dot, rms as _rms
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import paged_attention
from ..ops.paged_kv import paged_write
from ..parallel import routed_experts as _re
from . import family as _family

# leaves that are the right-hand operand of a product of the layers' own
# (held in the compute dtype); the router's are products too, in ITS dtype
MATRICES = ('embed', 'qkv', 'o', 'conv1', 'gate', 'up', 'down')
ROUTER_MATRICES = ('down', 'w1', 'w2', 'w3')
# what a call counts beside the routed layer's (COUNTS of routed_experts):
# rows that chose to skip the experts, summed over the layers, and the keys
# a decode step's slots attended, one layer's worth
OWN_COUNTS = ('rows_skipped', 'keys_attended')
# what a call notes of a sequence's last valid row, a layer (the cache's
# 'row_notes' [B, layers * sum of these] float32, which the engine hands a
# request that asked for its logits): the router's input (the normed
# residual it projects), its state after the depth averaging, the chosen
# one's probability and the choice. With the state of the layer above
# (zero for the first) that is a router's whole input and whole answer, so
# whoever holds the weights can run the router again on the program's own
# rows and tell ITS arithmetic from the residual stream's
NOTE = ('router_input', 'router_state', 'probability', 'choice')


def note_widths(config):
    """{name of ``NOTE``: its width in a layer's note}."""
    return dict(zip(NOTE, (config.hidden_size, config.router_hidden_size,
                           1, 1)))


@dataclasses.dataclass
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    moe_intermediate_size: int = 2048       # one expert's
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                      # the depthwise convolution's rows
    cca_time1: int = 2                      # the grouped one's
    num_experts: int = 16                   # the router's experts (+ skip)
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # (first, count) of the experts held here; None: all of them
    held: tuple = None
    dtype: str = 'bfloat16'
    param_dtype: str = 'bfloat16'
    router_dtype: str = 'float32'           # W_down to the choice

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(int(x) for x in self.held)
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f'held {self.held} outside the '
                             f'{self.num_experts} experts')
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('num_key_value_heads must divide '
                             'num_attention_heads')
        if (self.cca_time0, self.cca_time1, self.num_experts_per_tok,
                self.num_key_value_heads) != (2, 2, 1, 2):
            raise ValueError(
                'what is written: two convolutions of two rows each, one '
                'expert a token, two KV heads (a value row is [u_t W_v1 | '
                'u_{t-1} W_v2], a head each)')

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def conv_dim(self):
        """C: the channels the convolutions run over, ``[q~ | k~]``."""
        return (self.num_attention_heads
                + self.num_key_value_heads) * self.head_dim


def page_kinds(config):
    """The kinds of plane a layer needs (models/family.py): K and V pages,
    and a slot's two tails."""
    return (_family.PageKind('kv', planes=('k', 'v')),
            _family.PageKind('tail', per_slot=True,
                             planes=('conv', 'vtail')))


# ---- weights ---------------------------------------------------------------

def init_layer(config, key):
    """One layer's random weights, leaf for leaf what
    benchmark/reference/zaya.py ``init_layer`` makes (which says what each
    is): matrices N(0, 1/fan_in) in ``param_dtype``, the router, the
    depthwise convolution and the small leaves float32."""
    c, pdt = config, jnp.dtype(config.param_dtype)
    h, f, d, r = (c.hidden_size, c.moe_intermediate_size, c.head_dim,
                  c.router_hidden_size)
    nq, nk, e = c.num_attention_heads * d, c.num_key_value_heads * d, \
        c.num_experts
    keys = iter(jax.random.split(key, 40))

    def nrm(shape, std, dtype=pdt):
        return (std * jax.random.normal(next(keys), shape,
                                        jnp.float32)).astype(dtype)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)

    def merge():
        return jnp.stack([gain(h), nrm((h,), 0.02, jnp.float32), gain(h),
                          nrm((h,), 0.02, jnp.float32)])
    count = c.held[1]
    return {
        'norm_attn': gain(h), 'norm_moe': gain(h),
        'merge_attn': merge(), 'merge_moe': merge(),
        'q': nrm((h, nq), h ** -0.5), 'k': nrm((h, nk), h ** -0.5),
        'v1': nrm((h, d), h ** -0.5), 'v2': nrm((h, d), h ** -0.5),
        'o': nrm((nq, h), nq ** -0.5),
        'conv0': nrm((2, c.conv_dim), 2 ** -0.5, jnp.float32),
        'conv1': nrm((2, c.conv_dim // d, d, d), (2 * d) ** -0.5),
        'temp': nrm((c.num_key_value_heads,), 0.3, jnp.float32),
        'router': {
            'down': nrm((h, r), h ** -0.5, jnp.float32),
            'gamma': 0.25 + 0.5 * jax.random.uniform(next(keys), (),
                                                     jnp.float32),
            'norm': gain(r),
            'w1': nrm((r, r), (2.0 / r) ** 0.5, jnp.float32),
            'w2': nrm((r, r), (2.0 / r) ** 0.5, jnp.float32),
            'w3': nrm((r, e + 1), (4.0 / r) ** 0.5, jnp.float32),
            'bias': nrm((e + 1,), 0.02, jnp.float32)},
        'experts': {'gate': nrm((count, h, f), h ** -0.5),
                    'up': nrm((count, h, f), h ** -0.5),
                    'down': nrm((count, f, h), f ** -0.5)}}


@jax.jit
def _pack(lp):
    """A layer's leaves as the forward reads them: q, k, v1 and v2 side by
    side, one product."""
    lp = dict(lp)
    lp['qkv'] = jnp.concatenate([lp.pop(n) for n in ('q', 'k', 'v1', 'v2')],
                                axis=1)
    return lp


def stack_layers(config, layer_of):
    """``layer_of(l)`` -> layer ``l``'s weights (``init_layer``'s leaves) ->
    the stack as the scan takes it: every leaf ``[layers, ...]``. A layer
    is made, written into the stacks where they lie and dropped before the
    next: two copies of the whole never stand side by side (a layer is 0.4
    GB at the published sizes, the stack 8.3 GB)."""
    return _family.stack_layers(config.num_hidden_layers,
                                lambda l: _pack(layer_of(l)))


def init_params(config, key):
    """{'embed' [V, H] (the head too), 'norm_f' [H], 'layers':
    ``stack_layers`` of ``init_layer``}."""
    c = config
    k_embed, k_norm, k_layers = jax.random.split(key, 3)
    embed = (c.hidden_size ** -0.5 * jax.random.normal(
        k_embed, (c.vocab_size, c.hidden_size), jnp.float32)).astype(
            c.param_dtype)
    return {
        'embed': embed,
        'norm_f': 1.0 + 0.1 * jax.random.normal(
            k_norm, (c.hidden_size,), jnp.float32),
        'layers': stack_layers(c, lambda l: init_layer(
            c, jax.random.fold_in(k_layers, l)))}


def serve_params(params, config):
    """The parameters as an engine holds them (models/family.py): the
    layers' matrices in the compute dtype, the router's in its own, every
    other leaf float32."""
    cdt, rdt = jnp.dtype(config.dtype), jnp.dtype(config.router_dtype)

    def walk(node, name='', inside=''):
        if isinstance(node, dict):
            return {k: walk(v, k, name) for k, v in node.items()}
        if inside == 'router':
            want = rdt if name in ROUTER_MATRICES else jnp.float32
        else:
            want = cdt if name in MATRICES else jnp.float32
        return node if node.dtype == want else node.astype(want)
    return walk(params)


def init_pool(config, num_units, page_size):
    """The pool (the module's text says what each plane is):
    ``num_units['kv']`` pages, page 0 the trash page, and
    ``num_units['tail']`` slots."""
    c, cdt = config, jnp.dtype(config.dtype)
    n = int(num_units['kv'])
    if n < 2:
        raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
    layers, slots = c.num_hidden_layers, int(num_units['tail'])
    kv = (layers, n, c.num_key_value_heads, page_size, c.head_dim)
    return {'k': jnp.zeros(kv, cdt), 'v': jnp.zeros(kv, cdt),
            'conv': jnp.zeros((layers, slots, 2 * c.conv_dim), cdt),
            'vtail': jnp.zeros((layers, slots, c.head_dim), cdt)}


# ---- the layers ------------------------------------------------------------

def _rope(x, positions, config):
    """x [B, T, heads, d] float32 at ``positions`` [B, T]: the first
    ``partial_rotary_factor`` of d rotated, pairs half-split inside it."""
    d = x.shape[-1]
    rot = int(d * config.partial_rotary_factor)
    inv = float(config.rope_theta) ** (
        -2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    ang = positions[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = jnp.split(x, [rot // 2, rot], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _unit(x, eps):
    """x L2-normalised over its last axis, times the root of its width."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _before(a):
    """Row t of the result is row t - 1 of ``a`` [B, T, ...]; row 0 zero."""
    return jnp.pad(a, ((0, 0), (1, 0)) + ((0, 0),) * (a.ndim - 2))[:, :-1]


def _rows_before(a, ends, n):
    """a [B, T, C] -> [B, n, C]: rows ``ends[b] - n .. ends[b] - 1``, zeros
    where they lie before row 0."""
    padded = jnp.pad(a, ((0, 0), (n, 0), (0, 0)))
    return jax.vmap(lambda p, e: jax.lax.dynamic_slice_in_dim(
        p, e, n, axis=0))(padded, ends)


def _cca(lp, u, pool, tails, index, pos_v, tables, valid, config):
    """Compressed convolutional attention over u [B, T, H] (normed). ->
    (out [B, T, H] float32, what the layer leaves). T > 1, a prefill from
    row 0: it leaves (K rows, V rows ``[B, T, kv heads, 128]``, the
    convolutions' tail ``[B, 2 C]``, the value's ``[B, 128]``) for the
    caller to write. T == 1, a decode step at rows pos_v[b]: ``pool`` is
    the K and V planes carried flat ``[layers * pages, ...]`` with this
    layer the ``index``-th (traced: the scan's) and ``tails`` THIS layer's
    ``conv`` ``[slots, 2 C]`` and ``vtail`` ``[slots, 128]``; the slots'
    tails are read and rewritten, the row written to its page, the paged
    kernel attends, and it leaves (the planes, the layer's tails)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = u.shape
    nh, nkv, d, cd = (c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim, c.conv_dim)
    group, eps = nh // nkv, c.rms_norm_eps
    no_bias = jnp.zeros((cd,), jnp.float32)
    with jax.named_scope('proj'):
        qk, v1, v2 = jnp.split(_dot(u, lp['qkv'], cdt).astype(cdt),
                               [cd, cd + d], axis=-1)
    with jax.named_scope('conv'):
        if t > 1:
            ends = (jnp.full((b,), t, jnp.int32) if valid is None
                    else valid.astype(jnp.int32))
            c0 = _rec.causal_conv(qk, lp['conv0'], no_bias)[0].astype(cdt)
            c0_before = _before(c0)
            v = jnp.stack([v1, _before(v2)], axis=2)
            tails = (_rows_before(qk, ends, 2).reshape(b, 2 * cd),
                     _rows_before(v2, ends, 1)[:, 0])
        else:
            slots = tables['tail'].astype(jnp.int32)
            tail = tails['conv'][slots].reshape(b, 2, cd)
            # the depthwise convolution's rows t - 1 and t
            c0_before = _rec.conv_step(tail[:, :1], tail[:, 1], lp['conv0'],
                                       no_bias)[0].astype(cdt)[:, None]
            c0 = _rec.conv_step(tail[:, 1:], qk[:, 0], lp['conv0'],
                                no_bias)[0].astype(cdt)[:, None]
            v = jnp.stack([v1, tails['vtail'][slots][:, None]], axis=2)
            tails = {
                'conv': tails['conv'].at[slots].set(jnp.concatenate(
                    [tail[:, 1], qk[:, 0]], axis=-1)),
                'vtail': tails['vtail'].at[slots].set(v2[:, 0])}
        by_head = lambda a: a.reshape(b, t, nh + nkv, d)
        mix = lambda a, w: jnp.einsum(
            'btgc,gcd->btgd', by_head(a), w.astype(cdt),
            preferred_element_type=jnp.float32)
        c1 = mix(c0_before, lp['conv1'][0]) + mix(c0, lp['conv1'][1])
    with jax.named_scope('qk_mean_norm'):
        q_pre, k_pre = jnp.split(by_head(qk).astype(jnp.float32), [nh],
                                 axis=2)
        m = (q_pre + jnp.repeat(k_pre, group, axis=2)) / 2
        q = _unit(c1[:, :, :nh] + m, eps)
        k = (_unit(c1[:, :, nh:] + jnp.mean(
            m.reshape(b, t, nkv, group, d), axis=3), eps)
            * jnp.exp(lp['temp'])[:, None])
    with jax.named_scope('rope'):
        positions = pos_v[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = _rope(q, positions, c).astype(cdt)
        k = _rope(k, positions, c).astype(cdt)
    with jax.named_scope('attend'):
        if t > 1:
            o = flash_attention(q, k, v, causal=True)
            left = (k, v) + tails
        else:
            pages = pool['k'].shape[0] // c.num_hidden_layers
            table = tables['kv'] + (index * pages).astype(jnp.int32)
            with jax.named_scope('page_write'):
                planes = [paged_write(pool[n], rows_, table, pos_v)
                          for n, rows_ in (('k', k), ('v', v))]
            o = paged_attention(q, planes[0], planes[1], table, pos_v, cdt)
            left = ({'k': planes[0], 'v': planes[1]}, tails)
    with jax.named_scope('out_proj'):
        return _dot(o.reshape(b, t, nh * d), lp['o'], cdt), left


def _router(rp, u, r_above, config):
    """u [T, H] float32 (normed, not yet rounded to the compute dtype) ->
    (chosen [T] int32 in 0..E (E: skip), its probability [T] float32, the
    router's state [T, R] float32 for the layer below). ``router_dtype``
    from ``W_down`` to the probabilities: float32 products at the highest
    precision, whatever the layer computes in."""
    rdt = jnp.dtype(config.router_dtype)

    def dot(a, w):
        return jnp.dot(a.astype(rdt), w.astype(rdt),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32).astype(rdt)
    gelu = lambda a: jax.nn.gelu(a, approximate=True)
    r = (dot(u, rp['down']) + (rp['gamma'] * r_above).astype(rdt)).astype(
        jnp.float32)
    s = _rms(r, rp['norm'], config.rms_norm_eps)
    p = jax.nn.softmax(dot(gelu(dot(gelu(dot(s, rp['w1'])), rp['w2'])),
                           rp['w3']).astype(jnp.float32), axis=-1)
    chosen = jnp.argmax(p + rp['bias'], axis=-1).astype(jnp.int32)
    return chosen, jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0], r


def _expert_half(lp, experts, u, r_above, row_ok, index, noted, config):
    """The expert half over u [T, H] float32 (normed). -> (y [T, H] in the
    compute dtype, the router's state, counts: ``routed_experts.COUNTS``
    then rows skipped, the router's note on the rows ``noted`` [B]:
    ``NOTE``)."""
    c, cdt = config, jnp.dtype(config.dtype)
    with jax.named_scope('router'):
        chosen, p, r = _router(lp['router'], u, r_above, c)
        note = jnp.concatenate(
            [jnp.take(a.astype(jnp.float32).reshape(a.shape[0], -1), noted,
                      axis=0) for a in (u, r, p, chosen)], axis=-1)
    u = u.astype(cdt)
    y, counts = _re.held_experts(
        {'experts': experts}, u, row_ok, chosen[:, None], p[:, None],
        held=c.held, at=index)
    with jax.named_scope('skip'):
        skip = (chosen == c.num_experts) & row_ok
        y = jnp.where(skip[:, None],
                      (p[:, None] * u.astype(jnp.float32)).astype(cdt), y)
    return y, r, jnp.concatenate(
        [counts, jnp.sum(skip.astype(jnp.int32))[None]]), note


def _merge(vectors, x, f):
    a_r, b_r, a_f, b_f = vectors
    return (a_r * x.astype(jnp.float32) + b_r) + (a_f * f + b_f)


def _layer(lp, experts, x, r, pool, tails, index, pos_v, tables, valid,
           row_ok, config):
    """One layer over [B, T, H], the ``index``-th. -> (x, the router's
    state, what its attention leaves, counts, the router's note on each
    sequence's last valid row)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, h = x.shape
    last = (jnp.full((b,), t - 1, jnp.int32) if valid is None else
            jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1))
    with jax.named_scope('zaya.block'):
        with jax.named_scope('attn'):
            a, left = _cca(
                lp, _rms(x, lp['norm_attn'], c.rms_norm_eps).astype(cdt),
                pool, tails, index, pos_v, tables, valid, c)
            x = _merge(lp['merge_attn'], x, a).astype(cdt)
        with jax.named_scope('moe'):
            y, r, counts, note = _expert_half(
                lp, experts,
                _rms(x, lp['norm_moe'], c.rms_norm_eps).reshape(b * t, h),
                r.reshape(b * t, -1), row_ok.reshape(b * t), index,
                jnp.arange(b, dtype=jnp.int32) * t + last, c)
            x = _merge(lp['merge_moe'], x,
                       y.reshape(b, t, h).astype(jnp.float32)).astype(cdt)
    return x, r.reshape(b, t, -1), left, counts, note


def _decoder(params, tokens, pool, pos_v, tables, valid, config, last_only):
    """The layers and the head over [B, T] tokens. -> (logits, the pool
    (T == 1: every layer's rows updated) or what the layers left (T > 1:
    ``{'k', 'v', 'conv', 'vtail'}``, each ``[layers, B, ...]``, for
    ``_write_prefill``), counts [6]: ``routed_experts.COUNTS`` over the
    layers (the largest group's rows: the largest of any layer) and the
    rows that skipped)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t = tokens.shape
    n = c.num_hidden_layers
    row_ok = (jnp.ones((b, t), bool) if valid is None else
              jnp.arange(t)[None, :] < valid.astype(jnp.int32)[:, None])
    x = jnp.take(params['embed'], tokens, axis=0).astype(cdt)
    r = jnp.zeros((b, t, c.router_hidden_size), jnp.float32)
    layers = dict(params['layers'])
    # the experts' stacks whole, [layers * held, ...]: a view; the scan
    # slices every other leaf, and a product reads its slice where it lies
    experts = {k: a.reshape((-1,) + a.shape[2:])
               for k, a in layers.pop('experts').items()}
    # a decode step carries the K and V planes flat, [layers * pages,
    # ...]: views; a layer's rows are reached through an offset and written
    # in place. The tails, 5 MB of all slots' at the published sizes, are
    # scanned: a layer takes its own and leaves them rewritten (carried
    # flat the compiler moved the whole of them to fast memory and back in
    # every layer). A prefill reads no pool: its layers leave what they made
    flat = tails = None
    if t == 1:
        flat = {k: pool[k].reshape((-1,) + pool[k].shape[2:]) for k in 'kv'}
        tails = {k: pool[k] for k in ('conv', 'vtail')}

    def one_layer(carry, step):
        x, r, flat = carry
        lp, tails, index = step
        x, r, left, counts, note = _layer(
            lp, experts, x, r, flat, tails, index, pos_v, tables, valid,
            row_ok, c)
        if t == 1:
            return (x, r, left[0]), (left[1], counts, note)
        return (x, r, flat), (left, counts, note)
    (x, _, flat), (left, counts, notes) = jax.lax.scan(
        one_layer, (x, r, flat),
        (layers, tails, jnp.arange(n, dtype=jnp.int32)))
    if t == 1:
        left = dict(left, **{k: flat[k].reshape(pool[k].shape) for k in 'kv'})
    else:
        left = dict(zip(('k', 'v', 'conv', 'vtail'), left))
    if last_only:
        if valid is not None:
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope('zaya.head'):
        y = _rms(x, params['norm_f'], c.rms_norm_eps).astype(cdt)
        logits = jnp.einsum('bth,vh->btv', y, params['embed'].astype(cdt),
                            preferred_element_type=jnp.float32).astype(cdt)
    k = len(_re.COUNTS) - 1                 # all summed but the largest
    counts = jnp.concatenate([
        jnp.sum(counts[:, :k], axis=0), jnp.max(counts[:, k:k + 1], axis=0),
        jnp.sum(counts[:, k + 1:], axis=0)])
    return logits, left, counts, jnp.swapaxes(notes, 0, 1).reshape(b, -1)


def _write_prefill(pool, left, pos_v, tables, valid):
    """What a prefill's layers left, into the pool: every layer's tails
    over its sequence's slot's row (what the last occupant left there is
    never read), every layer's K and V rows to the pages of its table. One
    write after the layers' scan: a prefill's layers read no pool, so none
    is carried through them."""
    pool = dict(pool)
    n_layers, n_slots = pool['conv'].shape[:2]
    rows = (jnp.arange(n_layers, dtype=jnp.int32)[:, None] * n_slots
            + tables['tail'].astype(jnp.int32)[None, :]).reshape(-1)
    for name in ('conv', 'vtail'):
        plane, fresh = pool[name], left[name]
        flat = plane.reshape((-1,) + plane.shape[2:]).at[rows].set(
            fresh.reshape((-1,) + fresh.shape[2:]).astype(plane.dtype))
        pool[name] = flat.reshape(plane.shape)
    pages = pool['k'].shape[1]
    for name in 'kv':
        plane = pool[name]
        flat = plane.reshape((-1,) + plane.shape[2:])
        for layer in range(n_layers):
            flat = paged_write(flat, left[name][layer],
                               tables['kv'] + jnp.int32(layer * pages),
                               pos_v, valid)
        pool[name] = flat.reshape(plane.shape)
    return pool


def forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None):
    """[B, T] tokens at rows pos[b].. over the cache (``cache``: the pool's
    planes; 'page_table' ``{'kv': [B, P_max], 'tail': [B] slots}``; for a
    prefill 'valid' [B]) -> (logits, cache). T > 1 is a prefill from row 0
    (whatever ``pos`` says: the convolutions have no tail to start from, so
    the family declines a prefix cache); T == 1 a decode step. The cache
    that comes back holds 'counts': ``routed_experts.COUNTS`` then
    ``OWN_COUNTS``, and 'row_notes' (``NOTE``). Rows past ``valid`` are padding at any ``T``: how wide
    a prompt is padded is the engine's choice (``family.prefill_widths``)."""
    del partitioner     # one chip: no rules table for this family
    b, t = tokens.shape
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if t > 1:
        pos_v = jnp.zeros_like(pos_v)
    tables, valid = cache['page_table'], cache.get('valid')
    planes = {n: cache[n] for n in ('k', 'v', 'conv', 'vtail')}
    logits, left, counts, notes = _decoder(
        params, tokens, planes, pos_v, tables, valid, config, last_only)
    keys = jnp.sum(pos_v + 1) if t == 1 else jnp.int32(0)
    counts = jnp.concatenate([counts, keys[None]]).astype(jnp.int32)
    if t > 1:
        with jax.named_scope('zaya.page_write'):
            left = _write_prefill(planes, left, pos_v, tables, valid)
    return logits, dict(cache, **left, counts=counts, row_notes=notes)


def forward(params, tokens, config):
    """[B, T] tokens -> [B, T, V] logits: a prefill over a throwaway pool
    of just these rows (tests and small checks; serving goes through
    ``GenerationEngine``)."""
    b, t = tokens.shape
    cache = dict(
        init_pool(config, {'kv': b + 1, 'tail': b}, t),
        page_table={'kv': jnp.arange(1, b + 1, dtype=jnp.int32)[:, None],
                    'tail': jnp.arange(b, dtype=jnp.int32)})
    return forward_with_cache(params, tokens, cache,
                              jnp.zeros((b,), jnp.int32), config)[0]


def note_counts(counts, phase):
    """A call's counts to the ``moe.*`` counters (``rows_skipped_total``
    beside the routed layer's own) and, of a decode step, to
    ``attn.keys_attended_total`` (the engine calls this with what
    ``forward_with_cache`` counted)."""
    labels = {'phase': phase}
    vals = dict(zip(_re.COUNTS + OWN_COUNTS, (int(x) for x in counts)))
    for name in _re.COUNTS[:4] + OWN_COUNTS[:1]:
        _obs.counter(f'moe.{name}_total', labels=labels).inc(vals[name])
    _obs.histogram('moe.group_rows_max', labels=labels).observe(
        vals['group_rows_max'])
    if phase == 'decode':
        _obs.counter('attn.keys_attended_total',
                     labels={'kind': 'kv'}).inc(vals['keys_attended'])


_family.register(ZayaConfig, _family.GenerationFamily(
    name='zaya', init_pool=init_pool,
    forward_with_cache=forward_with_cache, serve_params=serve_params,
    note_counts=note_counts, tail_prefill=False, page_kinds=page_kinds))
