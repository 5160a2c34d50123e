"""Decoder family of power-retention layers (Manifest AI's ``brumby``, as
Brumby-14B-Base has it: a Qwen3-14B decoder retrained with every layer's
softmax attention replaced by power retention of degree 2). No layer keeps
a K or V row: what a sequence leaves behind is, a KV head a layer, a matrix
state ``S`` and a normaliser ``z`` of one size whatever its length
(ops/retention.py holds the recurrence and says how they are laid).

    h   = x + W_o Ret(n(x))           out = h + W_down(silu(W_gate m) * (W_up m)),  m = n(h)
    q_t = rope(qnorm(W_q n_t))        query heads of ``head_dim``
    k_t = rope(knorm(W_k n_t))        KV heads; qnorm, knorm: RMSNorm over a head, a gain a channel
    v_t = W_v n_t
    l_t = logsigmoid(W_g n_t + b_g)   one value a KV head: a token's log decay
    Ret: a query head of KV head j's group reads j's state,
         y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)
    logits = n_f(h) W_head            (the head is not the embedding)

``n`` is RMSNorm (eps ``rms_norm_eps``), rope is half-split over the whole
head (``rope_theta``), no projection has a bias but the gate's.

This is a SERVED family (models/family.py): a cached forward for
``serving.GenerationEngine``, no train step. Its pool has ONE kind of
plane and that kind is a row a SLOT (``per_slot``): the engine that serves
it holds no page, no allocator and no page table, and tells a call which
slots its sequences are (``page_table['state']``: [B] int32).

    s  [layers, slots, kv heads, head_dim, Dp]  ``state_dtype`` (float32)
    z  [layers, slots, kv heads, Dp]
    Dp = (head_dim / 2 + 1) head_dim: 8,320 at 128 (ops/retention.phi)

At the published widths a slot's row of a layer is 8 x (128 + 1) x 8,320 x
4 B = 34.3 MB. A prefill runs the chunked form from a zero state over its
padded prompt (``l = 0`` and ``k = 0`` past ``valid``) and writes the state
after its last real row over its slot's row; a decode step updates every
slot's row once, in place, and reads it once (``retention.state_update``).
Every layer is alike and the stack is a ``lax.scan`` over the layers.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..ops import retention as _ret
from ..ops.dense import dot as _dot, rms as _rms
from . import family as _family
from .afmoe import _rope

# leaves that are the right-hand operand of a product (held in the compute
# dtype); every other leaf is small and read in float32
MATRICES = ('embed', 'head', 'qkvg', 'o', 'gate_up', 'down')
COUNTS = ('state_rows', 'chunks')       # what a call counts, in order
# a head's memory, 1 / (1 - g) tokens, at the gate's bias alone
MEMORY = (16.0, 4096.0)


@dataclasses.dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    dtype: str = 'bfloat16'
    param_dtype: str = 'bfloat16'
    state_dtype: str = 'float32'            # the retention state's

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('num_key_value_heads must divide '
                             'num_attention_heads')
        if self.head_dim % 2:
            raise ValueError('head_dim must be even (rotary pairs, and '
                             'ops/retention.phi lays features by diagonal)')

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def group(self):
        """Query heads that read one KV head's state."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def widths(self):
        """(q, k, v, gate) columns of the layer's one input projection."""
        kv = self.num_key_value_heads * self.head_dim
        return (self.num_attention_heads * self.head_dim, kv, kv,
                self.num_key_value_heads)


def page_kinds(config):
    """The one kind of plane (models/family.py): a row a slot."""
    del config
    return (_family.PageKind('state', per_slot=True, planes=('s', 'z')),)


# ---- weights ---------------------------------------------------------------

def init_layer(config, key):
    """One layer's random weights. The matrices N(0, 1/fan_in) in
    ``param_dtype`` (q, k, v and the gate side by side: one product; the
    MLP's gate and up likewise); gains 1 + 0.1 N(0, 1) and the gate's bias
    float32. The bias is drawn so that a head's memory ``1 / (1 - g)`` is
    log-uniform in ``MEMORY`` tokens: with none, ``W_g n ~ N(0, 1)`` forgets
    everything in two tokens."""
    c, pdt = config, jnp.dtype(config.param_dtype)
    h, f, d = c.hidden_size, c.intermediate_size, c.head_dim
    nq = c.num_attention_heads * d
    keys = iter(jax.random.split(key, 12))

    def nrm(shape, fan_in):
        return (fan_in ** -0.5 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(pdt)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
    memory = jnp.exp(jax.random.uniform(
        next(keys), (c.num_key_value_heads,), jnp.float32,
        math.log(MEMORY[0]), math.log(MEMORY[1])))
    return {'norm_in': gain(h), 'norm_mlp': gain(h),
            'qkvg': nrm((h, sum(c.widths)), h), 'o': nrm((nq, h), nq),
            'q_norm': gain(d), 'k_norm': gain(d),
            'gate_bias': jnp.log(memory - 1.0),     # logit(1 - 1 / memory)
            'gate_up': nrm((h, 2 * f), h), 'down': nrm((f, h), f)}


def init_params(config, key):
    """{'embed' [V, H], 'head' [H, V], 'norm_f' [H], 'layers':
    ``family.stack_layers`` of ``init_layer``: every leaf ``[layers,
    ...]`` as the scan takes it, a layer (0.66 GB at the published sizes)
    made and laid before the next}."""
    c = config
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def nrm(k, shape):
        return (c.hidden_size ** -0.5 * jax.random.normal(
            k, shape, jnp.float32)).astype(c.param_dtype)
    return {'embed': nrm(k_embed, (c.vocab_size, c.hidden_size)),
            'head': nrm(k_head, (c.hidden_size, c.vocab_size)),
            'norm_f': 1.0 + 0.1 * jax.random.normal(
                k_norm, (c.hidden_size,), jnp.float32),
            'layers': _family.stack_layers(
                c.num_hidden_layers, lambda l: init_layer(
                    c, jax.random.fold_in(k_layers, l)))}


def serve_params(params, config):
    """The parameters as an engine holds them (models/family.py): the
    matrices in the compute dtype, everything else float32."""
    cdt = jnp.dtype(config.dtype)

    def walk(node, name=''):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        want = cdt if name in MATRICES else jnp.float32
        return node if node.dtype == want else node.astype(want)
    return walk(params)


def init_pool(config, num_units, page_size):
    """The pool (the module's text says what each plane is and its bytes):
    ``num_units['state']`` slots. ``page_size`` is no part of it."""
    del page_size
    c, sdt = config, jnp.dtype(config.state_dtype)
    rows = (c.num_hidden_layers, int(num_units['state']),
            c.num_key_value_heads)
    dp = _ret.features(c.head_dim)[1]
    return {'s': jnp.zeros(rows + (c.head_dim, dp), sdt),
            'z': jnp.zeros(rows + (dp,), sdt)}


# ---- the layers ------------------------------------------------------------

def _mlp(lp, x, config):
    c, cdt = config, jnp.dtype(config.dtype)
    y = _rms(x, lp['norm_mlp'], c.rms_norm_eps).astype(cdt)
    g, u = jnp.split(_dot(y, lp['gate_up'], cdt), 2, axis=-1)
    return _dot((jax.nn.silu(g) * u).astype(cdt), lp['down'], cdt)


def _retention(lp, u, pool, index, pos_v, slots, valid, config):
    """The retention half over u [B, T, H] (normed). -> (out [B, T, H]
    float32, what the layer leaves). T > 1: from a zero state at rows 0..,
    and it leaves (s, z) after row ``valid - 1`` as the pool holds them for
    the caller to write; T == 1: one step at rows pos_v[b] of the pool's
    rows ``slots`` of layer ``index`` (the planes carried flat, [layers *
    slots, ...]), and the planes are what it leaves."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = u.shape
    nh, nkv, d, grp = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim, c.group)
    with jax.named_scope('proj'):
        q, k, v, gate = jnp.split(
            _dot(u, lp['qkvg'], cdt),
            [sum(c.widths[:n]) for n in (1, 2, 3)], axis=-1)
    with jax.named_scope('qk_norm'):
        q = _rms(q.reshape(b, t, nh, d), lp['q_norm'], c.rms_norm_eps)
        k = _rms(k.reshape(b, t, nkv, d), lp['k_norm'], c.rms_norm_eps)
    with jax.named_scope('rope'):
        positions = pos_v[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = _rope(q, positions, float(c.rope_theta)).reshape(
            b, t, nkv, grp, d)
        k = _rope(k, positions, float(c.rope_theta))
    v = v.reshape(b, t, nkv, d)
    with jax.named_scope('gate'):
        l = jax.nn.log_sigmoid(gate + lp['gate_bias'])      # [B, T, kv]
    if t > 1:
        if valid is not None:
            real = (jnp.arange(t)[None, :]
                    < valid.astype(jnp.int32)[:, None])[..., None]
            l = jnp.where(real, l, 0.0)
            k = jnp.where(real[..., None], k, 0.0)
        with jax.named_scope('chunked'):
            # one chunk up to ``CHUNK`` rows, whole chunks past it
            chunk = min(t, _ret.CHUNK)
            pad = -t % chunk
            padded = lambda x: jnp.pad(
                x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            y, s, z = _ret.chunked_retention(
                padded(q), padded(k), padded(v), padded(l), cdt, chunk)
            y = y[:, :t]
        left = (s, z)
    else:
        n_slots = pool['s'].shape[0] // c.num_hidden_layers
        rows = (index * n_slots).astype(jnp.int32) + slots.astype(jnp.int32)
        with jax.named_scope('phi'):
            pk, pq = _ret.phi(k[:, 0]), _ret.phi(q[:, 0])
        with jax.named_scope('state_update'):
            y, s, z = _ret.state_update(
                pool['s'], pool['z'], rows, jnp.exp(l[:, 0]), pk, pq,
                v[:, 0])
            y = y[:, None]
        left = {'s': s, 'z': z}
    with jax.named_scope('out_proj'):
        return _dot(y.reshape(b, t, nh * d).astype(cdt), lp['o'], cdt), left


def _layer(lp, x, pool, index, pos_v, slots, valid, config):
    """One layer, the ``index``-th, over [B, T, H]. -> (x, what its
    retention leaves: a prefill's fresh state or a decode step's planes)."""
    c, cdt = config, jnp.dtype(config.dtype)
    with jax.named_scope('brumby.block'):
        u = _rms(x, lp['norm_in'], c.rms_norm_eps).astype(cdt)
        with jax.named_scope('retention'):
            out, left = _retention(lp, u, pool, index, pos_v, slots, valid, c)
        x = (x.astype(jnp.float32) + out).astype(cdt)
        with jax.named_scope('mlp'):
            x = (x.astype(jnp.float32) + _mlp(lp, x, c)).astype(cdt)
    return x, left


def _decoder(params, tokens, pool, pos_v, slots, valid, config, last_only):
    """The layers and the head over [B, T] tokens. -> (logits, the planes
    (T == 1: every layer's rows updated) or what the layers left (T > 1:
    ``{'s', 'z'}``, each ``[layers, B, ...]``, for ``_write_prefill``),
    counts [2] in the order of ``COUNTS``: one layer's worth)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t = tokens.shape
    n = c.num_hidden_layers
    x = jnp.take(params['embed'], tokens, axis=0).astype(cdt)
    # a decode step carries the planes flat, [layers * slots, ...]: views;
    # a layer's rows are reached through an offset and updated in place. A
    # prefill reads no pool: its layers leave what they made
    flat = ({k: a.reshape((-1,) + a.shape[2:]) for k, a in pool.items()}
            if t == 1 else None)

    def one_layer(carry, step):
        x, flat = carry
        lp, index = step
        x, left = _layer(lp, x, flat, index, pos_v, slots, valid, c)
        return ((x, left), None) if t == 1 else ((x, flat), left)
    (x, flat), left = jax.lax.scan(
        one_layer, (x, flat),
        (params['layers'], jnp.arange(n, dtype=jnp.int32)))
    if t == 1:
        left = {k: flat[k].reshape(pool[k].shape) for k in pool}
    else:
        left = dict(zip(('s', 'z'), left))
    if last_only:
        if valid is not None:
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope('brumby.head'):
        y = _rms(x, params['norm_f'], c.rms_norm_eps).astype(cdt)
        logits = _dot(y, params['head'], cdt).astype(cdt)
    # one layer's worth: the rows its recurrence served (a prefill's real
    # rows) and the chunks a prefill ran, padding too
    rows = (jnp.sum(valid.astype(jnp.int32)) if t > 1 and valid is not None
            else jnp.int32(b * t))
    chunks = b * -(-t // min(t, _ret.CHUNK)) if t > 1 else 0
    return logits, left, jnp.stack([rows, jnp.int32(chunks)])


def _write_prefill(pool, left, slots):
    """What a prefill's layers left, over their sequences' slots' rows
    (what the last occupant left there is never read). One write after the
    layers' scan: a prefill's layers read no pool, so none is carried
    through them."""
    slots = slots.astype(jnp.int32)
    return {name: plane.at[:, slots].set(left[name].astype(plane.dtype))
            for name, plane in pool.items()}


def forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None):
    """[B, T] tokens at rows pos[b].. over the cache (``cache``: the pool's
    two planes; 'page_table' ``{'state': [B] slots}``; for a prefill
    'valid' [B]) -> (logits, cache). T > 1 is a prefill from row 0
    (whatever ``pos`` says: a state has no rows to start a tail from, so
    the family declines a prefix cache); T == 1 a decode step. The cache
    that comes back holds 'counts' in the order of ``COUNTS``. Rows past
    ``valid`` are padding at any ``T``: how wide a prompt is padded is the
    engine's choice (``family.prefill_widths``)."""
    del partitioner     # one chip: no rules table for this family
    b, t = tokens.shape
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if t > 1:
        pos_v = jnp.zeros_like(pos_v)
    slots, valid = cache['page_table']['state'], cache.get('valid')
    planes = {n: cache[n] for n in ('s', 'z')}
    logits, left, counts = _decoder(
        params, tokens, planes, pos_v, slots, valid, config, last_only)
    if t > 1:
        left = _write_prefill(planes, left, slots)
    return logits, dict(cache, **left, counts=counts.astype(jnp.int32))


def forward(params, tokens, config):
    """[B, T] tokens -> [B, T, V] logits: a prefill over a throwaway pool
    of just these sequences (tests and small checks; serving goes through
    ``GenerationEngine``)."""
    b, t = tokens.shape
    cache = dict(init_pool(config, {'state': b}, t),
                 page_table={'state': jnp.arange(b, dtype=jnp.int32)})
    return forward_with_cache(params, tokens, cache,
                              jnp.zeros((b,), jnp.int32), config)[0]


def note_counts(counts, phase):
    """A call's counts to the ``retention.*`` counters (the engine calls
    this with what ``forward_with_cache`` counted): rows the recurrence
    served, one layer's worth (a decode step counts every slot), and the
    chunks a prefill ran."""
    vals = dict(zip(COUNTS, (int(x) for x in counts)))
    labels = {'phase': phase}
    _obs.counter('retention.state_rows_total', labels=labels).inc(
        vals['state_rows'])
    if phase == 'prefill':
        _obs.counter('retention.chunks_total', labels=labels).inc(
            vals['chunks'])


_family.register(BrumbyConfig, _family.GenerationFamily(
    name='brumby', init_pool=init_pool,
    forward_with_cache=forward_with_cache, serve_params=serve_params,
    note_counts=note_counts, tail_prefill=False, page_kinds=page_kinds))
