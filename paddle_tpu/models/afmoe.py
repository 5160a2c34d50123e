"""Decoder family with window and full attention in one stack and routed
expert layers (arcee-ai's ``afmoe`` block, as Trinity-Large-Preview has it):
RMSNorm before AND after each half of a layer (a sandwich), grouped-query
attention with an RMSNorm a head on q and k and a sigmoid gate on its
output, rotary positions on the window layers only (a full layer carries
no positional encoding), leading dense SwiGLU layers, then layers of
routed experts with one shared expert (parallel/routed_experts.py: sigmoid
scores, a bias that moves the choice, weights normalised and scaled), an
embedding scaled by sqrt(hidden) and an untied head.

This is a SERVED family: weights in ``param_dtype`` (bfloat16), a cached
forward for ``serving.GenerationEngine`` (models/family.py), no train step.
Its pool has TWO KINDS of plane (``page_kinds``): ``k_full`` / ``v_full``
``[full layers, pages, kv heads, page_size, head_dim]``, whose pages a slot
keeps for its whole life, and ``k_window`` / ``v_window`` over the window
layers, of which a slot holds only the pages its last ``sliding_window``
rows span; the engine keeps a page table a kind and gives back what left
the window. A prefill runs causal (and windowed) flash attention over its
fresh rows and writes them to the pages it was given (rows before a window
kind's first page go to the trash page); a decode step runs the paged
kernel, the window layers' call from the window's first page on
(ops/paged_attention.py). A prefill starts at row 0 (``tail_prefill=False``).

``held = (first, count)`` says which routed experts' weights are here; the
router scores all ``num_experts``. Layers are a list, not a stacked scan
(two kinds of attention, two of MLP); each kind's planes are carried whole
through the layers and updated in place (models/latent_moe.py says why).

Rotary dims pair half-split ([x1 | x2]); a checkpoint that interleaves
them loads with those columns of W_q and W_k permuted.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..ops.dense import dot as _dot, rms as _rms
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import paged_attention
from ..ops.paged_kv import paged_write
from ..parallel import routed_experts as _re
from . import family as _family

FULL, WINDOW = 'full_attention', 'sliding_attention'
KINDS = {FULL: 'full', WINDOW: 'window'}     # layer type -> page kind
# what a step counts beside the routed layers' (COUNTS of routed_experts):
# keys and pages a decode step's slots attended, by kind, one layer's worth
ATTENDED = ('keys_full', 'keys_window', 'pages_full', 'pages_window')


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # dense layers' SwiGLU
    moe_intermediate_size: int = 3072       # one expert's
    num_hidden_layers: int = 60
    num_dense_layers: int = 6               # leading dense layers
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    # a layer's kind; None: every ``global_attn_every_n_layers``-th is full
    layer_types: tuple = None
    global_attn_every_n_layers: int = 4
    num_experts: int = 256                  # the router's width
    num_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    route_scale: float = 2.448
    route_norm: bool = True
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    # (first, count) of the routed experts held here; None: all of them
    held: tuple = None
    dtype: str = 'bfloat16'
    param_dtype: str = 'bfloat16'

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else WINDOW
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - set(KINDS)):
            raise ValueError(
                f'layer_types must name {self.num_hidden_layers} layers, '
                f'each {FULL!r} or {WINDOW!r}: {self.layer_types}')
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(int(x) for x in self.held)
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f'held {self.held} outside the '
                             f'{self.num_experts} routed experts')
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('num_key_value_heads must divide '
                             'num_attention_heads')

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    def layers_of(self, kind):
        """The layers whose planes are of page kind ``kind``, in order."""
        return [i for i, t in enumerate(self.layer_types)
                if KINDS[t] == kind]


def page_kinds(config):
    """The kinds of plane this config's layers need (models/family.py)."""
    kinds = []
    for name, window in (('full', None), ('window', config.sliding_window)):
        if config.layers_of(name):
            kinds.append(_family.PageKind(
                name, window, planes=(f'k_{name}', f'v_{name}')))
    return tuple(kinds)


def _rope(x, positions, theta):
    """x [B, T, heads, d] float32 at ``positions`` [B, T], half-split."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# ---- weights ---------------------------------------------------------------

def init_params(config, key):
    """Random weights, one leaf at a time. The structure:

        embed [V, H], head [H, V], norm_f [H],
        layers: [{norm_in, norm_attn, norm_pre_mlp, norm_mlp [H],
                  q, gate [H, heads d], k, v [H, kv heads d], o [heads d, H],
                  q_norm, k_norm [d],  and  mlp {gate, up, down}  (dense)
                  or  router [E_all, H] f32, router_bias [E_all] f32,
                      experts {gate, up [held, H, F], down [held, F, H]},
                      shared {gate, up, down}}]

    The embedding's rows are N(0, 1/H), so that scaled by sqrt(H) they have
    unit variance beside the layers' normed outputs."""
    c, pdt = config, jnp.dtype(config.param_dtype)
    h, d = c.hidden_size, c.head_dim
    nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
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
        lp = {'norm_in': gain(h), 'norm_attn': gain(h),
              'norm_pre_mlp': gain(h), 'norm_mlp': gain(h),
              'q': nrm((h, nq), h), 'k': nrm((h, nkv), h),
              'v': nrm((h, nkv), h), 'gate': nrm((h, nq), h),
              'o': nrm((nq, h), nq), 'q_norm': gain(d), 'k_norm': gain(d)}
        if l < c.num_dense_layers:
            lp['mlp'] = swiglu(c.intermediate_size)
        else:
            lp['router'] = nrm((c.num_experts, h), h, jnp.float32)
            lp['router_bias'] = 0.002 * jax.random.normal(
                next(keys), (c.num_experts,), jnp.float32)
            lp['experts'] = swiglu(c.moe_intermediate_size, (c.held[1],))
            lp['shared'] = swiglu(c.moe_intermediate_size
                                  * c.num_shared_experts)
        layers.append(lp)
    return {'embed': nrm((c.vocab_size, h), h), 'norm_f': gain(h),
            'head': nrm((h, c.vocab_size), h), 'layers': layers}


def init_pool(config, num_pages, page_size):
    """The page pool: for each kind of ``page_kinds`` the planes
    ``k_<kind>`` and ``v_<kind>`` ``[layers of the kind, num_pages[kind],
    kv heads, page_size, head_dim]`` in the compute dtype, head-major pages
    (ops/paged_kv.py); page 0 of each kind is its trash page."""
    pool = {}
    for kind in page_kinds(config):
        n = int(num_pages[kind.name])
        if n < 2:
            raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
        shape = (len(config.layers_of(kind.name)), n,
                 config.num_key_value_heads, page_size, config.head_dim)
        for plane in 'kv':
            pool[f'{plane}_{kind.name}'] = jnp.zeros(
                shape, jnp.dtype(config.dtype))
    return pool


# ---- the layers ------------------------------------------------------------

def _attention(lp, x, planes, index, window, pos_v, page_table, valid,
               config):
    """x [B, T, H] (already normed) at rows pos_v[b].. -> (out [B, T, H]
    float32, the kind's (k, v) planes with the rows written). ``planes``
    are the kind's whole planes, this layer the ``index``-th of them;
    ``window`` None: a full layer, no positions."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = x.shape
    nh, nkv, d, eps = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim, c.rms_norm_eps)
    q = _rms(_dot(x, lp['q'], cdt).reshape(b, t, nh, d), lp['q_norm'], eps)
    k = _rms(_dot(x, lp['k'], cdt).reshape(b, t, nkv, d), lp['k_norm'], eps)
    v = _dot(x, lp['v'], cdt).reshape(b, t, nkv, d).astype(cdt)
    gate = jax.nn.sigmoid(_dot(x, lp['gate'], cdt))         # [B, T, nh d]
    if window is not None:
        positions = pos_v[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = _rope(q, positions, float(c.rope_theta))
        k = _rope(k, positions, float(c.rope_theta))
    q, k = q.astype(cdt), k.astype(cdt)

    # the kind's planes as [layers * pages, ...], this layer's pages through
    # its offset table: views, and writes in place into the donated pool
    n_layers, n = planes[0].shape[:2]
    table = page_table + jnp.int32(index * n)
    flat = [paged_write(p.reshape((n_layers * n,) + p.shape[2:]), rows,
                        table, pos_v, valid)
            for p, rows in zip(planes, (k, v))]
    if t > 1:
        # prefill from row 0: causal attention over the fresh rows
        o = flash_attention(q, k, v, causal=True, window=window)
    else:
        o = paged_attention(q, flat[0], flat[1], table, pos_v, cdt,
                            window=window)
    o = (o.reshape(b, t, nh * d).astype(jnp.float32) * gate).astype(cdt)
    return (_dot(o, lp['o'], cdt),
            tuple(f.reshape(p.shape) for f, p in zip(flat, planes)))


MLP_ROWS = 4096     # rows of a long prefill the MLP half takes at a time


def _mlp_half(lp, x, row_ok, config):
    """x + N_mlp(MLP(N_pre_mlp(x))) over [B, T, H] -> (x, counts or None).
    A prefill longer than ``MLP_ROWS`` goes through in pieces of that many
    rows (``lax.map``) and what is left over as a last, shorter piece, so
    that the widest intermediates (a dense layer's [T, 12288] float32
    pair, a routed layer's sorted rows) are a piece's and not the whole
    prompt's: rows do not meet in this half."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, h = x.shape

    def rows(x, row_ok):                       # [N, H], [N]
        y = _rms(x, lp['norm_pre_mlp'], c.rms_norm_eps).astype(cdt)
        if 'mlp' in lp:
            out, counts = _re.swiglu(lp['mlp'], y, cdt), None
        else:
            out, counts = _re.routed_experts(
                lp, y, row_ok, held=c.held, top_k=c.num_experts_per_tok,
                n_group=c.n_group, topk_group=c.topk_group,
                scale=c.route_scale, normalise=c.route_norm)
        x = (x.astype(jnp.float32)
             + _rms(out, lp['norm_mlp'], c.rms_norm_eps)).astype(cdt)
        return x, counts
    n = b * t
    x, row_ok = x.reshape(n, h), row_ok.reshape(n)
    if n <= MLP_ROWS:
        x, counts = rows(x, row_ok)
        return x.reshape(b, t, h), counts
    whole = n - n % MLP_ROWS
    out, counts = jax.lax.map(lambda a: rows(*a), (
        x[:whole].reshape(-1, MLP_ROWS, h),
        row_ok[:whole].reshape(-1, MLP_ROWS)))
    out = out.reshape(whole, h)
    if whole < n:
        last, last_counts = rows(x[whole:], row_ok[whole:])
        out = jnp.concatenate([out, last])
        if counts is not None:
            counts = jnp.concatenate([counts, last_counts[None]])
    if counts is not None:                     # [pieces, 5]
        counts = jnp.concatenate([jnp.sum(counts[:, :4], axis=0),
                                  jnp.max(counts[:, 4:], axis=0)])
    return out.reshape(b, t, h), counts


def _block(lp, x, pool, index, pos_v, tables, valid, row_ok, kind, config):
    """One layer over [B, T, H], the ``index``-th of its ``kind``. ->
    (x, pool, counts or None)."""
    c, cdt = config, jnp.dtype(config.dtype)
    window = c.sliding_window if kind == 'window' else None
    names = (f'k_{kind}', f'v_{kind}')
    with jax.named_scope('afmoe.block'):
        with jax.named_scope(f'attn_{kind}'):
            a, planes = _attention(
                lp, _rms(x, lp['norm_in'], c.rms_norm_eps).astype(cdt),
                tuple(pool[n] for n in names), index, window, pos_v,
                tables[kind], valid, c)
            pool = dict(pool, **dict(zip(names, planes)))
            x = (x.astype(jnp.float32)
                 + _rms(a, lp['norm_attn'], c.rms_norm_eps)).astype(cdt)
        with jax.named_scope('mlp' if 'mlp' in lp else 'moe'):
            x, counts = _mlp_half(lp, x, row_ok, c)
    return x, pool, counts


def _attended(pos_v, page_size, config):
    """[4] int32 in the order of ``ATTENDED``: what one full layer and one
    window layer of a decode step read, summed over the slots."""
    w = config.sliding_window
    last = pos_v // page_size
    return jnp.stack([
        jnp.sum(pos_v + 1), jnp.sum(jnp.minimum(pos_v + 1, w)),
        jnp.sum(last + 1),
        jnp.sum(last - jnp.maximum(pos_v - (w - 1), 0) // page_size + 1)])


def _decoder(params, tokens, pool, pos_v, tables, valid, config, last_only):
    """The layers and the head over [B, T] tokens. -> (logits, pool,
    counts [5] of the routed layers or None)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t = tokens.shape
    row_ok = (jnp.ones((b, t), bool) if valid is None else
              jnp.arange(t)[None, :] < valid.astype(jnp.int32)[:, None])
    x = jnp.take(params['embed'], tokens, axis=0).astype(jnp.float32)
    if c.mup_enabled:
        x = x * math.sqrt(c.hidden_size)
    x = x.astype(cdt)
    block = functools.partial(_block, config=c)
    if t > 1:
        # a prefill is traced and lowered once for every width the engine
        # may call it at: its layers of one kind and one MLP half (the
        # three window layers over experts, say) are traced once and
        # called, the layer's place among its kind an argument
        block = jax.jit(block, static_argnames=('kind',))
    counted = []
    for layer, lp in enumerate(params['layers']):
        kind = KINDS[c.layer_types[layer]]
        x, pool, counts = block(lp, x, pool, c.layers_of(kind).index(layer),
                                pos_v, tables, valid, row_ok, kind=kind)
        if counts is not None:
            counted.append(counts)
    if last_only:
        if valid is not None:
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope('afmoe.head'):
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
    """[B, T] tokens at rows pos[b].. over the paged cache (``cache``: the
    pool's planes, 'page_table' {kind: [B, P_max]}, and for a prefill
    'valid' [B]) -> (logits, cache). T > 1 is a prefill from row 0; T == 1
    a decode step. The cache that comes back holds 'counts': the routed
    layers' ``routed_experts.COUNTS`` summed over the layers (the largest
    group's rows: the largest of any layer; zeros in a model with no
    routed layer), then ``ATTENDED`` (zeros in a prefill). Rows past
    ``valid`` are padding at any ``T``: how wide a prompt is padded is the
    engine's choice (``family.prefill_widths``)."""
    del partitioner     # one chip: no rules table for this family
    b, t = tokens.shape
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    tables, valid = cache['page_table'], cache.get('valid')
    planes = {n: cache[n] for n in cache if n[:2] in ('k_', 'v_')}
    if t > 1 and valid is None:
        valid = jnp.full((b,), t, jnp.int32)
    logits, pool, counts = _decoder(
        params, tokens, planes, pos_v, tables, valid, config, last_only)
    counts = _no_counts(counts)
    page_size = next(iter(planes.values())).shape[3]
    attended = (_attended(pos_v, page_size, config) if t == 1
                else jnp.zeros((len(ATTENDED),), jnp.int32))
    return logits, dict(cache, **pool, counts=jnp.concatenate(
        [counts, attended.astype(jnp.int32)]))


def _no_counts(counts):
    return jnp.zeros((len(_re.COUNTS),), jnp.int32) if counts is None \
        else counts


def forward(params, tokens, config):
    """[B, T] tokens -> [B, T, V] logits: a prefill over a throwaway pool
    of just these rows (tests and small checks; serving goes through
    ``GenerationEngine``)."""
    b, t = tokens.shape
    pages = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    kinds = page_kinds(config)
    cache = dict(init_pool(config, {k.name: b + 1 for k in kinds}, t),
                 page_table={k.name: pages for k in kinds})
    return forward_with_cache(params, tokens, cache,
                              jnp.zeros((b,), jnp.int32), config)[0]


def note_counts(counts, phase):
    """A step's counts, to the ``moe.*`` counters and, of a decode step,
    to ``attn.keys_attended_total`` / ``attn.pages_attended_total`` by kind
    (the engine calls this with what ``forward_with_cache`` counted)."""
    labels = {'phase': phase}
    n = len(_re.COUNTS)
    vals = dict(zip(_re.COUNTS, (int(x) for x in counts[:n])))
    if vals['expert_calls']:
        for name in _re.COUNTS[:4]:
            _obs.counter(f'moe.{name}_total', labels=labels).inc(vals[name])
        _obs.histogram('moe.group_rows_max', labels=labels).observe(
            vals['group_rows_max'])
    if phase == 'decode':
        for name, x in zip(ATTENDED, counts[n:]):
            what, kind = name.split('_')
            _obs.counter(f'attn.{what}_attended_total',
                         labels={'kind': kind}).inc(int(x))


_family.register(AfmoeConfig, _family.GenerationFamily(
    name='afmoe', init_pool=init_pool,
    forward_with_cache=forward_with_cache, note_counts=note_counts,
    tail_prefill=False, page_kinds=page_kinds))
