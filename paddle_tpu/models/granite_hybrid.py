"""Decoder family of state-space (Mamba-2) layers with a few grouped-query
attention layers between them (IBM's ``granitemoehybrid`` block with no
routed experts, as granite-4.0-h-micro has it): nine layers in ten keep no
rows at all but a recurrent state a sequence, the same size whatever its
length; the tenth keeps K and V rows in pages and carries NO positional
encoding. Every layer's second half is a dense gated MLP. Scalars of the
family: the embedding is multiplied (``embedding_multiplier``), each half's
output enters the residual scaled (``residual_multiplier``), attention
scores are scaled by ``attention_multiplier`` (not ``head_dim ** -0.5``),
the logits divided by ``logits_scaling``; the head is the embedding.

    h = e_mult * E[ids]
    h = h + r_mult * mixer(RMSNorm(h));  h = h + r_mult * MLP(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling
    MLP(u) = W_out (silu(g) * v),  [g | v] = W_in u

*The state-space mixer* (ops/ssm.py holds the recurrence):
``[z | xBC | dt] = W_in u``; ``xBC = silu(conv1d(xBC))`` (depthwise, causal,
kernel ``d_conv``, bias); ``[x | B | C] = xBC``; ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; a head: ``S_t = exp(dt_t A) S_{t-1} + dt_t
x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z)) * w``
over all of ``d_inner`` (one group; the gate goes in BEFORE the norm);
``out = W_out y``.

This is a SERVED family (models/family.py): a cached forward for
``serving.GenerationEngine``, no train step. Its pool has TWO KINDS of
plane: ``k`` / ``v`` ``[attention layers, pages, kv heads, page_size,
head_dim]`` through the engine's allocator and page table as in every other
family (two KV heads of 64 side by side in a row of 128 lanes: ``_pack``),
and the per-slot kind ``state``: ``ssm`` ``[state-space layers, slots,
d_state, d_inner / 128, 128]`` in ``state_dtype`` (float32) and
``conv`` ``[state-space layers, slots, (d_conv - 1) * conv_dim]`` in the
compute dtype, a row a SLOT: the engine tells a call which slots its
sequences are (``page_table['state']``: [B] int32). A prefill runs the
chunked form of the recurrence from a zero state over its padded prompt
with ``dt = 0`` past ``valid`` and writes ``S_{valid-1}`` and the last
``d_conv - 1`` input rows before ``valid`` over its slot's row; a decode
step updates every slot's row once, in place (``ops/ssm.state_update``).

The layer pattern repeats (``layer_types`` is ``n`` copies of one PERIOD:
five state-space layers, one attention, four state-space, four times) and
the stack is a ``lax.scan`` over the periods: one period's body is
compiled, not every layer. So the weights are held a period POSITION at a
time, each leaf stacked over the periods (``stack_periods``).
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..ops import ssm as _rec
from ..ops.dense import dot as _dot, rms as _rms
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import paged_attention
from ..ops.paged_kv import paged_write
from . import family as _family

MAMBA, ATTENTION = 'mamba', 'attention'
# leaves that are the right-hand operand of a product (held in the compute
# dtype); every other leaf is small and read in float32
MATRICES = ('embed', 'in_proj', 'out_proj', 'mlp_in', 'mlp_out',
            'q', 'k', 'v', 'o')
COUNTS = ('state_rows', 'scan_chunks')     # what a call counts, in order


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192    # the dense MLP every layer has
    num_hidden_layers: int = 40
    # a layer's kind; None: ``attention`` at 5, 15, 25, ... of every ten
    layer_types: tuple = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: str = 'bfloat16'
    param_dtype: str = 'bfloat16'
    state_dtype: str = 'float32'            # the recurrent state's

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                ATTENTION if i % 10 == 5 else MAMBA
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {MAMBA, ATTENTION}):
            raise ValueError(
                f'layer_types must name {self.num_hidden_layers} layers, '
                f'each {MAMBA!r} or {ATTENTION!r}: {self.layer_types}')
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('num_key_value_heads must divide '
                             'num_attention_heads')
        if self.hidden_size % self.num_attention_heads:
            raise ValueError('num_attention_heads must divide hidden_size')
        if self.mamba_n_groups != 1:
            raise ValueError('one group of B and C is what is written')
        if self.d_inner != self.mamba_expand * self.hidden_size:
            raise ValueError('mamba_n_heads * mamba_d_head must be '
                             'mamba_expand * hidden_size')
        if self.d_inner % _rec.LANES:
            raise ValueError('the state pool holds 128 channels a lane row: '
                             'mamba_n_heads * mamba_d_head % 128 != 0')

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def period(self):
        """The shortest pattern that ``layer_types`` repeats: what the scan
        over the stack takes at a time."""
        types = self.layer_types
        for n in range(1, len(types) + 1):
            if len(types) % n == 0 and types == types[:n] * (len(types) // n):
                return types[:n]

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def page_kinds(config):
    """The kinds of plane this config's layers need (models/family.py): K
    and V pages for the attention layers, a row a slot for the others."""
    kinds = []
    if config.layers_of(ATTENTION):
        kinds.append(_family.PageKind('kv', planes=('k', 'v')))
    if config.layers_of(MAMBA):
        kinds.append(_family.PageKind('state', per_slot=True,
                                      planes=('ssm', 'conv')))
    return tuple(kinds)


# ---- weights ---------------------------------------------------------------

def init_layer(config, key, kind):
    """One layer's random weights. The matrices N(0, 1/fan_in) in
    ``param_dtype``; gains, the convolution and the recurrence's scalars
    float32: ``dt_bias`` the inverse softplus of a step drawn log-uniform in
    [1e-3, 0.1] and ``a_log = log U(1, 16)`` (the family's own
    initialisation: decays between nearly 1 and 0.2 a row)."""
    c, pdt = config, jnp.dtype(config.param_dtype)
    h, f = c.hidden_size, c.shared_intermediate_size
    keys = iter(jax.random.split(key, 16))

    def nrm(shape, fan_in, dtype=pdt):
        return (fan_in ** -0.5 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)

    lp = {'norm_in': gain(h), 'norm_mlp': gain(h),
          'mlp_in': nrm((h, 2 * f), h), 'mlp_out': nrm((f, h), f)}
    if kind == ATTENTION:
        nq = c.num_attention_heads * c.head_dim
        nkv = c.num_key_value_heads * c.head_dim
        lp.update(q=nrm((h, nq), h), k=nrm((h, nkv), h), v=nrm((h, nkv), h),
                  o=nrm((nq, h), nq))
        return lp
    nh, k = c.mamba_n_heads, c.mamba_d_conv
    dt = jnp.exp(jax.random.uniform(next(keys), (nh,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    lp.update(
        in_proj=nrm((h, c.d_inner + c.conv_dim + nh), h),
        conv_w=nrm((k, c.conv_dim), k, jnp.float32),
        conv_b=0.1 * jax.random.normal(next(keys), (c.conv_dim,),
                                       jnp.float32),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        a_log=jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32,
                                         1.0, 16.0)),
        d=jnp.ones((nh,), jnp.float32), norm_gate=gain(c.d_inner),
        out_proj=nrm((c.d_inner, h), c.d_inner))
    return lp


def stack_periods(config, layer_of):
    """``layer_of(l)`` -> layer ``l``'s weights; -> the stack as the scan
    takes it: a list over the period's positions, each leaf stacked over
    the periods (a position's layers are made, stacked and dropped before
    the next, so that two copies of the whole never stand side by side)."""
    n = len(config.period)
    out = []
    for j in range(n):
        layers = [layer_of(l)
                  for l in range(j, config.num_hidden_layers, n)]
        out.append(jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers))
        del layers
    return out


def init_params(config, key):
    """{'embed' [V, H] (the head too), 'norm_f' [H], 'periods':
    ``stack_periods`` of ``init_layer``}."""
    c = config
    k_embed, k_norm, k_layers = jax.random.split(key, 3)
    embed = (c.hidden_size ** -0.5 * jax.random.normal(
        k_embed, (c.vocab_size, c.hidden_size), jnp.float32)).astype(
            c.param_dtype)
    return {
        'embed': embed,
        'norm_f': 1.0 + 0.1 * jax.random.normal(
            k_norm, (c.hidden_size,), jnp.float32),
        'periods': stack_periods(c, lambda l: init_layer(
            c, jax.random.fold_in(k_layers, l), c.layer_types[l]))}


def serve_params(params, config):
    """The parameters as an engine holds them (models/family.py): the
    matrices in the compute dtype, everything else (gains, the
    convolution's weights, ``a_log``, ``d``, ``dt_bias``) float32."""
    cdt = jnp.dtype(config.dtype)

    def walk(node, name=''):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return [walk(v, name) for v in node]
        want = cdt if name in MATRICES else jnp.float32
        return node if node.dtype == want else node.astype(want)
    return walk(params)


def init_pool(config, num_units, page_size):
    """The pool (the module's text says what each plane is):
    ``num_units['kv']`` pages, page 0 the trash page, and
    ``num_units['state']`` slots."""
    c, cdt = config, jnp.dtype(config.dtype)
    pool = {}
    if config.layers_of(ATTENTION):
        n = int(num_units['kv'])
        if n < 2:
            raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
        pack = _pack(c)
        shape = (len(c.layers_of(ATTENTION)), n,
                 c.num_key_value_heads // pack, page_size, c.head_dim * pack)
        pool.update(k=jnp.zeros(shape, cdt), v=jnp.zeros(shape, cdt))
    if config.layers_of(MAMBA):
        layers, slots = len(c.layers_of(MAMBA)), int(num_units['state'])
        pool['ssm'] = jnp.zeros(
            (layers, slots, c.mamba_d_state, c.d_inner // _rec.LANES,
             _rec.LANES), jnp.dtype(c.state_dtype))
        pool['conv'] = jnp.zeros(
            (layers, slots, (c.mamba_d_conv - 1) * c.conv_dim), cdt)
    return pool


# ---- the layers ------------------------------------------------------------

def _mlp(lp, x, config):
    c, cdt = config, jnp.dtype(config.dtype)
    y = _rms(x, lp['norm_mlp'], c.rms_norm_eps).astype(cdt)
    g, v = jnp.split(_dot(y, lp['mlp_in'], cdt), 2, axis=-1)
    return _dot((jax.nn.silu(g) * v).astype(cdt), lp['mlp_out'], cdt)


def _pack(config):
    """KV heads that share a pool row: a head of 64 fills half the chip's
    128 lanes, and a pool whose rows are half empty is a pool XLA re-lays
    around every kernel call (the compiler's own choice for a described
    v5e). So as many neighbouring KV heads as fit 128 lanes, and divide the
    KV heads, lie side by side in one row, and the paged kernel sees
    ``kv heads / pack`` heads of ``head_dim * pack``."""
    pack = 1
    while (2 * pack * config.head_dim <= _rec.LANES
           and config.num_key_value_heads % (2 * pack) == 0):
        pack *= 2
    return pack


def _attention(lp, x, pool, index, pos_v, table, config):
    """x [B, T, H] (normed) -> (out [B, T, H] float32, what the layer
    leaves). T > 1, a prefill from row 0: causal attention over its own
    rows, and it leaves its (K, V) rows ``[B, T, kv heads / pack, pack *
    head_dim]`` for the caller to write. T == 1, a decode step at rows
    pos_v[b]: the row is written to the pool, carried flat ``[layers *
    pages, ...]`` with this layer the ``index``-th (traced: the scan's
    period), the paged kernel attends, and the pool is what it leaves."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = x.shape
    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    pack = _pack(c)
    # the kernels scale scores by (their) head_dim ** -0.5; the family's
    # scale goes onto q (at the published sizes a prefill's is 1/8: exact)
    q = _dot(x, lp['q'], cdt).reshape(b, t, nh, d)
    k = _dot(x, lp['k'], cdt).reshape(b, t, nkv, d).astype(cdt)
    v = _dot(x, lp['v'], cdt).reshape(b, t, nkv, d).astype(cdt)
    packed = [a.reshape(b, t, nkv // pack, pack * d) for a in (k, v)]
    if t > 1:
        o = flash_attention(
            (q * (c.attention_multiplier * math.sqrt(d))).astype(cdt), k, v,
            causal=True)
        left = tuple(packed)
    else:
        pages = pool['k'].shape[0] // len(c.layers_of(ATTENTION))
        table = table + (index * pages).astype(jnp.int32)
        planes = [paged_write(pool[n], rows, table, pos_v)
                  for n, rows in zip('kv', packed)]
        # a query head's 64 values in the lanes where its KV head lies in
        # the packed row, zeros in the others: the scores are its own, and
        # of the output row its KV head's lanes are its own
        place = (jnp.arange(nh) // (nh // nkv)) % pack          # [heads]
        mine = (place[:, None] == jnp.arange(pack)[None, :])[..., None]
        q = q * (c.attention_multiplier * math.sqrt(pack * d))
        wide = jnp.where(mine, q[..., None, :], 0.0).reshape(
            b, t, nh, pack * d).astype(cdt)
        o = paged_attention(wide, planes[0], planes[1], table, pos_v, cdt)
        o = jnp.sum(jnp.where(mine, o.reshape(b, t, nh, pack, d), 0), axis=3)
        left = dict(pool, k=planes[0], v=planes[1])
    return _dot(o.reshape(b, t, nh * d), lp['o'], cdt), left


def _state_space(lp, u, pool, index, slots, valid, config):
    """The state-space mixer over u [B, T, H] (normed). -> (out [B, T, H]
    float32, what the layer leaves). T > 1: from a zero state, and it
    leaves (the state after row ``valid - 1`` in the pool's layout, the
    convolution's tail there) for the caller to write; T == 1: one step of
    the pool's rows ``slots`` of layer ``index``, and the pool is what it
    leaves."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t, _ = u.shape
    nh, p, n, k = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                   c.mamba_d_conv)
    di = c.d_inner
    with jax.named_scope('in_proj'):
        zxbcdt = _dot(u, lp['in_proj'], cdt)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + c.conv_dim], axis=-1)
        xbc = xbc.astype(cdt)
        dt = jax.nn.softplus(dt + lp['dt_bias'])            # [B, T, heads]
    a = -jnp.exp(lp['a_log'])
    if t > 1:
        with jax.named_scope('conv'):
            if valid is not None:
                dt = jnp.where(jnp.arange(t)[None, :, None]
                               < valid.astype(jnp.int32)[:, None, None],
                               dt, 0.0)
            xbc, tail = _rec.causal_conv(xbc, lp['conv_w'], lp['conv_b'],
                                         valid)
            x, bm, cm = jnp.split(jax.nn.silu(xbc).astype(cdt),
                                  [di, di + n], axis=-1)
        with jax.named_scope('scan'):
            q = min(c.mamba_chunk_size, t)
            pad = -t % q
            padded = lambda v: jnp.pad(
                v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            y, last = _rec.chunked_scan(
                padded(x).reshape(b, t + pad, nh, p), padded(dt), a,
                padded(bm), padded(cm), q, cdt)
            y = y[:, :t]
        left = (last, tail.reshape(b, -1))
    else:
        n_slots = pool['ssm'].shape[0] // len(c.layers_of(MAMBA))
        rows = (index * n_slots).astype(jnp.int32) + slots.astype(jnp.int32)
        with jax.named_scope('conv'):
            tail = pool['conv'][rows].reshape(b, k - 1, c.conv_dim)
            xbc, tail = _rec.conv_step(tail, xbc[:, 0], lp['conv_w'],
                                       lp['conv_b'])
            x, bm, cm = jnp.split(jax.nn.silu(xbc).astype(cdt),
                                  [di, di + n], axis=-1)
            conv = pool['conv'].at[rows].set(tail.reshape(b, -1))
        with jax.named_scope('state_update'):
            dt1 = dt[:, 0]                                  # [B, heads]
            per_channel = lambda v: jnp.repeat(v, p, axis=-1)
            y, ssm = _rec.state_update(
                pool['ssm'], rows, per_channel(jnp.exp(dt1 * a)),
                per_channel(dt1) * x.astype(jnp.float32), bm, cm)
        left = dict(pool, ssm=ssm, conv=conv)
    with jax.named_scope('gate_norm'):
        x = x.reshape(b, t, nh, p).astype(jnp.float32)
        y = y.reshape(b, t, nh, p) + lp['d'][:, None] * x
        y = y.reshape(b, t, di) * jax.nn.silu(z)
        y = _rms(y, lp['norm_gate'], c.rms_norm_eps).astype(cdt)
    with jax.named_scope('out_proj'):
        return _dot(y, lp['out_proj'], cdt), left


def _layer(lp, x, pool, index, pos_v, tables, valid, kind, config):
    """One layer of ``kind``, the ``index``-th of its kind, over [B, T, H].
    -> (x, what its mixer leaves: a prefill's fresh rows or state, a decode
    step's pool)."""
    c, cdt = config, jnp.dtype(config.dtype)
    r = c.residual_multiplier
    with jax.named_scope('granite.block'):
        u = _rms(x, lp['norm_in'], c.rms_norm_eps).astype(cdt)
        if kind == ATTENTION:
            with jax.named_scope('attn'):
                out, left = _attention(lp, u, pool, index, pos_v,
                                       tables['kv'], c)
        else:
            with jax.named_scope('ssm'):
                out, left = _state_space(lp, u, pool, index,
                                         tables['state'], valid, c)
        x = (x.astype(jnp.float32) + r * out).astype(cdt)
        with jax.named_scope('mlp'):
            x = (x.astype(jnp.float32) + r * _mlp(lp, x, c)).astype(cdt)
    return x, left


def _decoder(params, tokens, pool, pos_v, tables, valid, config, last_only):
    """The layers and the head over [B, T] tokens. -> (logits, the pool
    (T == 1: every layer's rows updated) or what the layers left (T > 1:
    ``{'ssm', 'conv', 'k', 'v'}``, each ``[layers of its kind, B, ...]``,
    for ``_write_prefill``), counts [2] in the order of ``COUNTS``: one
    state-space layer's worth)."""
    c, cdt = config, jnp.dtype(config.dtype)
    b, t = tokens.shape
    period = c.period
    of_kind = {kind: sum(1 for k in period if k == kind)
               for kind in (MAMBA, ATTENTION)}
    n_periods = c.num_hidden_layers // len(period)
    x = (jnp.take(params['embed'], tokens, axis=0).astype(jnp.float32)
         * c.embedding_multiplier).astype(cdt)
    # a decode step carries every kind's planes flat, [layers * units,
    # ...]: views; a layer's rows are reached through an offset and written
    # in place. A prefill reads no pool: its layers leave what they made
    shapes = {n: a.shape for n, a in pool.items()}
    flat = {n: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
            for n, a in pool.items()} if t == 1 else None

    layer_of = functools.partial(_layer, config=c)
    if t > 1:
        # a prefill is traced and lowered once for every width the engine
        # may call it at: a period's nine state-space layers are traced
        # once and called, the layer's place among its kind an argument
        layer_of = jax.jit(layer_of, static_argnames=('kind',))

    def one_period(carry, step):
        x, flat = carry
        layers, at = step
        seen, left = {MAMBA: 0, ATTENTION: 0}, {MAMBA: [], ATTENTION: []}
        for lp, kind in zip(layers, period):
            x, out = layer_of(lp, x, flat, at * of_kind[kind] + seen[kind],
                              pos_v, tables, valid, kind=kind)
            seen[kind] += 1
            if t == 1:
                flat = out
            else:
                left[kind].append(out)
        return (x, flat), left
    (x, flat), left = jax.lax.scan(
        one_period, (x, flat),
        (params['periods'], jnp.arange(n_periods, dtype=jnp.int32)))
    if t == 1:
        out = {n: a.reshape(shapes[n]) for n, a in flat.items()}
    else:
        # [periods, B, ...] a position -> [layers of the kind, B, ...]
        def stack(parts):
            a = jnp.stack(parts, axis=1)
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
        out = {}
        for kind, names in ((MAMBA, ('ssm', 'conv')), (ATTENTION, 'kv')):
            for i, name in enumerate(names):
                if left[kind]:
                    out[name] = stack([pair[i] for pair in left[kind]])
    if last_only:
        if valid is not None:
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, t - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope('granite.head'):
        y = _rms(x, params['norm_f'], c.rms_norm_eps).astype(cdt)
        logits = (jnp.einsum('bth,vh->btv', y, params['embed'].astype(cdt),
                             preferred_element_type=jnp.float32)
                  / c.logits_scaling).astype(cdt)
    # one state-space layer's worth: the rows its recurrence served (a
    # prefill's real rows) and the chunks a prefill's scan ran, padding too
    rows = (jnp.sum(valid.astype(jnp.int32)) if t > 1 and valid is not None
            else jnp.int32(b * t))
    chunks = b * -(-t // min(c.mamba_chunk_size, t)) if t > 1 else 0
    has_state = int(bool(c.layers_of(MAMBA)))
    counts = jnp.stack([rows * has_state, jnp.int32(chunks * has_state)])
    return logits, out, counts.astype(jnp.int32)


def _write_prefill(pool, left, pos_v, tables, valid):
    """What a prefill's layers left, into the pool: every state-space
    layer's state and tail over its sequence's slot's row (what the last
    occupant left there is never read), every attention layer's K and V
    rows to the pages of its table. One write after the layers' scan: a
    prefill's layers read no pool, so none is carried through them."""
    pool = dict(pool)
    if 'ssm' in left:
        slots = tables['state'].astype(jnp.int32)
        n_slots = pool['ssm'].shape[1]
        rows = (jnp.arange(pool['ssm'].shape[0], dtype=jnp.int32)[:, None]
                * n_slots + slots[None, :]).reshape(-1)
        for name in ('ssm', 'conv'):
            plane, fresh = pool[name], left[name]
            flat = plane.reshape((-1,) + plane.shape[2:]).at[rows].set(
                fresh.reshape((-1,) + fresh.shape[2:]).astype(plane.dtype))
            pool[name] = flat.reshape(plane.shape)
    if 'k' in left:
        n_layers, pages = pool['k'].shape[:2]
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
    planes; 'page_table' ``{'kv': [B, P_max], 'state': [B] slots}``; for a
    prefill 'valid' [B]) -> (logits, cache). T > 1 is a prefill from row 0
    (whatever ``pos`` says: a recurrence has no tail to start from, so the
    family declines a prefix cache); T == 1 a decode step. The cache that
    comes back holds 'counts' in the order of ``COUNTS``. Rows past
    ``valid`` are padding at any ``T``: how wide a prompt is padded is the
    engine's choice (``family.prefill_widths``)."""
    del partitioner     # one chip: no rules table for this family
    b, t = tokens.shape
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    tables, valid = cache['page_table'], cache.get('valid')
    planes = {n: cache[n] for n in ('k', 'v', 'ssm', 'conv') if n in cache}
    logits, left, counts = _decoder(
        params, tokens, planes, pos_v, tables, valid, config, last_only)
    if t == 1:
        return logits, dict(cache, **left, counts=counts)
    pool = _write_prefill(planes, left, pos_v, tables, valid)
    return logits, dict(cache, **pool, counts=counts)


def forward(params, tokens, config):
    """[B, T] tokens -> [B, T, V] logits: a prefill over a throwaway pool
    of just these rows (tests and small checks; serving goes through
    ``GenerationEngine``)."""
    b, t = tokens.shape
    cache = dict(
        init_pool(config, {'kv': b + 1, 'state': b}, t),
        page_table={'kv': jnp.arange(1, b + 1, dtype=jnp.int32)[:, None],
                    'state': jnp.arange(b, dtype=jnp.int32)})
    return forward_with_cache(params, tokens, cache,
                              jnp.zeros((b,), jnp.int32), config)[0]


def note_counts(counts, phase):
    """A call's counts to the ``ssm.*`` counters (the engine calls this with
    what ``forward_with_cache`` counted): rows the state update served, one
    state-space layer's worth (a decode step counts every slot), and the
    chunks a prefill's scan ran."""
    vals = dict(zip(COUNTS, (int(x) for x in counts)))
    labels = {'phase': phase}
    _obs.counter('ssm.state_rows_total', labels=labels).inc(
        vals['state_rows'])
    if phase == 'prefill':
        _obs.counter('ssm.scan_chunks_total', labels=labels).inc(
            vals['scan_chunks'])


_family.register(GraniteHybridConfig, _family.GenerationFamily(
    name='granite_hybrid', init_pool=init_pool,
    forward_with_cache=forward_with_cache, serve_params=serve_params,
    note_counts=note_counts, tail_prefill=False, page_kinds=page_kinds))
