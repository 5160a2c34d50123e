"""Plain reference of Trinity-Large-Preview's decoder (arcee-ai, config.json
of huggingface.co/arcee-ai/Trinity-Large-Preview, model_type afmoe): a
decoder of grouped-query attention in which three layers of four attend a
sliding window with rotary positions and the fourth attends everything with
no positional encoding, every attention output gated, sandwich RMSNorms,
leading dense SwiGLU layers and routed expert layers (sigmoid scores, a
bias that moves the choice, one shared expert), an embedding scaled by
sqrt(hidden), an untied head. Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision('highest')``: no kernel, no cache, no scan
over layers, and nothing of the program is imported.

    x  = wte[ids] * sqrt(H)                                   (mup_enabled)
    x += N_attn(Attn(N_in(x)));  x += N_mlp(MLP(N_pre_mlp(x)))
    logits = N_f(x) W_head            N(v) = v * rsqrt(mean(v^2) + eps) * g

*Attention.* q = h W_q -> 48 heads of 128, k = h W_k, v = h W_v -> 8 heads,
g = h W_g -> 48 x 128; q and k are RMS-normed a head (gains q_norm,
k_norm); on a ``sliding_attention`` layer q and k are rotated (theta 10000,
all 128 dims, no scaling), on a ``full_attention`` layer they are not;
scores q.k * 128^-0.5, causal; a sliding layer's query at p attends keys
p - window + 1 .. p; six query heads share a KV head;
out = (softmax(.) v * sigmoid(g)) W_o.

*Routed experts.* s = sigmoid(h W_r^T) over the router's whole width; the
``num_experts_per_tok`` largest of s + bias are chosen (the lower index wins
a tie; ``n_group`` = ``topk_group`` = 1, so no group is left out);
w_i = route_scale * s_i / (sum of s over ALL chosen + 1e-20);
y = sum over chosen AND held of w_i E_i(h) + E_shared(h),
E(h) = (silu(h W_gate) * (h W_up)) W_down. No capacity, no dropped token.

*The share held.* ``shape['num_experts']`` experts are held here, from
``shape['held_first']`` on, of the ``shape['router_width']`` the router
scores; what the others would add is left out and the partial result goes on
to the next layer, as on one chip of an expert-parallel deployment without
its exchange. An expert's weights follow its place among ALL the experts, so
the shares of every chip add up to the uncut layer. The vocabulary is the
slice the configuration gives.

Departures from the published code, none of which random weights can see:
rotary dims are paired half-split where a checkpoint may interleave them (a
permutation of W_q's and W_k's columns within a head). What the catalog's
row does not state and this file takes from the family's published code as
the configuration's ``assumed`` lists it: the gate and its place, the q/k
norms, no positions on full layers, the four norms' places, the embedding's
scale.

Attention is computed in blocks of ``QUERY_BLOCK`` query rows, so that a
request of 16,384 rows holds 48 x 512 x 16,384 float32 scores (1.6 GB) at a
time and not 48 x 16,384^2. Weights are made from the seed one leaf at a
time and rounded to bfloat16, the type the configuration serves
(``init_params``: what program and reference both use; the reference widens
them). ``init_layer`` makes one layer alone, so that a comparison can hold
one layer's float32 weights at a time (``embed`` / ``layer`` / ``head``).
"""
import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SLIDING = 'sliding_attention'


def held(shape):
    """(first, count) of the routed experts held here, and the router's
    width."""
    return (int(shape.get('held_first', 0)), int(shape['num_experts']),
            int(shape.get('router_width', shape['num_experts'])))


# ---- weights ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, dims, std, dtype='bfloat16'):
    return (std * jax.random.normal(key, dims, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _gain(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _experts(key, first, count, dims, std):
    """[count, *dims]: expert e's matrix follows its place e among all."""
    one = lambda e: std * jax.random.normal(jax.random.fold_in(key, e), dims,
                                            jnp.float32)
    return jax.vmap(one)(first + jnp.arange(count)).astype(jnp.bfloat16)


def _swiglu_params(keys, h, f):
    return {'gate': _normal(next(keys), (h, f), h ** -0.5),
            'up': _normal(next(keys), (h, f), h ** -0.5),
            'down': _normal(next(keys), (f, h), f ** -0.5)}


def init_layer(shape, key, l):
    """Layer ``l``'s weights (bfloat16; the router float32, its values
    bfloat16's), from ``fold_in(key, l + 1)``."""
    k = jax.random.fold_in(key, l + 1)
    keys = (jax.random.fold_in(k, i) for i in range(64))
    h, d = int(shape['hidden_size']), int(shape['head_dim'])
    nq = int(shape['num_attention_heads']) * d
    nkv = int(shape['num_key_value_heads']) * d
    lp = {'norm_in': _gain(next(keys), h), 'norm_attn': _gain(next(keys), h),
          'norm_pre_mlp': _gain(next(keys), h),
          'norm_mlp': _gain(next(keys), h),
          'q': _normal(next(keys), (h, nq), h ** -0.5),
          'k': _normal(next(keys), (h, nkv), h ** -0.5),
          'v': _normal(next(keys), (h, nkv), h ** -0.5),
          'gate': _normal(next(keys), (h, nq), h ** -0.5),
          'o': _normal(next(keys), (nq, h), nq ** -0.5),
          'q_norm': _gain(next(keys), d), 'k_norm': _gain(next(keys), d)}
    if l < int(shape['num_dense_layers']):
        lp['mlp'] = _swiglu_params(keys, h, int(shape['intermediate_size']))
        return lp
    first, count, width = held(shape)
    f = int(shape['moe_intermediate_size'])
    lp['router'] = _normal(next(keys), (width, h), h ** -0.5).astype(
        jnp.float32)
    # small and not zero, so that the order of near scores feels it; small
    # beside the scores' own spread, as a trained bias that balances the
    # experts' load is (benchmark/reference/dots_vlm.py says what a larger
    # one did)
    lp['router_bias'] = _normal(next(keys), (width,), 0.002, 'float32')
    kg, ku, kd = next(keys), next(keys), next(keys)
    lp['experts'] = {'gate': _experts(kg, first, count, (h, f), h ** -0.5),
                     'up': _experts(ku, first, count, (h, f), h ** -0.5),
                     'down': _experts(kd, first, count, (f, h), f ** -0.5)}
    lp['shared'] = _swiglu_params(
        keys, h, f * int(shape.get('num_shared_experts', 1)))
    return lp


def init_ends(shape, key):
    """Embedding, final norm and head, over the vocabulary slice. The
    embedding's rows are N(0, 1/H): scaled by sqrt(H) they have unit
    variance, beside layers whose normed outputs have it too."""
    k = jax.random.fold_in(key, 0)
    v, h = int(shape['vocab_size']), int(shape['hidden_size'])
    return {'embed': _normal(jax.random.fold_in(k, 0), (v, h), h ** -0.5),
            'norm_f': _gain(jax.random.fold_in(k, 1), h),
            'head': _normal(jax.random.fold_in(k, 2), (h, v), h ** -0.5)}


def init_params(shape, key):
    """The weights program and reference both use, leaf by leaf."""
    return dict(init_ends(shape, key),
                layers=[init_layer(shape, key, l)
                        for l in range(int(shape['num_hidden_layers']))])


# ---- the layers ------------------------------------------------------------

def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, shape):
    """x [B, T, heads, d] at positions 0..T-1, half-split pairing."""
    d = x.shape[-1]
    inv = float(shape['rope_theta']) ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(lp, h, shape, kind):
    """h [B, T, H] float32, positions 0..T-1 -> [B, T, H]; ``kind`` the
    layer's entry of ``layer_types``."""
    b, t, _ = h.shape
    nh, nkv, d = (int(shape[x]) for x in (
        'num_attention_heads', 'num_key_value_heads', 'head_dim'))
    eps = float(shape['rms_norm_eps'])
    q = rms((h @ lp['q']).reshape(b, t, nh, d), lp['q_norm'], eps)
    k = rms((h @ lp['k']).reshape(b, t, nkv, d), lp['k_norm'], eps)
    v = (h @ lp['v']).reshape(b, t, nkv, d)
    gate = jax.nn.sigmoid(h @ lp['gate'])
    if kind == SLIDING:
        q, k = rope(q, shape), rope(k, shape)
    k, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
    first = (jnp.arange(t) - int(shape['sliding_window']) + 1
             if kind == SLIDING else jnp.zeros((t,), jnp.int32))

    def rows(start):                       # a block of query rows
        at = start + jnp.arange(QUERY_BLOCK)
        qb = jnp.take(q, jnp.minimum(at, t - 1), axis=1)
        s = jnp.einsum('bqhd,bkhd->bhqk', qb, k) * d ** -0.5
        keys = jnp.arange(t)[None, :]
        seen = (keys <= at[:, None]) & (
            keys >= jnp.take(first, jnp.minimum(at, t - 1))[:, None])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum('bhqk,bkhd->bqhd', p, v)
    starts = jnp.arange(0, t, QUERY_BLOCK)
    o = jnp.moveaxis(jax.lax.map(rows, starts), 0, 1)    # [B, n, QB, nh, d]
    o = o.reshape(b, -1, nh * d)[:, :t]
    return (o * gate) @ lp['o']


def swiglu(p, h):
    return (jax.nn.silu(h @ p['gate']) * (h @ p['up'])) @ p['down']


def route(h, router, bias, shape):
    """-> (chosen experts [..., k] int32, their weights [..., k])."""
    s = jax.nn.sigmoid(h @ router.T)
    chosen = jax.lax.top_k(s + bias, int(shape['num_experts_per_tok']))[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if shape.get('route_norm', True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(shape['route_scale'])


def routed_experts(lp, h, shape):
    """The part of the expert layer this share gives: the held experts'
    weighted outputs and the shared expert's."""
    first, count, _ = held(shape)
    chosen, w = route(h, lp['router'], lp['router_bias'], shape)

    def add(y, held_expert):        # one held expert after another
        weights, e = held_expert
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(weights, h), None
    y, _ = jax.lax.scan(add, swiglu(lp['shared'], h),
                        (lp['experts'], jnp.arange(count)))
    return y


def embed(ends, tokens, shape):
    x = ends['embed'][tokens].astype(jnp.float32)
    if shape.get('mup_enabled', True):
        x = x * math.sqrt(int(shape['hidden_size']))
    return x


def layer(lp, x, shape, kind):
    """One layer of ``kind`` (its entry of ``layer_types``) over [B, T, H]
    float32 at positions 0..T-1."""
    with jax.default_matmul_precision('highest'):
        lp, eps = _f32(lp), float(shape['rms_norm_eps'])
        a = attention(lp, rms(x, lp['norm_in'], eps), shape, kind)
        x = x + rms(a, lp['norm_attn'], eps)
        y = rms(x, lp['norm_pre_mlp'], eps)
        y = (swiglu(lp['mlp'], y) if 'mlp' in lp
             else routed_experts(lp, y, shape))
        return x + rms(y, lp['norm_mlp'], eps)


def head(ends, x, shape):
    with jax.default_matmul_precision('highest'):
        return rms(x, ends['norm_f'].astype(jnp.float32),
                   float(shape['rms_norm_eps'])) @ ends['head'].astype(
                       jnp.float32)


def forward(params, tokens, shape):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    x = embed(params, tokens, shape)
    for l, lp in enumerate(params['layers']):
        x = layer(lp, x, shape, shape['layer_types'][l])
    return head(params, x, shape)
