"""Plain reference of Brumby-14B-Base's decoder (manifestai, config.json of
huggingface.co/manifestai/Brumby-14B-Base, model_type brumby): a Qwen3-14B
decoder in which every layer's softmax attention is power retention of
degree 2 (Buckman, Gelada, Zhang, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239). Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision('highest')``: no kernel, no cache, no
batching, no scan over layers, and nothing of the program is imported.
Retention is computed in its ATTENTION FORM, every query row against every
row before it: no feature map, no state, no chunks. It shares no line and no
algebraic form with the program's recurrence.

    h   = x + W_o Ret(N(x))
    out = h + W_down (silu(W_gate m) * (W_up m)),  m = N(h)
    logits = N_f(out) W_head         N(v) = v rsqrt(mean(v^2) + eps) g

    q_t = rope(N_q(W_q n_t))     40 heads of 128   (N_q, N_k: over a head's 128)
    k_t = rope(N_k(W_k n_t))     8 heads of 128    (rope: theta 1e6, half-split,
    v_t = W_v n_t                8 heads of 128     over the whole head)
    l_t = logsigmoid(W_g n_t + b_g)    one a KV head;  L_t = sum_{s<=t} l_s
    query head a of KV head j's group, s <= t:
        a_ts  = (q^a_t . k^j_s)^2 exp(L^j_t - L^j_s)
        y^a_t = sum_s a_ts v^j_s / (sum_s a_ts + eps)

Scores are taken a block of query rows at a time (a request of 4,096 rows
would hold 2.7 GB of them at once).

What the catalog's row does not state and this file takes as the issue's
author knows the published method (no network here; the configuration's
``assumed`` lists each): degree 2; the gate, one value a KV head through
``logsigmoid`` of a linear map of the normed row with a bias; the
normaliser (the sum of the weights, ``eps`` beside it); that Qwen3's q/k
norms and rotary encoding stay. A scalar scale on ``q . k`` cancels in the
quotient and is left out. No bias in q, k, v, o or the MLP.

Weights are made from the seed one leaf at a time, the matrices rounded to
bfloat16 (the type the configuration serves; the reference widens them), the
small leaves (gains, the gate's bias) float32. ``init_layer`` makes one
layer alone, so that a comparison can hold one layer's float32 weights at a
time (``embed`` / ``layer`` / ``head``).
"""
import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6
QUERY_BLOCK = 512
# a head's memory, 1 / (1 - g) tokens, at the gate's bias alone
MEMORY = (16.0, 4096.0)


def sizes(shape):
    """(query heads, KV heads, head size, gate columns' offsets)."""
    nh, nkv, d = (int(shape[k]) for k in (
        'num_attention_heads', 'num_key_value_heads', 'head_dim'))
    return nh, nkv, d, (nh * d, (nh + nkv) * d, (nh + 2 * nkv) * d)


# ---- weights ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, dims, std, dtype='bfloat16'):
    return (std * jax.random.normal(key, dims, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _gain(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def init_layer(shape, key, l):
    """Layer ``l``'s weights, from ``fold_in(key, l + 1)``: matrices
    N(0, 1/fan_in) in bfloat16 (q, k, v and the gate's 8 columns side by
    side in ``qkvg``, the MLP's gate and up in ``gate_up``: how the program
    holds them); gains 1 + 0.1 N(0, 1); the gate's bias ``log(m - 1)`` with
    a head's memory ``m`` log-uniform in ``MEMORY`` tokens, so that at the
    bias alone ``1 / (1 - g) = m`` (with ``W_g n ~ N(0, 1)`` added a row's
    memory lies within a factor e of it, mostly)."""
    k = jax.random.fold_in(key, l + 1)
    keys = (jax.random.fold_in(k, i) for i in range(16))
    h, f = int(shape['hidden_size']), int(shape['intermediate_size'])
    nh, nkv, d, _ = sizes(shape)
    memory = jnp.exp(jax.random.uniform(
        next(keys), (nkv,), jnp.float32, math.log(MEMORY[0]),
        math.log(MEMORY[1])))
    return {'norm_in': _gain(next(keys), h), 'norm_mlp': _gain(next(keys), h),
            'qkvg': _normal(next(keys), (h, (nh + 2 * nkv) * d + nkv),
                            h ** -0.5),
            'o': _normal(next(keys), (nh * d, h), (nh * d) ** -0.5),
            'q_norm': _gain(next(keys), d), 'k_norm': _gain(next(keys), d),
            'gate_bias': jnp.log(memory - 1.0),
            'gate_up': _normal(next(keys), (h, 2 * f), h ** -0.5),
            'down': _normal(next(keys), (f, h), f ** -0.5)}


def init_ends(shape, key):
    """The embedding, the head (not tied) and the final norm."""
    k = jax.random.fold_in(key, 0)
    v, h = int(shape['vocab_size']), int(shape['hidden_size'])
    return {'embed': _normal(jax.random.fold_in(k, 0), (v, h), h ** -0.5),
            'head': _normal(jax.random.fold_in(k, 2), (h, v), h ** -0.5),
            'norm_f': _gain(jax.random.fold_in(k, 1), h)}


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
    """x [B, T, heads, d] at rows 0..T-1: pairs (i, i + d/2) rotated."""
    t, d = x.shape[1], x.shape[-1]
    inv = float(shape['rope_theta']) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # [T, d/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def retention(lp, u, shape):
    """u [B, T, H] float32, rows 0..T-1 -> [B, T, H]: the attention form."""
    b, t, _ = u.shape
    nh, nkv, d, cuts = sizes(shape)
    eps = float(shape['rms_norm_eps'])
    q, k, v, gate = jnp.split(u @ lp['qkvg'], cuts, axis=-1)
    q = rope(rms(q.reshape(b, t, nh, d), lp['q_norm'], eps), shape)
    k = rope(rms(k.reshape(b, t, nkv, d), lp['k_norm'], eps), shape)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v.reshape(b, t, nkv, d), nh // nkv, axis=2)
    # L_t, a query head's own copy of its KV head's: [B, heads, T]
    big_l = jnp.repeat(jnp.cumsum(jax.nn.log_sigmoid(gate + lp['gate_bias']),
                                  axis=1), nh // nkv, axis=2)
    big_l = jnp.moveaxis(big_l, 1, 2)
    keys = jnp.arange(t)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        dots = jnp.einsum('bqhd,bkhd->bhqk', q[:, lo:hi], k)
        seen = keys[None, :] <= jnp.arange(lo, hi)[:, None]
        fade = jnp.exp(jnp.where(
            seen, big_l[:, :, lo:hi, None] - big_l[:, :, None, :], -jnp.inf))
        w = dots * dots * fade
        total = jnp.sum(w, axis=-1)                         # [B, heads, Q]
        y = jnp.einsum('bhqk,bkhd->bqhd', w, v)
        out.append(y / (jnp.moveaxis(total, 1, 2)[..., None] + EPS))
    return jnp.concatenate(out, axis=1).reshape(b, t, nh * d) @ lp['o']


def mlp(lp, u):
    g, v = jnp.split(u @ lp['gate_up'], 2, axis=-1)
    return (jax.nn.silu(g) * v) @ lp['down']


def embed(ends, tokens, shape):
    del shape
    return ends['embed'][tokens].astype(jnp.float32)


def layer(lp, x, shape, kind=None):
    """One layer over [B, T, H] float32, rows 0..T-1 (every layer is of
    the one kind; ``kind`` is what a caller that walks ``layer_types``
    passes)."""
    del kind
    with jax.default_matmul_precision('highest'):
        lp, eps = _f32(lp), float(shape['rms_norm_eps'])
        x = x + retention(lp, rms(x, lp['norm_in'], eps), shape)
        return x + mlp(lp, rms(x, lp['norm_mlp'], eps))


def head(ends, x, shape):
    with jax.default_matmul_precision('highest'):
        y = rms(x, ends['norm_f'].astype(jnp.float32),
                float(shape['rms_norm_eps']))
        return y @ ends['head'].astype(jnp.float32)


def forward(params, tokens, shape):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    x = embed(params, tokens, shape)
    for lp in params['layers']:
        x = layer(lp, x, shape)
    return head(params, x, shape)
