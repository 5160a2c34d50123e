"""Plain reference of granite-4.0-h-micro's decoder (ibm-granite, config.json
of huggingface.co/ibm-granite/granite-4.0-h-micro, model_type
granitemoehybrid with no routed experts): state-space (Mamba-2) layers with
a grouped-query attention layer at every tenth place, a dense gated MLP in
every layer, a tied embedding. Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision('highest')``: no kernel, no cache, no
batching, no scan over layers, and nothing of the program is imported. The
recurrence is a SEQUENTIAL ``lax.scan`` over tokens, one row at a time: it
shares nothing with the program's chunked form.

    h = embedding_multiplier * E[ids]
    h = h + residual_multiplier * mixer(N(h))
    h = h + residual_multiplier * W_out (silu(g) * v),  [g | v] = W_in N(h)
    logits = N_f(h) E^T / logits_scaling    N(v) = v rsqrt(mean(v^2) + eps) g

*Attention mixer* (``attention`` layers): q = u W_q -> 32 heads of 64,
k = u W_k, v = u W_v -> 8 heads; NO positional encoding
(``position_embedding_type: nope``); scores q.k * attention_multiplier
(0.015625 = 1/64, not 64 ** -0.5), causal softmax; four query heads share a
KV head; out = (softmax(.) v) W_o. No bias anywhere.

*State-space mixer* (``mamba`` layers), d_inner = 4096 = 64 heads x 64, one
group, d_state = 128, conv_dim = 4096 + 2 x 128:

    [z | xBC | dt] = u W_in                  (4096 | 4352 | 64, no bias)
    xBC = silu(conv1d(xBC))                  depthwise, causal, kernel 4, bias
    [x | B | C] = xBC                        (4096 | 128 | 128)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)       (a scalar a head)
    head j:  S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t B_t^T    (S in R^{64x128})
             y_t = S_t C_t + D_j x_t
    y = N(y * silu(z)) * w over all 4096      (the gate goes in BEFORE the norm)
    out = y W_out

``mamba_chunk_size`` is the block of the published code's chunked
algorithm and no part of these equations: it is not read here.

Departures from the published model: random weights (``init_layer`` says
which scales); no rotary dims to pair, for there are none. What the catalog's
row does not state and this file takes from the family's published code
(HF transformers' modeling_granitemoehybrid.py) as the configuration's
``assumed`` lists it: the order [z | xBC | dt] and [x | B | C] of the two
splits, the gate before the norm, softplus with no clamp (time_step_limit
(0, inf)), D a scalar a head, where the four multipliers go.

Weights are made from the seed one leaf at a time, the matrices rounded to
bfloat16 (the type the configuration serves; the reference widens them), the
small leaves (gains, the convolution, dt_bias, A_log, D) float32.
``init_layer`` makes one layer alone, so that a comparison can hold one
layer's float32 weights at a time (``embed`` / ``layer`` / ``head``).
"""
import functools
import math

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = 'mamba', 'attention'


def sizes(shape):
    """(d_inner, conv_dim, heads, head size, d_state, d_conv)."""
    nh, p, n = (int(shape[k]) for k in (
        'mamba_n_heads', 'mamba_d_head', 'mamba_d_state'))
    return (nh * p, nh * p + 2 * int(shape['mamba_n_groups']) * n, nh, p, n,
            int(shape['mamba_d_conv']))


# ---- weights ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, dims, std, dtype='bfloat16'):
    return (std * jax.random.normal(key, dims, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _gain(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def init_layer(shape, key, l):
    """Layer ``l``'s weights, from ``fold_in(key, l + 1)``: matrices
    N(0, 1/fan_in) in bfloat16; gains 1 + 0.1 N(0, 1), the convolution
    N(0, 1/d_conv) with a bias of 0.1 N(0, 1), ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in [1e-3, 0.1], ``a_log = log
    U(1, 16)`` and ``d = 1`` (the family's own initialisation: a head's
    decay a row lies between nearly 1 and about 0.2, never at either end),
    all float32."""
    k = jax.random.fold_in(key, l + 1)
    keys = (jax.random.fold_in(k, i) for i in range(32))
    h, f = int(shape['hidden_size']), int(shape['shared_intermediate_size'])
    lp = {'norm_in': _gain(next(keys), h), 'norm_mlp': _gain(next(keys), h),
          'mlp_in': _normal(next(keys), (h, 2 * f), h ** -0.5),
          'mlp_out': _normal(next(keys), (f, h), f ** -0.5)}
    if shape['layer_types'][l] == ATTENTION:
        d = h // int(shape['num_attention_heads'])
        nq = int(shape['num_attention_heads']) * d
        nkv = int(shape['num_key_value_heads']) * d
        lp.update(q=_normal(next(keys), (h, nq), h ** -0.5),
                  k=_normal(next(keys), (h, nkv), h ** -0.5),
                  v=_normal(next(keys), (h, nkv), h ** -0.5),
                  o=_normal(next(keys), (nq, h), nq ** -0.5))
        return lp
    di, conv_dim, nh, _, _, kc = sizes(shape)
    dt = jnp.exp(jax.random.uniform(next(keys), (nh,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    lp.update(
        in_proj=_normal(next(keys), (h, di + conv_dim + nh), h ** -0.5),
        conv_w=_normal(next(keys), (kc, conv_dim), kc ** -0.5, 'float32'),
        conv_b=_normal(next(keys), (conv_dim,), 0.1, 'float32'),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        a_log=jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32,
                                         1.0, 16.0)),
        d=jnp.ones((nh,), jnp.float32), norm_gate=_gain(next(keys), di),
        out_proj=_normal(next(keys), (di, h), di ** -0.5))
    return lp


def init_ends(shape, key):
    """The embedding (the head too: tied) and the final norm. The
    embedding's rows are N(0, 1/H); times ``embedding_multiplier`` and then
    normed by the first layer they are what any scale would be."""
    k = jax.random.fold_in(key, 0)
    v, h = int(shape['vocab_size']), int(shape['hidden_size'])
    return {'embed': _normal(jax.random.fold_in(k, 0), (v, h), h ** -0.5),
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


def attention(lp, u, shape):
    """u [B, T, H] float32, rows 0..T-1 -> [B, T, H]; no positions."""
    b, t, h = u.shape
    nh, nkv = (int(shape[k]) for k in ('num_attention_heads',
                                       'num_key_value_heads'))
    d = h // nh
    q = (u @ lp['q']).reshape(b, t, nh, d)
    k = jnp.repeat((u @ lp['k']).reshape(b, t, nkv, d), nh // nkv, axis=2)
    v = jnp.repeat((u @ lp['v']).reshape(b, t, nkv, d), nh // nkv, axis=2)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * float(
        shape['attention_multiplier'])
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v).reshape(b, t, nh * d) @ lp['o']


def state_space(lp, u, shape):
    """u [B, T, H] float32, rows 0..T-1 from a zero state -> [B, T, H]:
    one row after another."""
    b, t, _ = u.shape
    di, conv_dim, nh, p, n, kc = sizes(shape)
    z, xbc, dt = jnp.split(u @ lp['in_proj'], [di, di + conv_dim], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    xbc = lp['conv_b'] + sum(padded[:, j:j + t] * lp['conv_w'][j]
                             for j in range(kc))
    x, bm, cm = jnp.split(jax.nn.silu(xbc), [di, di + n], axis=-1)
    x = x.reshape(b, t, nh, p)
    dt = jax.nn.softplus(dt + lp['dt_bias'])                # [B, T, heads]
    a = -jnp.exp(lp['a_log'])

    def row(state, at):             # state [B, heads, P, N]
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)
    _, y = jax.lax.scan(
        row, jnp.zeros((b, nh, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + lp['d'][:, None] * x        # [B, T, nh, P]
    y = y.reshape(b, t, di) * jax.nn.silu(z)
    return rms(y, lp['norm_gate'], float(shape['rms_norm_eps'])) @ lp[
        'out_proj']


def mlp(lp, u):
    g, v = jnp.split(u @ lp['mlp_in'], 2, axis=-1)
    return (jax.nn.silu(g) * v) @ lp['mlp_out']


def embed(ends, tokens, shape):
    return ends['embed'][tokens].astype(jnp.float32) * float(
        shape['embedding_multiplier'])


def layer(lp, x, shape, kind):
    """One layer of ``kind`` (its entry of ``layer_types``) over [B, T, H]
    float32, rows 0..T-1."""
    with jax.default_matmul_precision('highest'):
        lp, eps = _f32(lp), float(shape['rms_norm_eps'])
        r = float(shape['residual_multiplier'])
        u = rms(x, lp['norm_in'], eps)
        x = x + r * (attention(lp, u, shape) if kind == ATTENTION
                     else state_space(lp, u, shape))
        return x + r * mlp(lp, rms(x, lp['norm_mlp'], eps))


def head(ends, x, shape):
    with jax.default_matmul_precision('highest'):
        y = rms(x, ends['norm_f'].astype(jnp.float32),
                float(shape['rms_norm_eps']))
        return y @ ends['embed'].astype(jnp.float32).T / float(
            shape['logits_scaling'])


def forward(params, tokens, shape):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    x = embed(params, tokens, shape)
    for l, lp in enumerate(params['layers']):
        x = layer(lp, x, shape, shape['layer_types'][l])
    return head(params, x, shape)
