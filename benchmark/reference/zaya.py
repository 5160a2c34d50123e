"""Plain reference of ZAYA1-8B's decoder (Zyphra, config.json of
huggingface.co/Zyphra/ZAYA1-8B, model_type zaya): every layer an attention
half (compressed convolutional attention, CCA) and an expert half (sixteen
SwiGLU experts, one a token, chosen by a small MLP router whose state runs
down the stack, with a seventeenth choice that SKIPS the experts), each half
merged into the residual through learned scales and offsets, RMSNorms, a
tied head. Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision('highest')``: no kernel, no cache, no scan
over layers, no batching, and nothing of the program is imported. THIS FILE
IS THE DEFINITION of every equation the catalog row's keys do not fix (the
configuration's ``assumed`` lists each).

    x = E[ids];  r_{-1} = 0
    layer l:  x = merge_a(x, CCA(N_a(x)));  (y, r_l) = MoE(N_m(x), r_{l-1});
              x = merge_m(x, y)
    merge(x, f) = (a_r * x + b_r) + (a_f * f + b_f)   four vectors a half
    logits = N_f(x) E^T            N(v) = v * rsqrt(mean(v^2) + eps) * g

*CCA* over u = N_a(x), rows t = 0..T-1, everything before row 0 zero:
``q~_t = u_t W_q`` (heads x 128), ``k~_t = u_t W_k`` (kv heads x 128);
``c = [q~ | k~]`` goes through two causal convolutions over the sequence:
``c0_t = w0[0] c_{t-1} + w0[1] c_t`` (depthwise, kernel ``cca_time0`` = 2),
``c1_t = c0_{t-1} W1[0] + c0_t W1[1]`` a head (kernel ``cca_time1`` = 2,
grouped by head: ``W1[j]`` is [heads + kv heads, 128, 128] and mixes a
head's own channels); ``[q^ | k^] = c1``. The mean of the PRE-convolution q
and k joins both: ``m_h = (q~_h + k~_{h div group}) / 2``, ``q_h = q^_h +
m_h``, ``k_g = k^_g + mean of m_h over g's query heads``. q and k are
L2-normalised a head and scaled by sqrt(128) (together: v * rsqrt(mean(v^2)
+ eps)), k further by ``exp(temp_g)``; rotary positions on the first
``partial_rotary_factor`` of a head's dims (theta 5,000,000), pairs
half-split inside them. Value shift: ``v_t = [u_t W_v1 | u_{t-1} W_v2]``,
one KV head each. Causal softmax attention, scores / sqrt(128), ``group``
query heads a KV head; ``W_o``.

*The expert half* over u = N_m(x): ``r = u W_down`` (-> router_hidden_size);
exponential depth averaging ``r_l = r + gamma_l r_{l-1}`` with the layer
above's state AFTER its own averaging; ``p = softmax(W_3 gelu(W_2
gelu(W_1 N_r(r_l))))`` over the experts and, last, the skip choice; the
choice is ``argmax(p + b)`` (the lower index wins a tie): ``b`` moves the
choice and not the weight; y = ``p_e SwiGLU_e(u)``, for the skip choice
``p_skip u``. ``SwiGLU(u) = (silu(u W_gate) * (u W_up)) W_down``.

*The share held.* ``shape['num_experts']`` experts are held here, from
``shape['held_first']`` on, of the ``shape['router_width']`` the router
chooses among (all of them in the configuration the benchmark runs); a row
whose expert is not held gets nothing from this share, a skip row gets its
``p_skip u`` from every share alike. An expert's weights follow its place
among ALL the experts, so the shares add up to the uncut half.

Departures from the published description, none of which random weights can
see: rotary dims are paired half-split where a checkpoint may interleave
them. What the row does not state and is ASSUMED here: the order
convolution -> mean -> norm -> rotary, the sqrt(128) and exp(temp) scales,
that the skip choice exists in this model and returns ``p_skip u``, where
the four residual vectors sit, ``gamma`` a scalar a layer, gelu's tanh form,
no bias anywhere.

Weights are made from the seed one leaf at a time: the matrices N(0,
1/fan_in) rounded to bfloat16, the type the configuration serves (the
reference widens them); the router, the depthwise convolution and every
small leaf float32. ``init_layer`` makes one layer alone, so that a
comparison holds one layer's float32 weights at a time (``embed`` / ``layer``
/ ``head``).
"""
import functools
import math

import jax
import jax.numpy as jnp

HEAD = 128      # the row's head_dim: a head's channels, a group of conv1


def sizes(shape):
    """(query heads, KV heads, head size, router width of the experts: the
    skip choice is one more)."""
    nh, nkv = (int(shape[k]) for k in ('num_attention_heads',
                                       'num_key_value_heads'))
    return nh, nkv, int(shape.get('head_dim', HEAD)), int(
        shape.get('router_width', shape['num_experts']))


def held(shape):
    """(first, count) of the experts held here."""
    return int(shape.get('held_first', 0)), int(shape['num_experts'])


# ---- weights ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, dims, std, dtype='bfloat16'):
    return (std * jax.random.normal(key, dims, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _gain(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _experts(key, first, count, dims, std):
    """[count, *dims]: expert e's matrix follows its place e among all."""
    one = lambda e: std * jax.random.normal(jax.random.fold_in(key, e), dims,
                                            jnp.float32)
    return jax.vmap(one)(first + jnp.arange(count)).astype(jnp.bfloat16)


@jax.jit
def _centred(w):
    """``w`` [fan_in, out] with every column's sum taken out."""
    return w - jnp.mean(w, axis=0, keepdims=True)


def _merge_vectors(keys, h):
    """(a_r, b_r, a_f, b_f) [4, H]: scales 1 + 0.1 N, offsets 0.02 N, all
    away from the neutral (1, 0, 1, 0) so that a merge left out shows."""
    a_r, a_f = _gain(next(keys), h), _gain(next(keys), h)
    b_r = _normal(next(keys), (h,), 0.02, 'float32')
    b_f = _normal(next(keys), (h,), 0.02, 'float32')
    return jnp.stack([a_r, b_r, a_f, b_f])


def init_layer(shape, key, l):
    """Layer ``l``'s weights, from ``fold_in(key, l + 1)``.

        norm_attn, norm_moe [H]; merge_attn, merge_moe [4, H]
        q [H, heads 128], k [H, kv 128], v1, v2 [H, 128], o [heads 128, H]
        conv0 [2, C] f32, conv1 [2, C / 128, 128, 128] (C = (heads + kv) 128)
        temp [kv] f32
        router {down [H, R], gamma [], norm [R], w1, w2 [R, R],
                w3 [R, E + 1], bias [E + 1]} f32
        experts {gate, up [held, H, F], down [held, F, H]}

    The router's MLP is drawn wide enough (variance 2 / fan_in into a gelu,
    4 / fan_in into the softmax) that a row's largest probability lies
    around a third and not at 1 / 17: the weight ``p_e`` then matters.
    Every column of ``w2`` and of ``w3`` SUMS TO ZERO (``_centred``): a
    gelu's output has a mean, the same for every unit and every row, and
    through a column that does not sum to zero it is an offset on one
    expert's logit for every row alike, which sent a step's 48 rows to 10
    of 16 experts (PERF.md section 6, PR 40); a trained router's balancing
    leaves no such offset, and with none a step touches 14 to 15."""
    k = jax.random.fold_in(key, l + 1)
    keys = (jax.random.fold_in(k, i) for i in range(64))
    nh, nkv, d, width = sizes(shape)
    h, f = int(shape['hidden_size']), int(shape['moe_intermediate_size'])
    r = int(shape['router_hidden_size'])
    nq, nk = nh * d, nkv * d
    c = nq + nk
    lp = {'norm_attn': _gain(next(keys), h), 'norm_moe': _gain(next(keys), h),
          'merge_attn': _merge_vectors(keys, h),
          'merge_moe': _merge_vectors(keys, h),
          'q': _normal(next(keys), (h, nq), h ** -0.5),
          'k': _normal(next(keys), (h, nk), h ** -0.5),
          'v1': _normal(next(keys), (h, d), h ** -0.5),
          'v2': _normal(next(keys), (h, d), h ** -0.5),
          'o': _normal(next(keys), (nq, h), nq ** -0.5),
          'conv0': _normal(next(keys), (int(shape['cca_time0']), c),
                           int(shape['cca_time0']) ** -0.5, 'float32'),
          'conv1': _normal(next(keys), (int(shape['cca_time1']), c // d, d, d),
                           (int(shape['cca_time1']) * d) ** -0.5),
          'temp': _normal(next(keys), (nkv,), 0.3, 'float32')}
    first, count = held(shape)
    lp['router'] = {
        'down': _normal(next(keys), (h, r), h ** -0.5, 'float32'),
        'gamma': 0.25 + 0.5 * jax.random.uniform(next(keys), (), jnp.float32),
        'norm': _gain(next(keys), r),
        'w1': _normal(next(keys), (r, r), (2.0 / r) ** 0.5, 'float32'),
        'w2': _centred(_normal(next(keys), (r, r), (2.0 / r) ** 0.5,
                               'float32')),
        'w3': _centred(_normal(next(keys), (r, width + 1), (4.0 / r) ** 0.5,
                               'float32')),
        # small beside the probabilities' own spread, as a bias that
        # balances the experts' load is, and not zero
        'bias': _normal(next(keys), (width + 1,), 0.02, 'float32')}
    kg, ku, kd = next(keys), next(keys), next(keys)
    lp['experts'] = {'gate': _experts(kg, first, count, (h, f), h ** -0.5),
                     'up': _experts(ku, first, count, (h, f), h ** -0.5),
                     'down': _experts(kd, first, count, (f, h), f ** -0.5)}
    return lp


def init_ends(shape, key):
    """The embedding (the head too: tied) and the final norm. The
    embedding's rows are N(0, 1/H): the first layer norms them, and as the
    head against the final norm's unit rows they give logits of unit
    variance."""
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


def before(a):
    """Row t of the result is row t - 1 of ``a`` [B, T, ...]; row 0 zero."""
    return jnp.pad(a, ((0, 0), (1, 0)) + ((0, 0),) * (a.ndim - 2))[:, :-1]


def rope(x, shape):
    """x [B, T, heads, d] at positions 0..T-1: the first
    ``partial_rotary_factor`` of d rotated, pairs half-split inside it."""
    d = x.shape[-1]
    rot = int(d * float(shape['partial_rotary_factor']))
    inv = float(shape['rope_theta']) ** (
        -2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = jnp.split(x, [rot // 2, rot], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def cca(lp, u, shape):
    """u [B, T, H] float32 (normed), rows 0..T-1 -> [B, T, H]."""
    b, t, _ = u.shape
    nh, nkv, d, _ = sizes(shape)
    group, eps = nh // nkv, float(shape['rms_norm_eps'])
    qk = jnp.concatenate([u @ lp['q'], u @ lp['k']], axis=-1)    # [B, T, C]
    c0 = lp['conv0'][0] * before(qk) + lp['conv0'][1] * qk
    by_head = lambda a: a.reshape(b, t, nh + nkv, d)
    c1 = (jnp.einsum('btgc,gcd->btgd', by_head(before(c0)), lp['conv1'][0])
          + jnp.einsum('btgc,gcd->btgd', by_head(c0), lp['conv1'][1]))
    q_pre, k_pre = jnp.split(by_head(qk), [nh], axis=2)
    m = (q_pre + jnp.repeat(k_pre, group, axis=2)) / 2           # a q head
    q = c1[:, :, :nh] + m
    k = c1[:, :, nh:] + jnp.mean(m.reshape(b, t, nkv, group, d), axis=3)
    q = rms(q, 1.0, eps)                    # L2-normalised, times sqrt(d)
    k = rms(k, 1.0, eps) * jnp.exp(lp['temp'])[:, None]
    q, k = rope(q, shape), rope(k, shape)
    v = jnp.stack([u @ lp['v1'], before(u) @ lp['v2']], axis=2)  # kv heads
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * d ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v).reshape(b, t, nh * d) @ lp['o']


def swiglu(p, u):
    return (jax.nn.silu(u @ p['gate']) * (u @ p['up'])) @ p['down']


def router_probabilities(rp, u, r_above, shape):
    """-> (p [..., E + 1] over the experts and, last, the skip choice, the
    router's state for the layer below)."""
    r = u @ rp['down'] + rp['gamma'] * r_above
    s = rms(r, rp['norm'], float(shape['rms_norm_eps']))
    gelu = lambda a: jax.nn.gelu(a, approximate=True)
    return jax.nn.softmax(gelu(gelu(s @ rp['w1']) @ rp['w2']) @ rp['w3'],
                          axis=-1), r


def router(rp, u, r_above, shape):
    """-> (the choice [...] int32 in 0..E (E: skip), its probability, the
    router's state for the layer below)."""
    p, r = router_probabilities(rp, u, r_above, shape)
    chosen = jnp.argmax(p + rp['bias'], axis=-1).astype(jnp.int32)
    return chosen, jnp.take_along_axis(p, chosen[..., None], axis=-1)[..., 0], r


def router_again(rp, noted, shape):
    """The router run AGAIN on a program's own rows: ``noted`` {'input'
    [N, H], 'above' [N, R] (the state of the layer above as the program
    held it; zero for the first layer), 'state' [N, R], 'probability' [N],
    'choice' [N] int32}, what the program's router was given and what it
    answered for N rows of this layer. -> {'state': ||its state - this
    router's|| / ||this router's|| [N], 'weight': |its probability - this
    router's of ITS choice| relative [N], 'choice': its choice is not this
    router's [N] bool}. The program's residual stream plays no part: both
    routers start from the same rows, so what differs is the router's own
    arithmetic."""
    with jax.default_matmul_precision('highest'):
        rp = _f32(rp)
        p, r = router_probabilities(rp, noted['input'], noted['above'], shape)
        chosen = jnp.argmax(p + rp['bias'], axis=-1)
        p_its = jnp.take_along_axis(p, noted['choice'][..., None],
                                    axis=-1)[..., 0]
        return {'state': (jnp.linalg.norm(noted['state'] - r, axis=-1)
                          / jnp.linalg.norm(r, axis=-1)),
                'weight': jnp.abs(noted['probability'] - p_its) / p_its,
                'choice': chosen != noted['choice']}


def expert_half(lp, u, r_above, shape):
    """What this share gives of the half: the held experts' weighted
    outputs and, on a skip row, ``p_skip u``. -> (y, r)."""
    first, count = held(shape)
    width = sizes(shape)[3]
    chosen, p, r = router(lp['router'], u, r_above, shape)

    def add(y, held_expert):        # one held expert after another
        weights, e = held_expert
        w_e = jnp.where(chosen == first + e, p, 0.0)
        return y + w_e[..., None] * swiglu(weights, u), None
    skip = jnp.where(chosen == width, p, 0.0)[..., None] * u
    y, _ = jax.lax.scan(add, skip, (lp['experts'], jnp.arange(count)))
    return y, r


def merge(vectors, x, f):
    a_r, b_r, a_f, b_f = vectors
    return (a_r * x + b_r) + (a_f * f + b_f)


def embed(ends, tokens, shape):
    return ends['embed'][tokens].astype(jnp.float32)


def router_start(x, shape):
    """The state the first layer's router averages with: none."""
    return jnp.zeros(x.shape[:-1] + (int(shape['router_hidden_size']),),
                     jnp.float32)


def layer(lp, x, r_above, shape):
    """One layer over [B, T, H] float32, rows 0..T-1, the router's state of
    the layer above beside it -> (x, this layer's router state)."""
    with jax.default_matmul_precision('highest'):
        lp, eps = _f32(lp), float(shape['rms_norm_eps'])
        x = merge(lp['merge_attn'], x,
                  cca(lp, rms(x, lp['norm_attn'], eps), shape))
        y, r = expert_half(lp, rms(x, lp['norm_moe'], eps), r_above, shape)
        return merge(lp['merge_moe'], x, y), r


def head(ends, x, shape):
    with jax.default_matmul_precision('highest'):
        y = rms(x, ends['norm_f'].astype(jnp.float32),
                float(shape['rms_norm_eps']))
        return y @ ends['embed'].astype(jnp.float32).T


def forward(params, tokens, shape):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    x = embed(params, tokens, shape)
    r = router_start(x, shape)
    for lp in params['layers']:
        x, r = layer(lp, x, r, shape)
    return head(params, x, shape)
