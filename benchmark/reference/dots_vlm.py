"""Plain reference of dots.vlm1.inst's language model (rednote-hilab,
config.json of huggingface.co/rednote-hilab/dots.vlm1.inst, model_type
dots_vlm): a pre-norm decoder of multi-head latent attention with YaRN
rotary positions, leading dense SwiGLU layers and routed expert layers
(sigmoid scores, group-limited choice with a correction bias, one shared
expert), an untied head. Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision('highest')``: no kernel, no cache, no scan,
no absorbed form, and nothing of the program is imported.

    x += attn(rms(x)); x += ffn(rms(x)); logits = rms(x) W_head

*Latent attention.* c_q = rms(h W_qa); q = c_q W_qb -> heads of
[q_nope | q_r]; [c_kv | k_r] = h W_kva; c = rms(c_kv); [k_nope | v] = c W_kvb
per head; score = (q_nope.k_nope + rope(q_r).rope(k_r)) * s, causal softmax,
o = p v, out = concat(o) W_o. s = (nope + rope width)^-0.5 * m^2 with
m = 0.1 * mscale_all_dim * ln(factor) + 1 (YaRN's attention factor).

*Routed experts.* s = sigmoid(h W_g^T) over the router's whole width;
s' = s + b; a group's score is the sum of its two largest s'; the
``topk_group`` best groups are kept; the ``num_experts_per_tok`` largest s'
among their experts are chosen (the lower index wins a tie); w_i =
routed_scaling_factor * s_i / (sum of s over ALL chosen + 1e-20);
y = sum over chosen AND held of w_i E_i(h) + E_shared(h),
E(h) = (silu(h W_gate) * (h W_up)) W_down. No capacity, no dropped token.

*The share held.* ``shape['n_routed_experts']`` experts are held here, from
``shape['held_first']`` on, of the ``shape['router_width']`` the router
scores; what the others would add is left out and the partial result goes on
to the next layer, as on one chip of an expert-parallel deployment without
its exchange. An expert's weights follow its place among ALL the experts, so
the shares of every chip add up to the uncut layer. The vocabulary is the
slice the configuration gives.

Departures from the published code, none of which random weights can see:
rotary dims are paired half-split ([x1 | x2] -> [x1 cos - x2 sin | x2 cos +
x1 sin]) where the checkpoint interleaves them: a permutation of W_qb's and
W_kva's rotary columns. Groups outside the kept ones are masked with -inf
where the published code fills 0.0: the same choice while every s' is
positive, as a sigmoid's is beside a small bias. The vision tower and the
multi-token-prediction module are not part of the decoder's forward pass
and are left out.

Weights are made from the seed one leaf at a time and rounded to bfloat16,
the type the configuration serves (``init_params``: what program and
reference both use; the reference widens them). ``init_layer`` makes one
layer alone, so that at the published widths a comparison can hold one
layer's float32 block at a time (``embed`` / ``layer`` / ``head``).
"""
import functools
import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16     # heads whose [T, T] scores are held at once


def counts(shape):
    """(dense layers, expert layers)."""
    dense = int(shape['first_k_dense_replace'])
    return dense, int(shape['num_hidden_layers']) - dense


def held(shape):
    """(first, count) of the routed experts held here, and the router's
    width."""
    return (int(shape.get('held_first', 0)), int(shape['n_routed_experts']),
            int(shape.get('router_width', shape['n_routed_experts'])))


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
    h = int(shape['hidden_size'])
    nh = int(shape['num_attention_heads'])
    dn, dr, dv = (int(shape[x]) for x in (
        'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim'))
    rq, rkv = int(shape['q_lora_rank']), int(shape['kv_lora_rank'])
    lp = {'attn_norm': _gain(next(keys), h),
          'ffn_norm': _gain(next(keys), h),
          'q_a': _normal(next(keys), (h, rq), h ** -0.5),
          'q_a_norm': _gain(next(keys), rq),
          'q_b': _normal(next(keys), (rq, nh * (dn + dr)), rq ** -0.5),
          'kv_a': _normal(next(keys), (h, rkv + dr), h ** -0.5),
          'kv_a_norm': _gain(next(keys), rkv),
          'kv_b': _normal(next(keys), (rkv, nh * (dn + dv)), rkv ** -0.5),
          'o': _normal(next(keys), (nh * dv, h), (nh * dv) ** -0.5)}
    if l < counts(shape)[0]:
        lp['mlp'] = _swiglu_params(keys, h, int(shape['intermediate_size']))
        return lp
    first, count, width = held(shape)
    f = int(shape['moe_intermediate_size'])
    lp['router'] = _normal(next(keys), (width, h), h ** -0.5).astype(
        jnp.float32)
    # small and not zero, so that the order of near scores feels it; small
    # beside the scores' own spread (~0.2), as a trained bias that BALANCES
    # the experts' load is: at 0.02 a random one unbalanced it, the share
    # of rows that met the 16 held experts swung +-7 % with the seed, and
    # a decode step's time with it (PERF.md section 6, PR 27)
    lp['router_bias'] = _normal(next(keys), (width,), 0.002, 'float32')
    kg, ku, kd = next(keys), next(keys), next(keys)
    lp['experts'] = {'gate': _experts(kg, first, count, (h, f), h ** -0.5),
                     'up': _experts(ku, first, count, (h, f), h ** -0.5),
                     'down': _experts(kd, first, count, (f, h), f ** -0.5)}
    lp['shared'] = _swiglu_params(
        keys, h, f * int(shape.get('n_shared_experts', 1)))
    return lp


def init_ends(shape, key):
    """Embedding, final norm and head, over the vocabulary slice."""
    k = jax.random.fold_in(key, 0)
    v, h = int(shape['vocab_size']), int(shape['hidden_size'])
    return {'embed': _normal(jax.random.fold_in(k, 0), (v, h), 1.0),
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


def yarn_inv_freq(shape):
    """The rotary frequencies, [rope width / 2], as published for YaRN."""
    d = int(shape['qk_rope_head_dim'])
    base = float(shape['rope_theta'])
    rs = shape['rope_scaling']
    factor, orig = float(rs['factor']), float(
        rs['original_max_position_embeddings'])

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    lo = max(math.floor(dim_of(float(rs['beta_fast']))), 0)
    hi = min(math.ceil(dim_of(float(rs['beta_slow']))), d - 1)
    if lo == hi:
        hi += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / d)
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(shape):
    rs = shape['rope_scaling']
    m = yarn_mscale(float(rs['factor']), float(rs['mscale_all_dim']))
    return (int(shape['qk_nope_head_dim'])
            + int(shape['qk_rope_head_dim'])) ** -0.5 * m * m


def rope(x, positions, shape):
    """x [..., T, d] (or [..., T, heads, d] with ``positions`` [T, 1]),
    half-split pairing."""
    rs = shape['rope_scaling']
    factor = float(rs['factor'])
    mult = (yarn_mscale(factor, float(rs['mscale']))
            / yarn_mscale(factor, float(rs['mscale_all_dim'])))
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(shape)
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(lp, h, shape):
    """h [B, T, H] float32, positions 0..T-1 -> [B, T, H]."""
    b, t, _ = h.shape
    nh = int(shape['num_attention_heads'])
    dn, dr, dv = (int(shape[x]) for x in (
        'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim'))
    rkv, eps = int(shape['kv_lora_rank']), float(shape['rms_norm_eps'])
    pos = jnp.arange(t)
    q = (rms(h @ lp['q_a'], lp['q_a_norm'], eps) @ lp['q_b']).reshape(
        b, t, nh, dn + dr)
    kv = h @ lp['kv_a']
    c = rms(kv[..., :rkv], lp['kv_a_norm'], eps)
    k_rope = rope(kv[..., rkv:], pos, shape)                  # [B, T, dr]
    q_nope = q[..., :dn]
    q_rope = rope(q[..., dn:], pos[:, None], shape)           # [B, T, nh, dr]
    kvb = (c @ lp['kv_b']).reshape(b, t, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    causal = pos[:, None] >= pos[None, :]
    out = []
    for h0 in range(0, nh, HEAD_BLOCK):     # in blocks, so that it fits
        hs = slice(h0, h0 + HEAD_BLOCK)
        s = (jnp.einsum('bqhd,bkhd->bhqk', q_nope[:, :, hs], k_nope[:, :, hs])
             + jnp.einsum('bqhd,bkd->bhqk', q_rope[:, :, hs], k_rope)
             ) * softmax_scale(shape)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum('bhqk,bkhd->bqhd', p, v[:, :, hs]))
    return jnp.concatenate(out, axis=2).reshape(b, t, nh * dv) @ lp['o']


def swiglu(p, h):
    return (jax.nn.silu(h @ p['gate']) * (h @ p['up'])) @ p['down']


def route(h, router, bias, shape):
    """-> (chosen experts [..., k] int32, their weights [..., k])."""
    k, groups = int(shape['num_experts_per_tok']), int(shape['n_group'])
    s = jax.nn.sigmoid(h @ router.T)
    biased = s + bias
    by_group = biased.reshape(biased.shape[:-1] + (groups, -1))
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, int(shape['topk_group']))[1]
    keep = jnp.any(kept[..., None] == jnp.arange(groups), axis=-2)
    masked = jnp.where(keep[..., None], by_group, -jnp.inf).reshape(
        biased.shape)
    chosen = jax.lax.top_k(masked, k)[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if shape.get('norm_topk_prob', True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(shape['routed_scaling_factor'])


def routed_experts(lp, h, shape):
    """The part of the expert layer this share gives: the held experts'
    weighted outputs and the shared expert's."""
    first, count, _ = held(shape)
    chosen, w = route(h, lp['router'], lp['router_bias'], shape)
    y = swiglu(lp['shared'], h)
    for e in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        y = y + w_e[..., None] * swiglu(
            {k: v[e] for k, v in lp['experts'].items()}, h)
    return y


def embed(ends, tokens):
    return ends['embed'][tokens].astype(jnp.float32)


def layer(lp, x, shape):
    """One layer over [B, T, H] float32 at positions 0..T-1."""
    with jax.default_matmul_precision('highest'):
        lp, eps = _f32(lp), float(shape['rms_norm_eps'])
        x = x + attention(lp, rms(x, lp['attn_norm'], eps), shape)
        y = rms(x, lp['ffn_norm'], eps)
        return x + (swiglu(lp['mlp'], y) if 'mlp' in lp
                    else routed_experts(lp, y, shape))


def head(ends, x, shape):
    with jax.default_matmul_precision('highest'):
        return rms(x, ends['norm_f'].astype(jnp.float32),
                   float(shape['rms_norm_eps'])) @ ends['head'].astype(
                       jnp.float32)


def forward(params, tokens, shape):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    x = embed(params, tokens)
    for lp in params['layers']:
        x = layer(lp, x, shape)
    return head(params, x, shape)
