"""The plain reference of the GPT family (GPT-2 / GPT-3: learned positions,
pre-LayerNorm blocks, tanh GELU, tied output head), in straightforward
``jax.numpy`` and float32 with ``jax.default_matmul_precision('highest')``:
no kernel, no cache, no scan, no mixed precision. It imports nothing of the
program and takes nothing the program has made.

It also makes the weights: ``init_params`` is what every run gives the
program AND what the reference computes with, from ``--seed`` alone.

Departures from the published description, all forced by the weights'
layout, none changing the mathematics: the fused QKV projection is packed
per head as [q | k | v] (Megatron's layout), and the layers' weights are
stacked on a leading axis.

    shape = {'vocab_size', 'hidden_size', 'num_layers', 'num_heads',
             'max_seq_len', 'ffn_mult'}
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = 'highest'


def init_params(shape, key):
    """GPT-2's initialisation: N(0, 0.02) matrices, output projections
    scaled by 1/sqrt(2L), positions N(0, 0.01), unit LayerNorm gains."""
    h, v, L = shape['hidden_size'], shape['vocab_size'], shape['num_layers']
    f = h * shape.get('ffn_mult', 4)
    ks = jax.random.split(key, 8)
    kb = jax.random.split(ks[0], 6)
    std = 0.02

    def nrm(k, dims, scale=std):
        return (scale * jax.random.normal(k, dims)).astype(jnp.float32)

    ones, zeros = (lambda *d: jnp.ones(d, jnp.float32),
                   lambda *d: jnp.zeros(d, jnp.float32))
    blocks = {
        'ln1_g': ones(L, h), 'ln1_b': zeros(L, h),
        'qkv_w': nrm(kb[0], (L, h, 3 * h)), 'qkv_b': zeros(L, 3 * h),
        'proj_w': nrm(kb[1], (L, h, h), std / math.sqrt(2 * L)),
        'proj_b': zeros(L, h),
        'ln2_g': ones(L, h), 'ln2_b': zeros(L, h),
        'fc_w': nrm(kb[2], (L, h, f)), 'fc_b': zeros(L, f),
        'out_w': nrm(kb[3], (L, f, h), std / math.sqrt(2 * L)),
        'out_b': zeros(L, h),
    }
    return {'wte': nrm(ks[1], (v, h)),
            'wpe': nrm(ks[2], (shape['max_seq_len'], h), 0.01),
            'blocks': blocks, 'lnf_g': ones(h), 'lnf_b': zeros(h)}


def layer_norm(x, g, b, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(bp, x, heads):
    B, S, h = x.shape
    hd = h // heads
    y = layer_norm(x, bp['ln1_g'], bp['ln1_b'])
    qkv = (y @ bp['qkv_w'] + bp['qkv_b']).reshape(B, S, heads, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(B, S, h) @ bp['proj_w'] + bp['proj_b']
    y = layer_norm(x, bp['ln2_g'], bp['ln2_b'])
    y = gelu(y @ bp['fc_w'] + bp['fc_b']) @ bp['out_w'] + bp['out_b']
    return x + y


def forward(params, tokens, shape, checkpoint_layers=False):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision(HIGHEST):
        S = tokens.shape[1]
        x = params['wte'][tokens] + params['wpe'][:S]
        body = functools.partial(block, heads=shape['num_heads'])
        if checkpoint_layers:
            body = jax.checkpoint(body)
        for i in range(shape['num_layers']):
            x = body({k: w[i] for k, w in params['blocks'].items()}, x)
        x = layer_norm(x, params['lnf_g'], params['lnf_b'])
        return x @ params['wte'].T


def loss(params, tokens, targets, shape, checkpoint_layers=False):
    logits = forward(params, tokens, shape, checkpoint_layers)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def leaf_names(tree):
    return ['/'.join(str(getattr(k, 'key', k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def train_three_steps(shape, seed_key, batches, hyper, devices,
                      checkpoint_layers):
    """AdamW with decoupled decay on every leaf, three steps from the
    seeded weights over ``batches`` (each [B, S+1] int32). The rows of a
    step go through in blocks of one row a device, the gradients summed.
    -> {'loss': [3], 'grad_norm': [leaves], 'delta_norm': [leaves],
        'leaves': names}"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n = len(devices)
    mesh = Mesh(np.array(devices), ('x',))
    rows = NamedSharding(mesh, P('x', None))
    rep = NamedSharding(mesh, P())

    def spread(x):
        # a leaf lies over the devices along its largest axis they divide
        for ax in sorted(range(x.ndim), key=lambda a: -x.shape[a]):
            if n > 1 and x.shape[ax] % n == 0:
                return NamedSharding(mesh, P(*[None] * ax, 'x'))
        return rep

    abstract = jax.eval_shape(lambda k: init_params(shape, k), seed_key)
    layout = jax.tree_util.tree_map(spread, abstract)
    make = jax.jit(lambda k: init_params(shape, k), out_shardings=layout)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                    out_shardings=layout)
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss, shape=shape, checkpoint_layers=checkpoint_layers)),
        out_shardings=(rep, layout))
    add = jax.jit(lambda acc, g, w: jax.tree_util.tree_map(
        lambda a, b: a + w * b, acc, g), donate_argnums=0,
        out_shardings=layout)
    lr, wd = hyper['lr'], hyper['weight_decay']
    b1, b2, eps = hyper['beta1'], hyper['beta2'], hyper['epsilon']

    def adamw(p, g, m, v, t):
        def one(p, g, m, v):
            p = p * (1 - lr * wd)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            p = p - lr * (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return p, m, v
        out = jax.tree_util.tree_map(one, p, g, m, v)
        pick = lambda i: jax.tree_util.tree_map(      # noqa: E731
            lambda _, o: o[i], p, out)
        return pick(0), pick(1), pick(2)
    adamw = jax.jit(adamw, donate_argnums=(0, 2, 3),
                    out_shardings=(layout, layout, layout))
    norms = jax.jit(leaf_norms)
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))

    p = make(seed_key)
    m, v = zeros(p), zeros(p)
    losses, grad_norm = [], None
    for t, batch in enumerate(batches, start=1):
        B = batch.shape[0]
        acc, total = zeros(p), 0.0
        for r in range(0, B, n):
            blk = batch[r:r + n]
            w = blk.shape[0] / B
            toks = jax.device_put(blk[:, :-1], rows)
            tgts = jax.device_put(blk[:, 1:], rows)
            val, g = grad(p, toks, tgts)
            acc = add(acc, g, w)
            total += w * float(val)
        losses.append(total)
        if t == 1:
            grad_norm = np.asarray(norms(acc))
        p, m, v = adamw(p, acc, m, v, float(t))
    p0 = make(seed_key)
    return {'loss': losses, 'grad_norm': grad_norm,
            'delta_norm': np.asarray(delta(p, p0)),
            'leaves': leaf_names(abstract)}
