"""Operations and bytes of the causal flash-attention kernels, from shapes.

One layer of one training step calls the forward kernel (and, under
rematerialisation, calls it again in the backward pass), the dq kernel and
the dkv kernel, each over [B, H, S, D] in bf16 on one device. A causal
matrix product over S x S touches half the tiles: S*S*D flops instead of
2*S*S*D.

    forward  QK^T, PV                          2 products
    dq       QK^T, dO V^T, dS K                3 products
    dkv      QK^T, dO V^T, P^T dO, dS^T Q      4 products
"""
BF16 = 2
F32 = 4
PRODUCTS = {'fwd': 2, 'dq': 3, 'dkv': 4}
# [S, D] bf16 tensors read and written, and [S] float32 rows (lse, delta)
TENSORS = {'fwd': (4, 1), 'dq': (6, 2), 'dkv': (7, 2)}


def call_cost(variant, batch, heads, seq, head_dim):
    """(flops, bytes) of one call of one variant."""
    flops = PRODUCTS[variant] * batch * heads * seq * seq * head_dim
    wide, rows = TENSORS[variant]
    byts = batch * heads * (wide * seq * head_dim * BF16 + rows * seq * F32)
    return flops, byts


def least_seconds(facts, steps, peaks):
    """Least time one device of the cell's mesh could take for the kernel
    calls of ``steps`` training steps (every layer's mix), and which peak
    bounds each variant."""
    mesh = facts.get('mesh', {})
    shape = facts['shape']
    heads = shape['num_heads'] // mesh.get('mp', 1)
    batch = facts['batch'] // mesh.get('dp', 1)
    head_dim = shape['hidden_size'] // shape['num_heads']
    mix = ['fwd', 'dq', 'dkv']
    if facts.get('remat_policy') in ('dots', 'full'):
        mix.append('fwd')        # the backward pass runs the forward again
    seconds, bound = 0.0, {}
    for v in mix:
        flops, byts = call_cost(v, batch, heads, facts['seq'], head_dim)
        by_flops = flops / peaks['bf16_flops_per_s']
        by_bytes = byts / peaks['hbm_bytes_per_s']
        seconds += max(by_flops, by_bytes)
        bound[v] = 'compute' if by_flops >= by_bytes else 'memory'
    return {'seconds': seconds * facts['layers'] * steps, 'bound': bound}
