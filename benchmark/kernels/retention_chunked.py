"""Operations and bytes of power retention over a whole prompt (the scope
``brumby.block/retention/chunked``, ops/retention.chunked_retention), from
shapes: the least the DEFINITION asks for, whatever chunks the program
takes it in.

One prefill runs it once a layer over a prompt of ``rows`` tokens from a
zero state. H KV heads, each read by G query heads of size d, D = d (d +
1) / 2 features. Row t (the t-th of the prompt, from 1) reads the rows up
to itself by the cheaper of the definition's two forms, and the state the
prompt leaves is formed once:

    attention form, a query head   (q . k)^2 and the weights times v: 2 x 2 t d
    state's form, a query head     phi(q)^T [S, z]:                   2 D (d + 1)
    the state left, a KV head      phi(k) v^T and phi(k), a row:      2 D (d + 1)

(the attention form is the cheaper up to t = D (d + 1) / (2 d), 4,160 rows
at d = 128). What the call has to move is q in and y out (a value a query
head's channel and row, bfloat16), k and v (a value a KV head's channel and
row), the gate (a float32 a KV head and row) and the final state once,
float32. How many rows the program takes at a time, that it pads the prompt
to the width it runs, computes a chunk's whole square block, reads the
state for every chunk, writes phi(q) out and keeps y in float32 are the
program's choices: time, and no work.
"""
BF16 = 2
F32 = 4


def features(head_dim):
    return head_dim * (head_dim + 1) // 2


def call_cost(rows, kv_heads, group, head_dim):
    """(flops, bytes) of one call: one layer of one prefill."""
    rows = int(rows)
    state = features(head_dim) * (head_dim + 1)         # a KV head's S and z
    read = sum(min(2 * 2 * t * head_dim, 2 * state)
               for t in range(1, rows + 1))
    flops = kv_heads * (group * read + 2 * rows * state)
    byts = (2 * rows * kv_heads * group * head_dim * BF16   # q, y
            + 2 * rows * kv_heads * head_dim * BF16         # k, v
            + rows * kv_heads * F32                         # the gate
            + kv_heads * state * F32)                       # the state left
    return flops, byts


def least_seconds(rows_by_prefill, layers, kv_heads, group, head_dim, peaks):
    """Least time the chip could take for every layer's call of these
    prefills (for each call the longer of its two bounds), and which peak
    binds the most of it."""
    seconds, by = 0.0, {'compute': 0.0, 'memory': 0.0}
    for rows in rows_by_prefill:
        flops, byts = call_cost(rows, kv_heads, group, head_dim)
        by_flops = flops / peaks['bf16_flops_per_s']
        by_bytes = byts / peaks['hbm_bytes_per_s']
        seconds += layers * max(by_flops, by_bytes)
        by['compute' if by_flops >= by_bytes else 'memory'] += (
            layers * max(by_flops, by_bytes))
    return {'seconds': seconds, 'bound': max(by, key=by.get)}
