"""Operations and bytes of power retention's one-token state update
(``retention_state_update``, ops/retention.py), from shapes.

One decode step calls it once a layer. For each sequence it serves, a KV
head's state ``S`` in R^{D x d} and its normaliser ``z`` in R^D, D =
d (d + 1) / 2, are read once and written once in float32 (``S = g S +
phi(k) v^T``, ``z = g z + phi(k)``, and each of the group's query heads
reads ``phi(q)^T S`` and ``phi(q)^T z``): that is what the recurrence
needs, whatever is done about it. Beside it move ``q`` and ``y`` (a value a
query head's channel, bfloat16), ``k`` and ``v`` (a value a KV head's
channel, bfloat16) and the gate (a float32 a KV head). A slot that decodes
nothing needs nothing, the zeros that close a layout's last tile (8,320
lanes for 8,256 features) are time and no work, and ``phi(k)`` / ``phi(q)``
written out before the call are the program's choice: none is counted as
needed.

    operations  a multiply by the decay, a multiply-add of phi(k) v^T and a
                multiply-add into each query head's read-out: 3 + 2 G an
                element of S and of z (on the vector unit, not in the
                matrix unit; benchmark/peaks.json holds no vector peak, so
                the bytes' bound is what the share is taken of)
"""
BF16 = 2
F32 = 4


def features(head_dim):
    return head_dim * (head_dim + 1) // 2


def call_cost(sequences, kv_heads, group, head_dim):
    """(flops, bytes) of one call (one layer of one decode step) that
    serves ``sequences`` sequences."""
    state = kv_heads * features(head_dim) * (head_dim + 1)     # S and z
    flops = (3 + 2 * group) * state
    byts = (2 * state * F32                             # read and written
            + 2 * kv_heads * group * head_dim * BF16    # q, y
            + 2 * kv_heads * head_dim * BF16            # k, v
            + kv_heads * F32)                           # the gate
    return sequences * flops, sequences * byts


def least_seconds(sequence_steps, layers, kv_heads, group, head_dim, peaks):
    """Least time the chip could take for the calls of ``layers`` layers
    that served ``sequence_steps`` (sequence, decode step) pairs, and which
    peak binds."""
    flops, byts = call_cost(sequence_steps, kv_heads, group, head_dim)
    by_flops = layers * flops / peaks['bf16_flops_per_s']
    by_bytes = layers * byts / peaks['hbm_bytes_per_s']
    return {'seconds': max(by_flops, by_bytes),
            'bound': 'compute' if by_flops >= by_bytes else 'memory'}
