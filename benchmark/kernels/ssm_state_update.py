"""Operations and bytes of the state-space layer's one-token state update
(``ssm_state_update``, ops/ssm.py), from shapes.

One decode step calls it once a state-space layer. For each sequence it
serves, a head's state ``S`` in R^{P x N} is read once and written once in
float32 (``S = exp(dt A) S + dt x B^T``, ``y = S C``): that is what the
recurrence needs, whatever is done about it. Beside it move ``x`` and ``y``
(a value a channel, bfloat16), ``B`` and ``C`` (N values each, bfloat16)
and ``dt`` (a float32 a head). A slot that decodes nothing needs nothing;
that the program updates every slot's row, busy or idle, is the program's
choice and is not counted as needed.

    operations  a multiply by the decay, a multiply-add of dt x B^T and a
                multiply-add into y: 5 an element of S (on the vector
                unit, not in the matrix unit: the bytes bind by far)
"""
BF16 = 2
F32 = 4


def call_cost(sequences, heads, head_dim, d_state):
    """(flops, bytes) of one call (one layer of one decode step) that
    serves ``sequences`` sequences."""
    state = heads * head_dim * d_state
    flops = 5 * state
    byts = (2 * state * F32                     # S read and written
            + 2 * heads * head_dim * BF16       # x, y
            + 2 * d_state * BF16                # B, C
            + heads * F32)                      # dt
    return sequences * flops, sequences * byts


def least_seconds(sequence_steps, layers, heads, head_dim, d_state, peaks):
    """Least time the chip could take for the calls of ``layers`` layers
    that served ``sequence_steps`` (sequence, decode step) pairs, and which
    peak binds."""
    flops, byts = call_cost(sequence_steps, heads, head_dim, d_state)
    by_flops = layers * flops / peaks['bf16_flops_per_s']
    by_bytes = layers * byts / peaks['hbm_bytes_per_s']
    return {'seconds': max(by_flops, by_bytes),
            'bound': 'compute' if by_flops >= by_bytes else 'memory'}
