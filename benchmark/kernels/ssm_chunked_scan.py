"""Operations and bytes of the state-space layer's recurrence over a whole
prompt in its chunked form (the scope ``granite.block/ssm/scan``,
ops/ssm.chunked_scan), from shapes.

One prefill runs it once a state-space layer over a prompt of ``rows``
tokens from a zero state, in chunks of ``chunk`` rows (the last one
partial). For a chunk of q rows, H heads of size P, one group of d_state N:

    C B^T                       2 q (q + 1) / 2 N   (row t needs s <= t)
    decay-weighted (C B^T) x    2 q (q + 1) / 2 H P
    the chunk's own state       2 q H P N
    the state before it, by C   2 q H P N           (not in the first chunk)

What the call has to move is x in and y out (a value a channel and row,
bfloat16), B and C (N values a row each, bfloat16), dt (a float32 a head
and row) and the final state once, float32. That the program pads the
prompt to the width of the body it runs, computes the whole q x q block of
a chunk and keeps y in float32 is the program's choice and is not counted
as needed.
"""
BF16 = 2
F32 = 4


def chunks_of(rows, chunk):
    rows, chunk = int(rows), int(chunk)
    return [chunk] * (rows // chunk) + ([rows % chunk] if rows % chunk
                                        else [])


def call_cost(rows, heads, head_dim, d_state, chunk):
    """(flops, bytes) of one call: one layer of one prefill."""
    channels = heads * head_dim
    flops = 0
    for i, q in enumerate(chunks_of(rows, chunk)):
        pairs = q * (q + 1) // 2
        flops += 2 * pairs * d_state + 2 * pairs * channels
        flops += 2 * q * channels * d_state * (1 if i == 0 else 2)
    byts = (2 * rows * channels * BF16          # x, y
            + 2 * rows * d_state * BF16         # B, C
            + rows * heads * F32                # dt
            + channels * d_state * F32)         # the state it leaves
    return flops, byts


def least_seconds(rows_by_prefill, layers, heads, head_dim, d_state, chunk,
                  peaks):
    """Least time the chip could take for every state-space layer's call of
    these prefills (for each call the longer of its two bounds), and which
    peak binds the most of it."""
    seconds, by = 0.0, {'compute': 0.0, 'memory': 0.0}
    for rows in rows_by_prefill:
        flops, byts = call_cost(rows, heads, head_dim, d_state, chunk)
        by_flops = flops / peaks['bf16_flops_per_s']
        by_bytes = byts / peaks['hbm_bytes_per_s']
        seconds += layers * max(by_flops, by_bytes)
        by['compute' if by_flops >= by_bytes else 'memory'] += (
            layers * max(by_flops, by_bytes))
    return {'seconds': seconds, 'bound': max(by, key=by.get)}
