"""Operations and bytes of the paged latent decode-attention kernel
(``paged_latent_attention``, ops/paged_latent_attention.py), from shapes.

One decode step calls the kernel once a layer. For a slot whose sequence
holds ``rows`` cached rows (the token being decoded included) it has to
read the pages those rows span, once for ALL heads (the latent row has no
heads axis), and nothing of the rest of the pool. What the algorithm needs
of a row is its ``rank + rope`` values (576: 1,152 bytes in bfloat16); that
the program stores a row padded to whole lanes (640) is the program's
choice and is not counted as needed. q and the output are one row a head.

    products  q [c | k_rope]^T over rank + rope and p c over rank:
              2 x heads x rows x (2 x rank + rope)
"""
BF16 = 2


def pages_spanned(rows, page_rows):
    return -(-int(rows) // int(page_rows))


def call_cost(rows_by_slot, heads, rank, rope, page_rows):
    """(flops, bytes) of one call (one layer of one decode step) whose
    active slots hold ``rows_by_slot`` rows each."""
    flops = byts = 0
    for rows in rows_by_slot:
        flops += 2 * heads * int(rows) * (2 * rank + rope)
        byts += pages_spanned(rows, page_rows) * page_rows * (
            rank + rope) * BF16
        byts += heads * (rank + rope) * BF16 + heads * rank * BF16  # q, out
    return flops, byts


def least_seconds(rows_by_slot, layers, heads, rank, rope, page_rows, peaks):
    """Least time the chip could take for every layer's call of the decode
    steps that served these rows, and which peak binds."""
    flops, byts = call_cost(rows_by_slot, heads, rank, rope, page_rows)
    by_flops = layers * flops / peaks['bf16_flops_per_s']
    by_bytes = layers * byts / peaks['hbm_bytes_per_s']
    return {'seconds': max(by_flops, by_bytes),
            'bound': 'compute' if by_flops >= by_bytes else 'memory'}
