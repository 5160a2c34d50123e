"""Operations and bytes of the paged decode-attention kernel over
grouped-query heads, with or without a window (``paged_attention`` and
``paged_attention_window``, ops/paged_attention.py), from shapes.

One decode step calls the kernel once a layer. For a slot whose sequence
holds ``rows`` keys (the token being decoded included) a full layer attends
all of them and a window layer the last ``window``. What the call has to
read is those keys' rows, K and V, once a KV head (the query heads of a
group share them), and nothing of the rest of the pool; that the program
fetches whole pages, the first and the last of which hold rows outside the
window or past the position, is the program's choice and is not counted as
needed. q and the output are one row a QUERY head. A slot that decodes
nothing costs nothing.

    products  q K^T and p V over the keys: 2 x 2 x keys x head_dim a
              query head
"""
BF16 = 2


def keys_attended(rows, window=None):
    rows = int(rows)
    return rows if window is None else min(rows, int(window))


def call_cost(rows_by_slot, heads, kv_heads, head_dim, window=None):
    """(flops, bytes) of one call (one layer of one decode step) whose
    active slots hold ``rows_by_slot`` keys each."""
    flops = byts = 0
    for rows in rows_by_slot:
        keys = keys_attended(rows, window)
        flops += 2 * 2 * keys * head_dim * heads
        byts += 2 * keys * kv_heads * head_dim * BF16             # K and V
        byts += 2 * heads * head_dim * BF16                       # q, out
    return flops, byts


def least_seconds(rows_by_slot, layers, heads, kv_heads, head_dim, window,
                  peaks):
    """Least time the chip could take for the calls of ``layers`` layers of
    the decode steps that served these rows, and which peak binds."""
    flops, byts = call_cost(rows_by_slot, heads, kv_heads, head_dim, window)
    by_flops = layers * flops / peaks['bf16_flops_per_s']
    by_bytes = layers * byts / peaks['hbm_bytes_per_s']
    return {'seconds': max(by_flops, by_bytes),
            'bound': 'compute' if by_flops >= by_bytes else 'memory'}
