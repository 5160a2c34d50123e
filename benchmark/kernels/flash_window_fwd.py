"""Operations and bytes of the causal flash-attention FORWARD as a served
prefill runs it: one prompt of ``rows`` tokens from row 0, grouped-query
heads, with or without a window (``flash_fwd`` and ``flash_fwd_window``,
ops/flash_attention.py), from shapes.

A row i of a full layer attends keys 0..i and of a window layer its last
``window``: rows (rows + 1) / 2 scores a head, or window (window + 1) / 2 +
(rows - window) window. What the call has to move is q and the output once a
QUERY head, K and V once a KV head, and the rows' log-sum-exp in float32.
That the program pads the prompt to the width of the body it runs, and
stores the log-sum-exp once a lane, is the program's choice and is not
counted as needed.

    products  q K^T and p V over the scores kept: 2 x 2 x scores x head_dim
              a query head (the accepted benchmark/kernels/flash_attention.py
              counts a causal forward the same: 2 x S x S x D)
"""
BF16 = 2
F32 = 4


def scores_kept(rows, window=None):
    """Scores a head keeps over a prompt of ``rows`` tokens."""
    rows = int(rows)
    if window is None or rows <= int(window):
        return rows * (rows + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (rows - w) * w


def call_cost(rows, heads, kv_heads, head_dim, window=None):
    """(flops, bytes) of one call: one layer of one prefill."""
    flops = 2 * 2 * scores_kept(rows, window) * head_dim * heads
    byts = (2 * rows * heads * head_dim * BF16            # q, out
            + 2 * rows * kv_heads * head_dim * BF16       # K, V
            + rows * heads * F32)                         # log-sum-exp
    return flops, byts


def least_seconds(rows_by_prefill, full_layers, window_layers, heads,
                  kv_heads, head_dim, window, peaks):
    """Least time the chip could take for every layer's call of these
    prefills, and which peak binds the most of it."""
    seconds, by = 0.0, {'compute': 0.0, 'memory': 0.0}
    for rows in rows_by_prefill:
        for layers, w in ((full_layers, None), (window_layers, window)):
            flops, byts = call_cost(rows, heads, kv_heads, head_dim, w)
            by_flops = flops / peaks['bf16_flops_per_s']
            by_bytes = byts / peaks['hbm_bytes_per_s']
            seconds += layers * max(by_flops, by_bytes)
            by['compute' if by_flops >= by_bytes else 'memory'] += (
                layers * max(by_flops, by_bytes))
    return {'seconds': seconds, 'bound': max(by, key=by.get)}
