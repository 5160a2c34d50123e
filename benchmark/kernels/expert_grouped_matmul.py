"""Operations and bytes of the grouped expert product
(``expert_grouped_matmul``, ops/expert_grouped_matmul.py), from shapes and
from the program's own counts of what was routed.

One call of a routed layer makes three products over the rows that met a
held expert: gate and up (hidden -> expert width) and down (expert width ->
hidden). What they have to read is the three matrices of every expert that
got a row (an expert TOUCHED; one that got none costs nothing), once, and
the rows moved: each row in at the product's input width and out at its
output width. Counted a layer call:

    rows      rows that met a held expert (``moe.rows_held_total``)
    touched   held experts that got at least one (``moe.experts_touched_total``)
    products  2 x rows x hidden x width, three times
"""
BF16 = 2


def layer_call_cost(rows, touched, hidden, width):
    """(flops, bytes) of the three products of layer calls that together
    routed ``rows`` rows to ``touched`` experts (both may be sums over many
    calls, or means)."""
    flops = 3 * 2 * rows * hidden * width
    weights = touched * 3 * hidden * width * BF16
    moved = rows * BF16 * ((hidden + width) * 2      # gate and up
                           + (width + hidden))       # down
    return flops, weights + moved


def least_seconds(rows, touched, hidden, width, peaks):
    flops, byts = layer_call_cost(rows, touched, hidden, width)
    by_flops = flops / peaks['bf16_flops_per_s']
    by_bytes = byts / peaks['hbm_bytes_per_s']
    return {'seconds': max(by_flops, by_bytes),
            'bound': 'compute' if by_flops >= by_bytes else 'memory'}
