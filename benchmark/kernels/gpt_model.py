"""Operations the GPT forward and backward passes require for one token of
training: 6 per matrix-multiplied parameter (forward 2, backward 4) plus
causal attention; recomputed operations are not counted. The embedding
lookup is a gather, the tied output head a multiplication."""


def matmul_params(shape):
    h, L, v = shape['hidden_size'], shape['num_layers'], shape['vocab_size']
    return (4 + 2 * shape.get('ffn_mult', 4)) * L * h * h + v * h


def train_flops_per_token(shape, seq):
    h, L = shape['hidden_size'], shape['num_layers']
    # a query meets seq/2 keys on average: QK^T and PV, 2 flops a
    # multiply-add, forward once and backward twice
    attention = 3 * 2 * 2 * (seq / 2) * h * L
    return 6 * matmul_params(shape) + attention
