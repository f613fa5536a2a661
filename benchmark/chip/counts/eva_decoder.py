"""Operations EvaByte's training step needs, from the configuration's
shapes alone.  A multiply-accumulate is two operations; attention is
counted over the (query, key) pairs the mask lets through: the tokens of
the query's own window up to itself, and one summary a chunk of every
earlier window."""


def block_matmul_params(model):
    d, f = model["hidden_size"], model["intermediate_size"]
    return 4 * d * d + 3 * d * f


def visible_pairs(model):
    """(query, key) pairs a head sees in one sequence, local and remote:
    ``windows x W (W + 1) / 2`` and ``W x (W / c) x (0 + 1 + .. +
    windows - 1)``."""
    T, W, c = model["seq_len"], model["window_size"], model["chunk_size"]
    W = min(W, T)
    nw = T // W
    local = nw * W * (W + 1) // 2
    remote = W * (W // c) * (nw * (nw - 1) // 2)
    return local, remote


def forward_flops_per_sequence(model):
    """Every block's projections and SwiGLU, the eight-predictor head,
    and the attention's two products (q k^T and p v) over the visible
    pairs.  The embedding's lookup and the pooling of the chunks (two
    products of a key with a vector a token) are left out."""
    T = model["seq_len"]
    matmul = 2.0 * T * (
        model["num_hidden_layers"] * block_matmul_params(model)
        + model["num_pred_heads"] * model["vocab_size"]
        * model["hidden_size"])
    attention = model["num_hidden_layers"] * 4.0 * model["hidden_size"] \
        * sum(visible_pairs(model))
    return matmul + attention


def model_flops_per_step(model):
    """The customary model FLOPs of a step: three times the forward's
    (recomputed operations do not count)."""
    return 3 * forward_flops_per_sequence(model) * model["sequences"]


# matrix products over the visible pairs that attention's forward and
# backward have to make, whatever kernels make them: q k^T and p v; then
# q k^T again, dO v^T, dS k (for dq), P^T dO (for dv) and dS^T q (for dk)
ATTENTION_PRODUCTS = 2 + 5


def eva_attention_flops(model):
    """FLOPs a step of EVA attention's products over the visible pairs,
    every layer and sequence: what a kernel with a mask of exactly the
    visible pairs would compute (a kernel that walks whole tiles, or
    runs its forward twice, does more and reads a lower roofline)."""
    return ATTENTION_PRODUCTS * 2.0 * model["hidden_size"] \
        * sum(visible_pairs(model)) * model["num_hidden_layers"] \
        * model["sequences"]
