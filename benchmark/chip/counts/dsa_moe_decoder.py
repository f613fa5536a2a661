"""Operations the training step of a sparse-attention MoE decoder needs,
from the configuration's shapes alone.  A multiply-accumulate is two
operations.  Attention is counted over the selected (query, key) pairs,
``min(topk, t + 1)`` a query; the indexer's scores over every causal
pair; the routed experts by the (token, expert) pairs that reach an
expert held here."""


def _sizes(model):
    sa = model["sa_config"]
    return (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def selected_pairs(model):
    """(query, key) pairs one sequence's attention runs over, a layer:
    ``sum_t min(topk, t + 1)``; 65,012,736 at 32,768 tokens."""
    T = model["seq_len"]
    K = min(_sizes(model)[6], T)
    return K * (K + 1) // 2 + (T - K) * K


def causal_pairs(model):
    T = model["seq_len"]
    return T * (T + 1) // 2


def expected_held_pairs_per_token(model):
    """Routed pairs a token sends to the experts held here where routing
    is uniform: ``top_k * held / experts`` (1 at 8 of 128, 16 held)."""
    return model["num_experts_per_tok"] * model["num_experts"] \
        / model["num_local_experts"]


def layer_params_per_token(model):
    """Weights a token's projections multiply in one layer: the
    attention's, the indexer's, the router's, and its expected held
    experts' SwiGLU."""
    d, H, G, D, Hi, Di, _ = _sizes(model)
    attention = d * H * D + 2 * d * G * D + H * D * d
    indexer = d * Hi * Di + d * Di + d * Hi
    router = d * model["num_local_experts"]
    experts = expected_held_pairs_per_token(model) * 3 * d \
        * model["moe_intermediate_size"]
    return attention + indexer + router + experts


# matrix products over the selected pairs that the sparse attention's
# forward and backward have to make, whatever kernels make them: q k^T
# and p v; then q k^T again, dO v^T, dS k (dq), P^T dO (dv), dS^T q (dk)
ATTENTION_PRODUCTS = 2 + 5
# the experts' products of a routed pair: three forward (gate, up, down),
# six backward (each one's two gradients)
EXPERT_PRODUCTS = 3 + 6


def sparse_attn_flops(model):
    """FLOPs a step of the sparse attention's products over the selected
    pairs, every layer and sequence."""
    _, H, _, D, _, _, _ = _sizes(model)
    return ATTENTION_PRODUCTS * 2.0 * H * D * selected_pairs(model) \
        * model["num_hidden_layers"] * model["sequences"]


def index_score_flops(model):
    """FLOPs of one scoring of every causal pair by the indexer (the
    ``dsa_index`` kernel's work a step): each head's q . k."""
    _, _, _, _, Hi, Di, _ = _sizes(model)
    return 2.0 * Hi * Di * causal_pairs(model) \
        * model["num_hidden_layers"] * model["sequences"]


def index_flops(model):
    """The indexer's attention-like work a step: the scores of every
    causal pair once, and the two products of its loss's backward (d q,
    d k) over the selected pairs."""
    _, _, _, _, Hi, Di, _ = _sizes(model)
    backward = 2 * 2.0 * Hi * Di * selected_pairs(model) \
        * model["num_hidden_layers"] * model["sequences"]
    return index_score_flops(model) + backward


def experts_flops(model, pairs):
    """FLOPs of the held experts' products for ``pairs`` routed (token,
    expert) pairs (summed over the layers)."""
    return EXPERT_PRODUCTS * 2.0 * model["hidden_size"] \
        * model["moe_intermediate_size"] * pairs


def model_flops_per_step(model):
    """The model FLOPs of a step: three times the forward's projections
    (with the expected held experts, 1 pair a token at uniform routing:
    the realised count is the step's aux output, read by the experts'
    roofline) and head, plus the sparse attention's seven products over
    the selected pairs and the indexer's work.  Recomputed operations do
    not count."""
    tokens = model["seq_len"] * model["sequences"]
    matmul = 2.0 * tokens * (
        model["num_hidden_layers"] * layer_params_per_token(model)
        + model["vocab_size"] * model["hidden_size"])
    return 3 * matmul + sparse_attn_flops(model) + index_flops(model)
