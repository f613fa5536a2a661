"""Operations the training step of a window/full-attention MoE decoder
needs, from the configuration's shapes alone.  A multiply-accumulate is
two operations.  A full layer's attention is counted over the causal
(query, key) pairs, a sliding layer's over the band's, ``min(window, t +
1)`` a query; the routed experts by the (token, expert) pairs that reach
an expert held here."""


def _layers(model):
    """``[(sliding, heads, dense)]`` of the layers held here."""
    n = model["num_hidden_layers"]
    return [(kind == "sliding_attention", heads, mlp == "dense")
            for kind, heads, mlp in zip(
                model["layer_types"][:n],
                model["num_attention_heads_per_layer"][:n],
                model["mlp_layer_types"][:n])]


def band_pairs(model):
    """(query, key) pairs one sequence's sliding layer attends over:
    ``sum_t min(window, t + 1)``; 4,063,488 at 8,192 tokens."""
    T, W = model["seq_len"], min(model["sliding_window"], model["seq_len"])
    return W * (W + 1) // 2 + (T - W) * W


def causal_pairs(model):
    T = model["seq_len"]
    return T * (T + 1) // 2


def expected_held_pairs_per_token(model):
    """Routed pairs a token sends to the experts held here where routing
    is uniform: ``top_k * held / experts`` (0.3125 at 10 of 256, 8
    held)."""
    return model["num_experts_per_tok"] * model["num_experts"] \
        / model["router_width"]


def layer_params_per_token(model, heads, dense):
    """Weights a token's projections multiply in one layer: attention
    and head gate, then the dense SwiGLU, or the router, the shared
    expert and its expected held experts."""
    d, G, D = (model["hidden_size"], model["num_key_value_heads"],
               model["head_dim"])
    attention = 2 * d * heads * D + 2 * d * G * D + d * heads
    if dense:
        return attention + 3 * d * model["intermediate_size"]
    return attention + d * model["router_width"] \
        + 3 * d * model["shared_expert_intermediate_size"] \
        + expected_held_pairs_per_token(model) * 3 * d \
        * model["moe_intermediate_size"]


# matrix products over the band's pairs that the window attention's
# forward and backward have to make, whatever kernels make them: q k^T
# and p v; then q k^T again, dO v^T, dS k (dq), P^T dO (dv), dS^T q (dk)
ATTENTION_PRODUCTS = 2 + 5
# the experts' products of a routed pair: three forward (gate, up, down),
# six backward (each one's two gradients)
EXPERT_PRODUCTS = 3 + 6
# products of (T x D) by (D x T) or (T x T) by (T x D) a head that each
# flash kernel makes (counts/looped_decoder.py's count of the same kernels)
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def window_attn_flops(model):
    """FLOPs a step of the window attention's products over the band's
    pairs, every sliding layer and sequence."""
    heads = sum(h for sliding, h, _ in _layers(model) if sliding)
    return ATTENTION_PRODUCTS * 2.0 * heads * model["head_dim"] \
        * band_pairs(model) * model["sequences"]


def flash_train_flops(model, calls):
    """Causal FLOPs of the full layers' flash kernels for ``calls`` =
    ``{kernel: how often it ran}``, each call one full layer's attention
    over the step's sequences (every full layer has the same heads)."""
    heads = {h for sliding, h, _ in _layers(model) if not sliding}
    if len(heads) != 1:
        raise ValueError("full layers of %s heads: a call's count is not "
                         "one number" % sorted(heads))
    one = 2.0 * causal_pairs(model) * model["head_dim"] * heads.pop() \
        * model["sequences"]
    return sum(FLASH_PRODUCTS[k] * one * n for k, n in calls.items())


def experts_flops(model, pairs):
    """FLOPs of the held experts' products for ``pairs`` routed (token,
    expert) pairs (summed over the layers)."""
    return EXPERT_PRODUCTS * 2.0 * model["hidden_size"] \
        * model["moe_intermediate_size"] * pairs


def model_flops_per_step(model):
    """The customary model FLOPs of a step, three times the forward's:
    the projections (with the expected held experts, 0.3125 pairs a token
    at uniform routing: the realised count is the step's aux output, read
    by the experts' roofline) and head, and the attention's two products
    over each layer's visible pairs.  Recomputed operations do not
    count."""
    tokens = model["seq_len"] * model["sequences"]
    d, D = model["hidden_size"], model["head_dim"]
    weights = model["vocab_size"] * d
    pairs = 0
    for sliding, heads, dense in _layers(model):
        weights += layer_params_per_token(model, heads, dense)
        pairs += heads * (band_pairs(model) if sliding
                          else causal_pairs(model))
    forward = 2.0 * tokens * weights \
        + 2 * 2.0 * D * pairs * model["sequences"]
    return 3 * forward
