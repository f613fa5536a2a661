"""Operations and bytes a decoder's serving steps need, from the
configuration's shapes and the true lengths of what was processed;
padding and whatever a kernel reads beyond the live cache do not count.
"""


def _dims(model):
    d, h = model["hidden_size"], model["num_attention_heads"]
    return d, h, model["num_key_value_heads"], d // h, \
        model["num_hidden_layers"]


def matmul_params(model):
    """Parameters every token is multiplied with: the layers' matrices
    and the output head; not the embedding table, which is a lookup."""
    d, h, kv, hd, L = _dims(model)
    f = model["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * model["vocab_size"]


def kv_bytes_per_token(model, itemsize=2):
    d, h, kv, hd, L = _dims(model)
    return 2 * kv * hd * itemsize * L


def prefill_attention_flops(model, true_len):
    """Causal attention over ``true_len`` tokens: QK^T and PV over the
    lower triangle, every head and layer."""
    d, h, kv, hd, L = _dims(model)
    pairs = true_len * (true_len + 1) / 2.0
    return 2 * 2.0 * pairs * hd * h * L


def decode_attention_flops(model, context_tokens):
    """One new token per sequence against ``context_tokens`` cached
    positions in total: QK^T and PV."""
    d, h, kv, hd, L = _dims(model)
    return 2 * 2.0 * context_tokens * hd * h * L


def decode_kv_read_bytes(model, context_tokens):
    """The live K and V that decode steps had to read."""
    return context_tokens * kv_bytes_per_token(model)


def step_flops(model, calls):
    """Model FLOPs of the logged calls: two per matrix parameter per
    token processed (true prompt tokens, one token per active slot), and
    attention at the true lengths."""
    p = matmul_params(model)
    total = 0.0
    for c in calls:
        if c["kind"] == "prefill":
            total += 2.0 * p * c["true"] \
                + prefill_attention_flops(model, c["true"])
        else:
            total += 2.0 * p * c["active"] \
                + decode_attention_flops(model, c["context"])
    return total
