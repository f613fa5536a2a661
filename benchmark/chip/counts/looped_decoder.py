"""Operations a looped decoder's training step needs, from the
configuration's shapes alone.  A multiply-accumulate is two operations;
attention is counted causal (half the square)."""


def block_matmul_params(model):
    d, f, hd = (model["hidden_size"], model["intermediate_size"],
                model["head_dim"])
    q = model["num_attention_heads"] * hd
    kv = model["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def applications(model):
    """Block applications a forward pass makes: every layer in every
    pass."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def forward_flops_per_token(model):
    """Every block's projections and SwiGLU in every pass, the head once
    a pass (each exit has logits), and causal attention (QK^T and PV
    over half the square) at the configuration's sequence length.  The
    embedding's lookup and the exit gate (2 x hidden a pass) are left
    out."""
    matmul = 2.0 * (applications(model) * block_matmul_params(model)
                    + model["total_ut_steps"] * model["vocab_size"]
                    * model["hidden_size"])
    attention = applications(model) * 2.0 * model["seq_len"] \
        * model["num_attention_heads"] * model["head_dim"]
    return matmul + attention


def model_flops_per_step(model):
    """The customary model FLOPs of a step: three times the forward's
    (recomputed operations do not count)."""
    return 3 * forward_flops_per_token(model) \
        * model["sequences"] * model["seq_len"]


# matrix products of (T x D) by (D x T) or (T x T) by (T x D) a head that
# each flash kernel has to make: the forward QK^T and PV; the dq kernel
# QK^T, dO V^T and dS K; the dkv kernel QK^T, dO V^T, P^T dO and dS^T Q
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def flash_train_flops(model, calls):
    """Causal FLOPs of the flash kernels for ``calls`` = ``{kernel: how
    often it ran}``, each call one block application over the step's
    sequences."""
    T = model["seq_len"]
    one = 2.0 * (T * (T + 1) / 2) * model["head_dim"] \
        * model["num_attention_heads"] * model["sequences"]
    return sum(FLASH_PRODUCTS[k] * one * n for k, n in calls.items())
