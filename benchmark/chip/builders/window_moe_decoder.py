"""Builds the program's window/full-attention MoE decoder and its
training step the way a user of the library does:
``mx.models.TransformerLM`` from the library's own constructor of the
published configuration with the layers' specs read from the file, every
block marked ``Block.recompute()``, AdamW, ``parallel.TrainStep(net,
None, opt, forward_fn=...)`` with the model's own loss; then gives it the
benchmark's weights and says which of the program's parameters is which
of the reference's leaves."""

from builders import looped_decoder

_FLAT = {"embed": "tok_embeddings.weight", "final_norm": "norm.gamma",
         "lm_head": "output.weight"}
_LEAF = {"attention_norm": "attention_norm.gamma",
         "ffn_norm": "ffn_norm.gamma",
         "wq": "attention.wq.weight", "wk": "attention.wk.weight",
         "wv": "attention.wv.weight", "wo": "attention.wo.weight",
         "head_gate": "attention.head_gate.weight",
         "mlp_gate": "feed_forward.w1.weight",
         "mlp_up": "feed_forward.w3.weight",
         "mlp_down": "feed_forward.w2.weight",
         "router": "feed_forward.router.weight",
         "w_gate": "feed_forward.experts_w1",
         "w_up": "feed_forward.experts_w3",
         "w_down": "feed_forward.experts_w2",
         "shared_gate": "feed_forward.shared_expert.w1.weight",
         "shared_up": "feed_forward.shared_expert.w3.weight",
         "shared_down": "feed_forward.shared_expert.w2.weight"}
# the configuration file's key for each model-wide field of the library's
# config
_FIELDS = {"vocab_size": "vocab_size", "dim": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
           "hidden_dim": "intermediate_size",
           "max_seq_len": "max_position_embeddings",
           "norm_eps": "rms_norm_eps",
           "moe_num_experts": "router_width",
           "moe_held": "num_experts", "moe_first_held": "first_expert_held",
           "moe_top_k": "num_experts_per_tok",
           "moe_hidden_dim": "moe_intermediate_size",
           "moe_aux_coef": "router_aux_loss_coef"}


def _program_name(ref):
    """``layer3.w_gate`` -> ``layer3.feed_forward.experts_w1``."""
    if ref in _FLAT:
        return _FLAT[ref]
    layer, leaf = ref.split(".")
    return "%s.%s" % (layer, _LEAF[leaf])


def library_config(model):
    """The library's constructor of the published configuration, every
    field the file states set from the file and each layer's spec from
    its per-layer lists (tests/test_bench_window_moe holds the two to
    each other)."""
    from mxnet_tpu.models import laguna_s21_config
    from mxnet_tpu.models.transformer import laguna_layers
    n = model["num_hidden_layers"]
    if not model["norm_topk_prob"] or model["attention_bias"] \
            or model["moe_router_logit_softcapping"] \
            or model["moe_apply_router_weight_on_input"] \
            or set(model["gating_types"][:n]) != {"per_head"} \
            or model["gating"] != "per-head":
        raise RuntimeError("the builder builds per-head gates, gates "
                           "normalised over the top k and applied to the "
                           "experts' outputs, no softcapping, no biases")
    layers = laguna_layers(
        model["layer_types"][:n], model["num_attention_heads_per_layer"][:n],
        model["mlp_layer_types"][:n], model["sliding_window"],
        model["rope_parameters"], model["shared_expert_intermediate_size"],
        model["moe_routed_scaling_factor"])
    return laguna_s21_config(n_layers=n, dtype=model["param_dtype"],
                             layers=layers,
                             **{f: model[k] for f, k in _FIELDS.items()})


class TrainCell(looped_decoder.TrainCell):
    """The compiled step with its state: ``step(tokens, labels)`` is
    ``TrainStep.__call__`` and gives ``(loss, {"ce": (1,), "router_loss",
    "held_pairs"})``, the model's own aux output.  What the driver reads
    of the step it reads as of the looped decoder's cell."""

    def __init__(self, model, weights, kernel_marker="tpu_custom_call"):
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.models import TransformerLM
        from mxnet_tpu.ndarray.ndarray import NDArray
        self._NDArray = NDArray
        net = TransformerLM(library_config(model))
        net.cast(model["param_dtype"])     # the norms' gains too
        for blk in net.layers:
            blk.recompute()
        ps = net.collect_params()
        self.names = {}
        for ref, value in weights.items():
            ps[_program_name(ref)].set_data(NDArray(value))
            self.names[_program_name(ref)] = ref
        unset = [n for n, p in ps.items() if p._data is None]
        if unset:
            raise RuntimeError("parameters the benchmark made no weights "
                               "for: %s" % unset)
        o = model["optimizer"]
        opt = getattr(mx.optimizer, o["name"])(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"], wd=o["wd"])
        self.beta1 = o["beta1"]
        self.net = net
        chunk = model["loss_chunk"]
        self.step = parallel.TrainStep(
            net, None, opt, mesh=None,
            forward_fn=lambda net, tokens, labels: net.loss(
                tokens, labels, chunk=chunk))
        self.kernel_marker = kernel_marker
        self._compiled = None
