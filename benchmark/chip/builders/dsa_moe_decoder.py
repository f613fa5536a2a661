"""Builds the program's sparse-attention MoE decoder and its training step
the way a user of the library does: ``mx.models.TransformerLM`` from the
library's own constructor of the published configuration, every block
marked ``Block.recompute()``, AdamW, ``parallel.TrainStep(net, None, opt,
forward_fn=...)`` with the model's own loss; then gives it the
benchmark's weights and says which of the program's parameters is which
of the reference's leaves."""

from builders import looped_decoder

_FLAT = {"embed": "tok_embeddings.weight", "final_norm": "norm.gamma",
         "lm_head": "output.weight"}
_LEAF = {"attention_norm": "attention_norm.gamma",
         "q_norm": "attention.q_norm.gamma",
         "k_norm": "attention.k_norm.gamma",
         "ffn_norm": "ffn_norm.gamma",
         "wq": "attention.wq.weight", "wk": "attention.wk.weight",
         "wv": "attention.wv.weight", "wo": "attention.wo.weight",
         "idx_wq": "attention.indexer.wq.weight",
         "idx_wk": "attention.indexer.wk.weight",
         "idx_w": "attention.indexer.weights_proj.weight",
         "idx_norm_g": "attention.indexer.k_norm.gamma",
         "idx_norm_b": "attention.indexer.k_norm.beta",
         "router": "feed_forward.router.weight",
         "w_gate": "feed_forward.experts_w1",
         "w_up": "feed_forward.experts_w3",
         "w_down": "feed_forward.experts_w2"}
# the configuration file's key for each field of the library's config
_FIELDS = {"vocab_size": "vocab_size", "dim": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
           "hidden_dim": "intermediate_size",
           "max_seq_len": "max_position_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "moe_num_experts": "num_local_experts",
           "moe_held": "num_experts", "moe_first_held": "first_expert_held",
           "moe_top_k": "num_experts_per_tok",
           "moe_hidden_dim": "moe_intermediate_size",
           "moe_aux_coef": "router_aux_loss_coef"}
_INDEXER = {"index_heads": "indexer_num_heads",
            "index_head_dim": "indexer_head_dim", "index_topk": "topk"}


def _program_name(ref):
    """``layer3.w_gate`` -> ``layer3.feed_forward.experts_w1``."""
    if ref in _FLAT:
        return _FLAT[ref]
    layer, leaf = ref.split(".")
    return "%s.%s" % (layer, _LEAF[leaf])


def library_config(model):
    """The library's constructor of the published configuration, every
    field the file states set from the file (tests/test_bench_dsa_moe
    holds the two to each other)."""
    from mxnet_tpu.models import keye_vl2_30b_a3b_config
    sa = model["sa_config"]
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"] \
            or not model["norm_topk_prob"] \
            or sa["indexer_num_kv_heads"] != 1 \
            or model["index_loss_weight"] != 1.0:
        raise RuntimeError("the builder builds routed experts in every "
                           "layer with gates normalised over the top k, "
                           "one indexer key head and an indexer loss of "
                           "weight 1")
    return keye_vl2_30b_a3b_config(
        n_layers=model["num_hidden_layers"], dtype=model["param_dtype"],
        **{f: model[k] for f, k in _FIELDS.items()},
        **{f: sa[k] for f, k in _INDEXER.items()})


class TrainCell(looped_decoder.TrainCell):
    """The compiled step with its state: ``step(tokens, labels)`` is
    ``TrainStep.__call__`` and gives ``(loss, {"ce": (1,), "index_loss",
    "router_loss", "held_pairs"})``, the model's own aux output (what a
    training loop of such a model logs).  What the driver reads of the
    step (moments, moves, the compiled program's temporaries) it reads
    as of the looped decoder's cell."""

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

    def first_selection(self, tokens):
        """The first layer's selection of the sequence ``tokens`` (T,) under
        the weights the cell holds, by the layer's own ``selection``."""
        net = self.net
        blk = net.layers[0]
        x = net._embed(self._NDArray(tokens[None]))
        return blk.attention.selection(blk.attention_norm(x))._data[0]
