"""Builds the program's serving replica the way ``chip_smoke.phase_serve``
does: ``TransformerLM`` from the configuration's published sizes,
``mx.serve.Server`` from the replica shape in the configuration's file;
gives the network the benchmark's weights (``Parameter.set_data``: no
float32 draw, so the process's peak stays near the served footprint) and
requires the Pallas kernels in the compiled programs."""


def _program_name(ref):
    """``layer3.w_gate`` -> ``layer3.feed_forward.w1.weight``."""
    flat = {"embed": "tok_embeddings.weight", "final_norm": "norm.gamma",
            "lm_head": "output.weight"}
    if ref in flat:
        return flat[ref]
    layer, leaf = ref.split(".")
    leafs = {"attention_norm": "attention_norm.gamma",
             "ffn_norm": "ffn_norm.gamma",
             "wq": "attention.wq.weight", "wk": "attention.wk.weight",
             "wv": "attention.wv.weight", "wo": "attention.wo.weight",
             "w_gate": "feed_forward.w1.weight",
             "w_up": "feed_forward.w3.weight",
             "w_down": "feed_forward.w2.weight"}
    return "%s.%s" % (layer, leafs[leaf])


class ServeCell:
    """``server`` is the ``mx.serve.Server``; the window drives its
    ``submit()`` / ``result()`` with the engine thread running."""

    def __init__(self, model, weights, kernel_marker="tpu_custom_call"):
        from mxnet_tpu import serve
        from mxnet_tpu.models import TransformerLM
        from mxnet_tpu.models.transformer import LlamaConfig
        from mxnet_tpu.ndarray.ndarray import NDArray
        cfg = LlamaConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            hidden_dim=model["intermediate_size"],
            rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
            max_seq_len=model["max_position_embeddings"],
            dtype=model["torch_dtype"])
        net = TransformerLM(cfg)
        net.setattr("grad_req", "null")
        ps = net.collect_params()
        for ref in list(weights):
            ps[_program_name(ref)].set_data(NDArray(weights.pop(ref)))
        unset = [n for n, p in ps.items() if p._data is None]
        if unset:
            raise RuntimeError("parameters the benchmark made no weights "
                               "for: %s" % unset)
        r = model["replica"]
        self.server = serve.Server(net, serve.ServeConfig(
            slots=r["slots"], page_size=r["page_size"],
            ladder=tuple(r["ladder"]), max_new=r["max_new"],
            pages=r["pages"], int8=r["int8"],
            temperature=r["temperature"]))
        pool = self.server.pool
        programs = {"decode": pool._decode,
                    **{"prefill%d" % T: p for T, p in pool._prefill.items()}}
        if kernel_marker:
            for name, compiled in programs.items():
                if kernel_marker not in compiled.as_text():
                    raise RuntimeError(
                        "%s holds no %s: a dense stand-in was compiled "
                        "where the Pallas kernel belongs"
                        % (name, kernel_marker))
        self.temp_bytes = max(
            int(c.memory_analysis().temp_size_in_bytes)
            for c in programs.values())
        self.compile_s = pool.stats["compile_s"]

    def free(self):
        self.server = None
