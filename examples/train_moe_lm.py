"""Train a Mixture-of-Experts TransformerLM with expert parallelism.

Beyond-parity capability (the reference has no MoE, SURVEY.md §2.3):
every second block routes each token to the top 2 of 4 SwiGLU experts
(``models/experts.py``: dropless, sorted by expert, a grouped matmul);
the router's load-balance loss joins the cross-entropy inside the same
trace (``TransformerLM.loss``).

Run on real chips or a virtual mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python examples/train_moe_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.models import TransformerLM, tiny_config


def main():
    mx.np.random.seed(0)
    cfg = tiny_config(n_layers=4, dim=128, hidden_dim=256, n_heads=4,
                      n_kv_heads=2, vocab_size=512,
                      moe_num_experts=4, moe_every=2, moe_top_k=2)
    net = TransformerLM(cfg)
    net.initialize()
    print("params: %.2fM (moe blocks: %d/%d)"
          % (net.num_params() / 1e6,
             sum(type(b.feed_forward).__name__ == "RoutedExperts"
                 for b in net.layers), cfg.n_layers))

    def fwd(net, tokens, labels):
        return net.loss(tokens, labels)[0]

    # a toy copy task: predict the previous token
    rs = onp.random.RandomState(0)
    data = rs.randint(1, cfg.vocab_size, (64, 33)).astype("int32")
    toks = mx.np.array(data[:, :-1])
    labs = mx.np.array(data[:, 1:] * 0 + data[:, :-1])  # copy task

    import jax
    n = len(jax.devices())
    mesh = parallel.create_mesh(dp=n) if n > 1 else None
    step = parallel.TrainStep(net, None,
                              mx.optimizer.AdamW(learning_rate=3e-3),
                              mesh=mesh, forward_fn=fwd)
    for i in range(30):
        loss = float(step(toks, labs))
        if i % 5 == 0:
            print("step %2d  loss %.4f" % (i, loss))
    print("final loss %.4f" % loss)


if __name__ == "__main__":
    main()
