"""The one general traffic generator.  A traffic mix is a data file under
``traffic/``; this turns it and a seed into the inputs the program is
given, and nothing else of the mix reaches the program.

Every seed gets the same schedule: the lengths and the gaps between
arrivals come from the mix's own ``sizes_seed``, in the order drawn.  The
run's seed draws the token ids and the pixels (and, in ``common``, the
weights): it changes what is computed on, not how much work a run holds
nor when it arrives.  (Measured, PR 25: with the schedule's order drawn
from the run's seed too, two runs of one seed agreed within 0.1% in
tokens/s while six seeds spread by 10%, and ``ttft_p95_ms`` by 33%.)
"""
import numpy as onp


def _rng(seed, salt=0):
    return onp.random.RandomState((int(seed) + salt) % (2 ** 32))


def _draw(dist, n, rng):
    """``n`` whole numbers from a length distribution of the data file,
    clipped to its ``min`` and ``max``."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = onp.exp(rng.normal(onp.log(dist["median"]), dist["sigma"], n))
    elif kind == "uniform":
        v = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError("no length distribution %r" % kind)
    return onp.clip(onp.floor(v), dist["min"], dist["max"]).astype(int)


def request_plan(mix, seed, vocab, seconds):
    """The requests of one run of a serving mix: a list of
    ``{"due": seconds from the start or None, "prompt": [ids], "out": n}``.

    ``arrivals.process`` is ``closed`` (``clients`` callers, each sending
    its next request when the last is delivered: ``due`` is None) or
    ``open`` (arrivals on a schedule at ``rate_per_s`` whether or not
    earlier ones finished; gaps are gamma with coefficient of variation
    ``cv``, so ``cv`` 1 is a Poisson process and above 1 is bursty)."""
    arr = mix["arrivals"]
    horizon = mix["warmup_s"] + seconds
    if arr["process"] == "open":
        n = int(onp.ceil(arr["rate_per_s"] * horizon * 1.2)) + 8
    else:
        n = int(arr["requests_per_s_at_most"] * horizon) + arr["clients"]
    while True:
        sizes = _rng(mix["sizes_seed"])
        prompts = _draw(mix["prompt_tokens"], n, sizes)
        outs = _draw(mix["output_tokens"], n, sizes)
        dues = [None] * n
        if arr["process"] != "open":
            break
        cv = float(arr.get("cv", 1.0))
        dues = onp.cumsum(sizes.gamma(1.0 / cv ** 2,
                                      cv ** 2 / arr["rate_per_s"],
                                      n)).tolist()
        if dues[-1] >= horizon:
            break
        n = n + n // 4 + 1     # the gaps fell short of the window: more
    ids = _rng(seed, 3)
    return [{"due": dues[i], "out": int(outs[i]),
             "prompt": ids.randint(1, vocab, int(prompts[i])).tolist()}
            for i in range(n)]


def image_ring(mix, seed, batch, size, classes, dtype):
    """``(x, y)``: a ring of ``mix["ring"]`` seeded batches made on the
    device in one jitted call, rows all different: ``x`` (ring, batch, 3,
    size, size) uniform in [0, 1), ``y`` (ring, batch) labels."""
    import jax
    import jax.numpy as jnp
    k = mix["ring"]

    @jax.jit
    def make(s):
        kx, ky = jax.random.split(jax.random.key(s))
        x = jax.random.uniform(kx, (k, batch, 3, size, size), jnp.float32)
        y = jax.random.randint(ky, (k, batch), 0, classes, jnp.int32)
        return x.astype(dtype), y

    return make(onp.uint32(int(seed) % (2 ** 32)))
