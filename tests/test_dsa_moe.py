"""DeepSeek Sparse Attention (``models/dsa.py``, the ``dsa_fwd`` /
``dsa_bwd`` / ``dsa_masked_*`` / ``dsa_index`` kernels of ``ops/pallas_ops.py``) and routed
experts (``models/experts.py``) against straightforward float32
``jax.numpy`` at toy size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.models import TransformerLM, dsa, experts, tiny_config
from mxnet_tpu.ops import pallas_ops


def _normal(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _dense_dsa(q, k, v, qi, ki, w, topk):
    """One sequence: the scores of every pair, the top ``topk`` of each
    row by ``lax.top_k``, one dense softmax masked to them, and the
    indexer's KL against the heads' mean weight."""
    T, H, D = q.shape
    rep = H // k.shape[1]
    s = jnp.einsum("tjs,tj->ts", jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi,
                                                        ki)), w)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    _, idx = jax.lax.top_k(s, topk)
    n_valid = jnp.minimum(topk, jnp.arange(T) + 1)
    sel = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(
        jnp.arange(topk)[None, :] < n_valid[:, None])
    a = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, rep, 1)) / jnp.sqrt(D)
    a = jax.nn.softmax(jnp.where(sel[None], a, -jnp.inf), -1)
    o = jnp.einsum("hts,shd->thd", a, jnp.repeat(v, rep, 1))
    p = jax.lax.stop_gradient(jnp.where(sel, jnp.mean(a, 0), 0.0))
    logq = jnp.where(sel, jax.nn.log_softmax(jnp.where(sel, s, -jnp.inf)),
                     0.0)
    kl = jnp.sum(jnp.where(sel, jax.scipy.special.xlogy(p, p) - p * logq,
                           0.0), -1)
    return o, jnp.mean(kl), sel


def _dsa_inputs(T=64, H=4, G=2, D=16, Hi=2, Di=8):
    q, k, v = _normal(1, T, H, D), _normal(2, T, G, D), _normal(3, T, G, D)
    qi, ki, w = _normal(4, T, Hi, Di), _normal(5, T, Di), _normal(6, T, Hi)
    return q, k, v, qi, ki, w


def test_dsa_output_loss_and_every_gradient_match_the_dense_form():
    args = _dsa_inputs()
    r = _normal(7, *args[0].shape)

    def program(q, k, v, qi, ki, w):
        o, loss = dsa.dsa_attention(q[None], k[None], v[None], qi[None],
                                    ki[None], w[None], 16)
        return jnp.sum(o[0] * r) + loss

    def plain(q, k, v, qi, ki, w):
        o, loss, _ = _dense_dsa(q, k, v, qi, ki, w, 16)
        return jnp.sum(o * r) + loss

    with jax.default_matmul_precision("highest"):
        assert abs(float(program(*args)) - float(plain(*args))) < 1e-4
        got = jax.jit(jax.grad(program, argnums=range(6)))(*args)
        want = jax.jit(jax.grad(plain, argnums=range(6)))(*args)
    for name, g, w_ in zip("q k v qi ki w".split(), got, want):
        scale = float(jnp.max(jnp.abs(w_)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(g - w_))) < 1e-4 * max(scale, 1), name


def test_the_indexer_loss_reaches_only_the_indexer():
    """The selection is discrete: the attention's output carries no
    gradient to the indexer, and the indexer's loss none to q, k, v."""
    args = _dsa_inputs()

    def part(which):
        def f(*a):
            o, loss = dsa.dsa_attention(*(x[None] for x in a), 16)
            return jnp.sum(o) if which == "out" else loss
        return jax.grad(f, argnums=range(6))(*args)

    out, loss = part("out"), part("loss")
    for i in range(3):
        assert float(jnp.abs(out[i]).max()) > 0
        assert float(jnp.abs(loss[i]).max()) == 0
    for i in range(3, 6):
        assert float(jnp.abs(out[i]).max()) == 0
        assert float(jnp.abs(loss[i]).max()) > 0


def _selection(T, K, hot=False, ragged=False):
    """A causal top-``K`` of seeded scores: ``(idx, n_valid, the (T, T)
    mask of the selected pairs)``.  ``hot``: every query keeps key 0;
    ``ragged``: many queries keep fewer than they could, so their last
    slots are empty (their indices still name rows)."""
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None],
                  _normal(4, T, T), -jnp.inf)
    if hot:
        s = s.at[:, 0].set(1e9)
    _, idx = jax.lax.top_k(s, K)
    n_valid = jnp.minimum(K, jnp.arange(T) + 1)
    if ragged:
        n_valid = jnp.maximum(1, n_valid - jnp.arange(T) % 5)
    sel = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(
        jnp.arange(K)[None, :] < n_valid[:, None])
    return idx, n_valid, sel


@pytest.mark.parametrize("T,G,K,hot,ragged,path", [
    (32, 2, 8, False, False, "chunked"),
    (32, 2, 8, True, False, "chunked"),  # many adds into one row
    (32, 2, 16, False, True, "chunked"),  # empty slots
    (32, 1, 8, False, False, "chunked"),
    (32, 4, 8, True, True, "chunked"),
    (32, 2, 12, False, False, "chunked"),   # K no multiple of 8
    (256, 2, 16, True, False, "masked"),
    (512, 2, 16, False, True, "masked"),    # and future tiles skipped
    (256, 1, 16, False, False, "masked"),
    (256, 4, 16, True, True, "masked"),
], ids=["base", "hot_key", "empty_slots", "g1", "g4", "fallback",
        "masked_hot_key", "masked_empty_slots", "masked_g1", "masked_g4"])
def test_the_sparse_kernels_interpreted_match_attention_masked_to_the_set(
        monkeypatch, T, G, K, hot, ragged, path):
    """The two backward paths (the walk over the causal tiles under the
    selection's bits; the chunks of XLA-gathered rows and their scatter)
    against one softmax masked to the selection: output and all three
    gradients.  The masked walk at 128-query and 256-key tiles: one key
    tile at 256 tokens, two at 512."""
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    monkeypatch.setattr(pallas_ops, "_DSA_MASKED_TILES", ((128,), (256,)))
    H = 4
    q, k, v = _normal(1, T, H, 128), _normal(2, T, G, 128), \
        _normal(3, T, G, 128)
    idx, n_valid, sel = _selection(T, K, hot, ragged)
    bits = pallas_ops.pack_selection(sel) if path == "masked" else None
    r = _normal(5, T, H, 128)

    def kernel(q, k, v):
        return jnp.sum(pallas_ops.sparse_attention(
            q, k, v, idx, n_valid, chunk=16 if bits is None else 128,
            bits=bits)[0] * r)

    def masked(q, k, v):
        a = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, H // G, 1)) \
            / jnp.sqrt(128.0)
        a = jax.nn.softmax(jnp.where(sel[None], a, -jnp.inf), -1)
        return jnp.sum(jnp.einsum("hts,shd->thd", a,
                                  jnp.repeat(v, H // G, 1)) * r)

    with jax.default_matmul_precision("highest"):
        want = float(masked(q, k, v))
        want_g = jax.grad(masked, (0, 1, 2))(q, k, v)
        before = mx.profiler.get_counters()
        got, got_g = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        assert abs(float(got) - want) < 1e-3
        moved = {n: c - before.get(n, 0) for n, c in
                 mx.profiler.get_counters().items()
                 if n.startswith("sparse_attn::") and c != before.get(n, 0)}
        assert moved == {"sparse_attn::%s_bwd" % path: 1}, moved
        for name, g, w in zip("qkv", got_g, want_g):
            assert float(jnp.max(jnp.abs(g - w))) < 1e-4, name


def test_the_counters_name_the_path_each_sparse_call_took():
    """The backward walks the causal tiles (``sparse_attn::masked_bwd``)
    where it is handed the selection's bits and goes by chunks
    (``sparse_attn::chunked_bwd``) where it is not, once a call, at
    trace time; the forward, which has one path, counts nothing."""
    T, K = 256, 8
    q, k, v = _normal(1, T, 4, 128), _normal(2, T, 2, 128), \
        _normal(3, T, 2, 128)
    idx, n_valid, sel = _selection(T, K)

    def moved(fn):
        before = mx.profiler.get_counters()
        jax.make_jaxpr(fn)(q, k, v)
        return {n: c - before.get(n, 0) for n, c in
                mx.profiler.get_counters().items()
                if n.startswith("sparse_attn::") and c != before.get(n, 0)}

    def forward(bits):
        return lambda q, k, v: pallas_ops.sparse_attention(
            q, k, v, idx, n_valid, bits=bits)[0]

    def grad(bits):
        return lambda *a: jax.grad(lambda *a: jnp.sum(forward(bits)(*a)),
                                   (0, 1, 2))(*a)

    bits = pallas_ops.pack_selection(sel)
    assert moved(forward(None)) == {}
    assert moved(forward(bits)) == {}
    assert moved(grad(None)) == {"sparse_attn::chunked_bwd": 1}
    assert moved(grad(bits)) == {"sparse_attn::masked_bwd": 1}


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_selections_bits_name_exactly_its_slots(monkeypatch, ties):
    """``select``'s bits against its ``idx``: every query's bits name
    exactly ``idx[t, :n_valid[t]]`` and count ``n_valid[t]`` — the first
    queries with fewer than topk earlier keys (tau -inf), rows of 256
    to 512 keys taken in two stages of 128-key segments, and with
    ``ties`` keys that repeat seven rows, so that most rows tie at tau
    and the lower positions are the ones taken."""
    monkeypatch.setattr(dsa, "SEGMENT", 128)
    monkeypatch.setattr(dsa, "exact_top_k", lambda s, k: _TOP_K(s, k, 128))
    T, K = 512, 32
    qi, ki, w = _normal(4, 2, T, 16), _normal(5, T, 16), _normal(6, T, 2)
    if ties:
        ki = ki[jnp.arange(T) % 7]
    idx, vals, n_valid, bits = dsa.select(qi, ki, w, K, chunk=128,
                                          bits=True)
    assert bits.shape == (T // 32, T) and bits.dtype == jnp.uint32
    got = pallas_ops.unpack_selection(bits).T          # (query, key)
    want = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(
        jnp.arange(K)[None, :] < n_valid[:, None])
    assert bool(jnp.all(jnp.sum(got, 1) == n_valid))
    assert bool(jnp.all(got == want))
    if ties:    # the test is one of ties: tau repeats in most rows
        assert int(jnp.sum(jnp.sum(vals == vals[:, -1:], 1) > 1)) > T // 2


_TOP_K = dsa.exact_top_k


def test_the_rule_takes_the_masked_walk_at_the_sparse_cells_shape():
    """The sparse cell's shape (32,768 tokens, 2,048 slots, heads of 128)
    makes the bits, and a call with them walks the causal tiles
    (``sparse_attn::masked_bwd``); a call without them goes by chunks.
    A sequence of 131,072 tokens, whose bits would outweigh its slots'
    indices, makes none; nor do heads of no whole lane rows or a length
    of no whole tiles, and bits handed over for those are refused."""
    takes = pallas_ops.sparse_attention_takes_bits
    assert takes(32768, 2048, 128) and takes(65536, 2048, 128)
    assert not takes(131072, 2048, 128)
    assert not takes(32768, 2048, 96) and not takes(32640, 2048, 128)

    def moved(N, with_bits):
        def grad(q, k, v, idx, n_valid, bits):
            return jax.grad(lambda *a: jnp.sum(pallas_ops.sparse_attention(
                *a, idx, n_valid, bits=bits if with_bits else None)[0]),
                (0, 1, 2))(q, k, v)

        av = jax.ShapeDtypeStruct
        before = mx.profiler.get_counters()
        jax.make_jaxpr(grad)(
            av((N, 32, 128), jnp.bfloat16), av((N, 4, 128), jnp.bfloat16),
            av((N, 4, 128), jnp.bfloat16), av((N, 2048), jnp.int32),
            av((N,), jnp.int32), av((N // 32, N), jnp.uint32))
        return {n: c - before.get(n, 0) for n, c in
                mx.profiler.get_counters().items()
                if n.startswith("sparse_attn::") and c != before.get(n, 0)}

    assert moved(32768, True) == {"sparse_attn::masked_bwd": 1}
    assert moved(32768, False) == {"sparse_attn::chunked_bwd": 1}
    with pytest.raises(ValueError, match="bits"):
        pallas_ops.sparse_attention(
            *(jnp.zeros((256, h, 96), jnp.bfloat16) for h in (4, 2, 2)),
            jnp.zeros((256, 8), jnp.int32), jnp.ones((256,), jnp.int32),
            bits=jnp.zeros((8, 256), jnp.uint32))


def test_the_index_kernel_interpreted_matches_its_xla_form(monkeypatch):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    qi, ki, w = _normal(1, 2, 512, 64), _normal(2, 1024, 64), \
        _normal(3, 512, 2)
    with jax.default_matmul_precision("highest"):
        got = pallas_ops.index_scores(qi, ki, w, q0=512)
        want = pallas_ops.index_scores_dense(qi, ki, w, q0=512)
    seen = jnp.isfinite(want)
    assert bool(jnp.all(jnp.isfinite(got) == seen))
    assert float(jnp.max(jnp.abs(jnp.where(seen, got - want, 0.0)))) < 1e-3


@pytest.mark.parametrize("L,segment", [(64, 16), (96, 32), (40, 16)])
def test_the_two_stage_top_k_is_the_top_k_ties_to_the_lower_position(
        L, segment):
    # integer scores: many ties
    s = jnp.floor(_normal(9, 6, L) * 3)
    v1, i1 = dsa.exact_top_k(s, 8, segment)
    v2, i2 = jax.lax.top_k(s, 8)
    assert bool(jnp.all(jnp.sort(i1, -1) == jnp.sort(i2, -1)))
    assert bool(jnp.all(jnp.sort(v1, -1) == jnp.sort(v2, -1)))


def _experts(E=8, D=16, F=8, key=0):
    return (_normal(key, E, D) * 0.5, _normal(key + 1, E, D, F) * 0.3,
            _normal(key + 2, E, D, F) * 0.3, _normal(key + 3, E, F, D) * 0.3)


def _plain_experts(x, router, w1, w3, w2, top_k, held):
    """Every token through every expert; a token's output the gated sum
    of the outputs of its top_k experts that are held."""
    probs = jax.nn.softmax(x @ router.T, -1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    gates = top_p / jnp.sum(top_p, -1, keepdims=True)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w1)) \
        * jnp.einsum("td,edf->tef", x, w3)
    out = jnp.einsum("tef,efd->ted", h, w2)
    g = jnp.zeros(probs.shape).at[jnp.arange(x.shape[0])[:, None],
                                  top_e].add(gates)
    g = g * jnp.isin(jnp.arange(router.shape[0]), jnp.asarray(list(held)))
    return jnp.einsum("te,ted->td", g, out)


def test_eight_shares_of_the_experts_add_up_to_the_whole_layer():
    router, w1, w3, w2 = _experts()
    x = _normal(10, 24, 16)
    with jax.default_matmul_precision("highest"):
        whole, aux, n = experts.routed_experts(x, router, w1, w3, w2, 0, 3)
        parts = [experts.routed_experts(x, router, w1[i:i + 1],
                                        w3[i:i + 1], w2[i:i + 1], i, 3)
                 for i in range(8)]
        plain = _plain_experts(x, router, w1, w3, w2, 3, range(8))
    assert float(jnp.max(jnp.abs(sum(p[0] for p in parts) - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(whole - plain))) < 1e-5
    assert int(n) == 24 * 3 == sum(int(p[2]) for p in parts)
    for p in parts:     # the router's term is the whole model's
        assert abs(float(p[1]) - float(aux)) < 1e-6


def test_no_token_is_dropped_when_routing_piles_onto_one_expert():
    router, w1, w3, w2 = _experts()
    router = router.at[5].set(router[5] + 40.0 * jnp.ones(16))
    x = jnp.abs(_normal(11, 64, 16)) + 0.1
    with jax.default_matmul_precision("highest"):
        probs, top_e, _ = experts.route(x, router, 2)
        assert bool(jnp.all(top_e[:, 0] == 5))
        got, _, n = experts.routed_experts(x, router, w1[4:8], w3[4:8],
                                           w2[4:8], 4, 2)
        want = _plain_experts(x, router, w1, w3, w2, 2, range(4, 8))
        grads = jax.grad(lambda a: jnp.sum(experts.routed_experts(
            a, router, w1[4:8], w3[4:8], w2[4:8], 4, 2)[0] ** 2))(x)
        want_g = jax.grad(lambda a: jnp.sum(_plain_experts(
            a, router, w1, w3, w2, 2, range(4, 8)) ** 2))(x)
    assert int(n) >= 64      # every token's pair with expert 5
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(grads - want_g))) < 1e-4


@pytest.mark.parametrize("T,top_k,held,E,C", [
    (8192, 10, 8, 256, 5120),      # laguna_s21_train_1x8192
    (32768, 8, 16, 128, 65536),    # keye_vl2_30b_a3b_train_1x32768
    (2048, 2, 4, 64, 512),
    (24, 3, 1, 8, 24),             # every pair the held expert can take
    (24, 3, 8, 8, 72)])
def test_the_bound_is_twice_the_held_share_in_whole_row_tiles(T, top_k,
                                                             held, E, C):
    assert experts._capacity(T, top_k, held, E) == C


_MOE_T, _MOE_TOP, _MOE_E, _MOE_HELD, _MOE_FIRST = 2048, 2, 64, 4, 8


def _moe_layer(bias=0.0):
    """A layer of 4 held experts of 64, top 2, over 2,048 tokens: a bound
    of 512 rows of the 4,096 pairs.  ``bias`` on the router's row of
    the first held expert piles the tokens onto it."""
    router, w1, w3, w2 = _experts(E=_MOE_E)
    router = router.at[_MOE_FIRST].add(bias)
    held = slice(_MOE_FIRST, _MOE_FIRST + _MOE_HELD)
    return router, w1[held], w3[held], w2[held], _normal(20, _MOE_T, 16)


def _plain_held(x, router, w1, w3, w2, first, top_k, score, scale):
    """``routed_experts`` written plainly: every token through every held
    expert, the router's gates by a dense mask."""
    logits = x @ router.T
    if score == "softmax":
        probs = jax.nn.softmax(logits, -1)
        top_p, top_e = jax.lax.top_k(probs, top_k)
        gates = top_p / jnp.sum(top_p, -1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        top_p, top_e = jax.lax.top_k(scores, top_k)
        gates = scale * top_p / jnp.sum(top_p, -1, keepdims=True)
        probs = scores / jnp.sum(scores, -1, keepdims=True)
    held = w1.shape[0]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w1)) \
        * jnp.einsum("td,edf->tef", x, w3)
    out = jnp.einsum("tef,efd->ted", h, w2)
    g = jnp.zeros(probs.shape).at[jnp.arange(x.shape[0])[:, None],
                                  top_e].add(gates)[:, first:first + held]
    n = jnp.sum((top_e >= first) & (top_e < first + held))
    return jnp.einsum("te,ted->td", g, out), experts.balance_loss(
        probs, top_e), n


def _moe_outputs(layer, score, router, w1, w3, w2, x):
    """``y``, aux, held pairs, and the gradients of x, router, w1, w3, w2
    of a loss that reads both outputs."""
    def loss(x, router, w1, w3, w2):
        y, aux, n = layer(x, router, w1, w3, w2, _MOE_FIRST, _MOE_TOP,
                          score, 2.5)
        return jnp.sum(jnp.sin(y)) + 3.0 * aux, (y, aux, n)

    with jax.default_matmul_precision("highest"):
        (_, (y, aux, n)), grads = jax.value_and_grad(
            loss, range(5), has_aux=True)(x, router, w1, w3, w2)
    return (y, aux, n) + tuple(grads)


def _counted(fn, *args):
    """The ``experts::`` counters ``fn`` moves while traced, and its jaxpr."""
    before = mx.profiler.get_counters()
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {n: c - before.get(n, 0) for n, c in
            mx.profiler.get_counters().items()
            if n.startswith("experts::") and c != before.get(n, 0)}, jaxpr


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("bias", [0.0, 40.0], ids=["fit", "overflow"])
def test_the_bounded_buffers_give_what_the_plain_layer_gives(score, bias):
    """The held pairs ``C`` at a time: one buffer where they fit in it;
    where routing piles onto a held expert (``bias``), more turns of the
    loop, and no pair is dropped.  Against every token through every
    held expert: the same output, aux, held pairs and gradients."""
    layer = _moe_layer(bias)
    moved, jaxpr = _counted(
        lambda x, *w: experts.routed_experts(x, *w, _MOE_FIRST, _MOE_TOP,
                                             score, 2.5),
        layer[4], *layer[:4])
    assert moved == {"experts::bounded": 1}
    assert "while[" in str(jaxpr)
    got = _moe_outputs(experts.routed_experts, score, *layer)
    want = _moe_outputs(_plain_held, score, *layer)
    C = experts._capacity(_MOE_T, _MOE_TOP, _MOE_HELD, _MOE_E)
    assert C < _MOE_T * _MOE_TOP
    assert (int(got[2]) > C) == bool(bias) and int(got[2]) > 0
    assert int(want[2]) == int(got[2])
    for name, a, b in zip(["y", "aux", "x", "router", "w1", "w3", "w2"],
                          got[:2] + got[3:], want[:2] + want[3:]):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * max(scale, 1.0), name


@pytest.mark.parametrize("held,first", [(8, 0), (1, 2)])
def test_a_buffer_of_every_pair_the_held_experts_take_is_not_counted_bounded(
        held, first):
    """``held == E``, or one expert of eight held, top 3 (a token sends
    it at most one pair): the buffer holds every pair the held experts
    can take, and ``experts::bounded`` stays."""
    router, w1, w3, w2 = _experts()
    x = _normal(10, 24, 16)
    moved, _ = _counted(
        lambda x: experts.routed_experts(
            x, router, w1[first:first + held], w3[first:first + held],
            w2[first:first + held], first, 3), x)
    assert moved == {}


def test_the_model_s_indexer_loss_moves_the_indexer_alone():
    """A step whose loss is the indexer's KL alone (plain SGD, no decay)
    moves the indexer's leaves and nothing else: its input is detached
    and the selection is discrete."""
    cfg = tiny_config(n_layers=1, attn_impl="dsa", qk_norm=True,
                      index_heads=2, index_head_dim=16, index_topk=8,
                      head_dim=32, vocab_size=64)
    net = TransformerLM(cfg)
    net.initialize()
    before = {k: onp.asarray(p.data()._data)
              for k, p in net.collect_params().items()}
    step = parallel.TrainStep(
        net, None, mx.optimizer.SGD(learning_rate=1.0, wd=0.0), mesh=None,
        forward_fn=lambda net, x, y: net.hidden_with_aux(x)[1][
            "index_loss"])
    toks = mx.np.array(onp.random.RandomState(0).randint(0, 64, (2, 32))
                       .astype("int32"))
    step(toks, toks)
    moved = {k for k, p in net.collect_params().items()
             if not onp.array_equal(onp.asarray(p.data()._data), before[k])}
    assert moved and all(".indexer." in k for k in moved), moved
    assert any("indexer.wk" in k for k in moved)


def test_head_dim_is_its_own_field():
    cfg = tiny_config(head_dim=48, n_heads=4, n_kv_heads=2, qk_norm=True)
    net = TransformerLM(cfg)
    ps = net.collect_params()
    assert ps["layer0.attention.wq.weight"].shape == (4 * 48, cfg.dim)
    assert ps["layer0.attention.wk.weight"].shape == (2 * 48, cfg.dim)
    assert ps["layer0.attention.q_norm.gamma"].shape == (48,)
    net.initialize()
    out = net(mx.np.array(onp.zeros((1, 16), "int32")))
    assert out.shape == (1, 16, cfg.vocab_size)
    from mxnet_tpu import serve
    spec = serve.ServeConfig(slots=2, pages=8, page_size=16).cache_spec(cfg)
    assert spec.head_dim == 48
