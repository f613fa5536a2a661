"""The precisions a plain reference can be computed in.  ``f32`` is the
reference proper; the others are the controls, one step below what a
configuration states, which the comparison has to tell from the program:
each rounds both operands of every matrix product or convolution and
accumulates in float32.  ``rounder`` is for a forward pass alone (a
served model); ``contraction`` is for a training step, whose backward
products are held in the lower precision too."""
import jax
import jax.numpy as jnp


def _fake_int8(x, axis):
    """Symmetric int8 with one scale per slice along ``axis`` (a token's
    row of activations, a weight's output channel), as a W8A8 matmul
    with dynamic activation scales has it."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fake_fp8(x, axis):
    """float8_e4m3 with one scale per slice along ``axis``, amax mapped
    to 448, the format's largest finite value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rounder(precision):
    """``round_operand(x, axis)``: ``x`` as the precision holds it, back
    in float32.  ``axis`` is the contracted axis (or axes).  The rounding
    is straight-through: a backward pass sees the rounded operands and
    passes its cotangents on unrounded (they would underflow otherwise,
    and a control that computes no gradient at all tells nothing)."""
    if precision == "f32":
        return lambda x, axis: x
    how = {"bf16": lambda x, axis: x.astype(jnp.bfloat16)
           .astype(jnp.float32),
           "int8": _fake_int8, "fp8": _fake_fp8}
    if precision not in how:
        raise ValueError("no precision %r" % (precision,))
    f = how[precision]
    return lambda x, axis: x + jax.lax.stop_gradient(f(x, axis) - x)


def _fp8_tensor(mantissa_bits, min_exponent, top):
    """``x`` in an 8-bit float format with one scale for the whole
    tensor, its largest magnitude mapped to ``top``, the format's largest
    finite value: the scaling an fp8 training step uses, so that
    gradients do not underflow.  The rounding is spelt out (to the
    nearest multiple of the value's own step, ties to even, the step of
    the smallest normal number below it), which gives what ``astype`` to
    the format gives and compiles for the chip in a fraction of the
    time."""
    def q(x):
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / top, 1.0)
        v = x / scale
        _, e = jnp.frexp(v)                    # |v| in [2**(e-1), 2**e)
        step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e - 1, min_exponent)
                         - mantissa_bits)
        return jnp.round(v / step) * step * scale
    return q


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# operands of a product, and the cotangent that enters its backward
# products: fp8 training keeps e4m3 for the first and e5m2, the wider
# range, for the second
TRAINING = {"bf16": (_bf16, _bf16),
            "fp8": (_fp8_tensor(3, -6, 448.0),       # float8_e4m3fn
                    _fp8_tensor(2, -14, 57344.0))}   # float8_e5m2


def contraction(precision, f):
    """``f(x, w)``, a product linear in each operand (a convolution, a
    matrix product), as a training step in ``precision`` computes it:
    both operands rounded in the forward product, and in the backward
    products the rounded operands and the rounded cotangent.  Sums are
    float32 throughout."""
    if precision == "f32":
        return f
    if precision not in TRAINING:
        raise ValueError("no training precision %r" % (precision,))
    q_operand, q_cotangent = TRAINING[precision]

    @jax.custom_vjp
    def product(x, w):
        return f(q_operand(x), q_operand(w))

    def forward(x, w):
        xq, wq = q_operand(x), q_operand(w)
        return f(xq, wq), (xq, wq)

    def backward(rounded, ct):
        return jax.vjp(f, *rounded)[1](q_cotangent(ct))

    product.defvjp(forward, backward)
    return product
