"""Shared encoder building blocks (used by bert.py and deberta.py).

Numerically-sensitive primitives live in exactly one place: dense matmuls
run in the param dtype with f32 accumulation on the MXU; layernorm always
computes in f32 regardless of the activation dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dense_init(rng, in_dim: int, out_dim: int, dtype) -> dict:
    return {
        "kernel": (
            jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * 0.02
        ).astype(dtype),
        "bias": jnp.zeros((out_dim,), dtype),
    }


def ln_init(dim: int, dtype) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layer_norm(x, params: dict, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    # one-pass variance (E[x^2] - mean^2, clamped): both reductions fuse
    # into a single read of x, unlike jnp.var's subtract-then-reduce
    # second pass.  The cancellation risk is bounded: LN inputs are
    # O(1-10) f32, and flax LayerNorm uses the same formulation.
    meansq = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    var = jnp.maximum(meansq - mean * mean, 0.0)
    normed = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (
        normed * params["scale"].astype(jnp.float32)
        + params["bias"].astype(jnp.float32)
    ).astype(x.dtype)


def dense(x, p: dict):
    return (
        jnp.einsum(
            "...i,io->...o",
            x,
            p["kernel"],
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        + p["bias"]
    )


def dense_cfg(x, p: dict, config):
    """The layer-dense op under the config's quantize mode: param-dtype
    matmul (dense above), the W8A8 int8-MXU twin, or the packed-int4
    W4A8 twin (both models/quant.py) — selected statically by
    ``config.quantize``, so the jit sees one path.  Shared by every
    model family (bert, deberta)."""
    if config.quantize.startswith("int8"):
        from .quant import dense_int8, impl_for

        return dense_int8(x, p, impl=impl_for(config.quantize))
    if config.quantize.startswith("int4"):
        from .quant import dense_int4, impl_for

        return dense_int4(x, p, impl=impl_for(config.quantize))
    return dense(x, p)


def mlp_cfg(x, p_in: dict, p_out: dict, config):
    """The encoder MLP (dense -> GELU -> dense) under the config's
    quantize mode.  Full precision keeps the dense/gelu_erf composition;
    int8/int4 modes route BOTH matmuls through their quantized dense
    with the GELU folded into the expansion matmul's kernel epilogue
    (ops/kernels.w8a8_matmul / w4a8_matmul) — the [B*S, intermediate]
    GELU input never round-trips HBM between separate
    quant/matmul/activation passes."""
    if config.quantize.startswith("int8"):
        from .quant import dense_int8, impl_for

        impl = impl_for(config.quantize)
        h = dense_int8(x, p_in, gelu=True, impl=impl)
        return dense_int8(h, p_out, impl=impl)
    if config.quantize.startswith("int4"):
        from .quant import dense_int4, impl_for

        impl = impl_for(config.quantize)
        h = dense_int4(x, p_in, gelu=True, impl=impl)
        return dense_int4(h, p_out, impl=impl)
    return dense(gelu_erf(dense(x, p_in)), p_out)


def gelu_erf(x: jax.Array) -> jax.Array:
    """Exact (erf) GELU: HF BERT/bge/deberta checkpoints use
    hidden_act="gelu", which is erf-based — jax.nn.gelu's default tanh
    approximation would silently diverge from real checkpoints
    (tests/test_hf_parity.py): its output differs from exact-erf GELU by
    up to 257 bf16 ulps and flips the bf16 rounding of ~40% of inputs
    (measured, r4).

    f32 inputs always take XLA's exact erf; upcast from bf16 would too
    be exact — but for bf16 activations the erf lowering's ~12-op
    polynomial is the single largest non-matmul cost in the encoder
    forward (a builder's profile on another toolchain, not measured
    on this chip).  The bf16 path instead uses the Abramowitz-Stegun
    7.1.26 erfc form, which rides the TPU's hardware exp: design error
    2.2e-7 absolute (f64), and after bf16 rounding it agrees with the
    exact-erf f32 GELU to <=1 bf16 ulp on ALL finite bf16 inputs
    x >= -3 (<2% of them flip by that 1 ulp — inherent to any f32
    evaluation near rounding midpoints) and to 2e-5 absolute in the deep
    tail (|gelu| < 0.005, where f32 cancellation in the polynomial
    shows).  Asserted exhaustively over every finite bf16 input in
    tests/test_models.py."""
    x32 = x.astype(jnp.float32)
    return gelu_f32(x32, approx=x.dtype == jnp.bfloat16).astype(x.dtype)


def gelu_f32(x32: jax.Array, approx: bool = False) -> jax.Array:
    """The f32 GELU core behind gelu_erf, split out so the W8A8 kernel
    epilogue (ops/kernels.py) applies the IDENTICAL math — same exact-erf
    vs A&S-7.1.26 split, same coefficients — inside the fused matmul."""
    if not approx:
        return x32 * 0.5 * (1.0 + jax.lax.erf(x32 * (2.0 ** -0.5)))
    z = jnp.abs(x32) * (2.0 ** -0.5)
    t = 1.0 / (1.0 + 0.3275911 * z)
    poly = t * (
        0.254829592
        + t
        * (
            -0.284496736
            + t * (1.421413741 + t * (-1.453152027 + t * 1.061405429))
        )
    )
    half_erfc = 0.5 * poly * jnp.exp(-z * z)
    phi = jnp.where(x32 > 0, 1.0 - half_erfc, half_erfc)
    return x32 * phi
