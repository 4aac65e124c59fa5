"""Opt-in int8 quantization for the encoder's dense matmuls.

The v5e MXU runs int8 x int8 -> int32 at twice the bf16 rate (394 vs 197
TOPS), and the consensus forward's FLOPs are almost all dense matmuls —
so a W8A8 path can halve the FLOP term on the serving hot path (what it
gains on the chip is not measured).  No reference analog (the reference
delegates model compute to upstream HTTP APIs); this is a TPU-native
serving optimization, OFF by default, selected per embedder
(``TpuEmbedder(..., quantize="int8")`` / ``EMBEDDER_QUANTIZE=int8``).

Scheme — the standard symmetric W8A8 recipe:

* weights: per-OUTPUT-channel symmetric int8 (scale[out] = max|W[:,o]|/127,
  quantized ONCE at load time);
* activations: per-ROW dynamic symmetric int8 (scale[row] = max|x[row]|/127);
* matmul: int8 x int8 with int32 accumulation on the MXU
  (``preferred_element_type=int32`` — exact), dequantized by the rank-1
  outer product of the two scales, bias added.

Two implementations of the same math, selected by ``impl_for``:

* ``pallas`` (TPU default) — ops/kernels.w8a8_matmul: activation quant +
  int8 matmul + dequant/bias(+GELU) epilogue fused in ONE kernel, so the
  quantized activations, the int32 accumulator, and any dequantized
  weight copy stay in VMEM — nothing but the input and the finished
  output touches HBM;
* ``xla`` (non-TPU default, the default under a mesh — a Mosaic kernel
  cannot be auto-partitioned, parallel/sharding.py ``gspmd_config`` —
  and the VMEM-overflow fallback) — the jnp
  composition below with ``preferred_element_type=int32``; XLA fuses the
  quant pass into surrounding elementwise work but stages the int8
  activations through HBM.

The mode strings ``int8-pallas`` / ``int8-xla`` pin an implementation
(tests, debugging); plain ``int8`` auto-selects per backend.

What stays un-quantized, deliberately: attention QK^T/PV (bf16, already
cheap and softmax-sensitive), layernorm/softmax (f32 module contract),
GELU (f32/A&S), embeddings/pooling.  Accuracy is pinned in
tests/test_quant.py: per-matmul error bounds, end-to-end embedding cosine
vs the bf16 path, and consensus-vote top-1 agreement on the committed
golden checkpoint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_weight(kernel: jax.Array):
    """kernel[..., in, out] (f32/bf16) -> (int8 kernel, f32 scale[..., out]).

    Per-output-channel symmetric: preserves each output feature's dynamic
    range independently, which matters for LN-adjacent projections whose
    channel magnitudes vary by orders of magnitude."""
    k32 = kernel.astype(jnp.float32)
    scale = jnp.max(jnp.abs(k32), axis=-2) / 127.0  # [..., out]
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(k32 / scale[..., None, :])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def _quantize_rows(x: jax.Array):
    """x[..., rows, in] -> (int8 x, f32 scale[..., rows]) per-row dynamic."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1) / 127.0  # [..., rows]
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(x32 / scale[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


QUANT_MODES = ("none", "int8", "int8-pallas", "int8-xla", "int4-pallas")


def impl_for(mode: str) -> str:
    """Quantize mode string -> dense implementation name.

    Called at trace time, so the backend probe is a compile-time constant
    — the jit sees exactly one path."""
    if mode == "int8":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode in ("int8-pallas", "int8-xla"):
        return mode[len("int8-"):]
    if mode == "int4-pallas":
        # one spelling: the packed layout exists FOR the fused kernel
        # (non-TPU backends run it in interpret mode)
        return "pallas"
    raise ValueError(f"quantize={mode!r} is not a quantized mode")


def dense_int8(
    x: jax.Array, p: dict, *, gelu: bool = False, impl: str = None
) -> jax.Array:
    """W8A8 dense: x[..., in] @ p["kernel_q"][in, out] -> [..., out].

    int32 accumulation on the MXU (exact), dequantized by
    act_scale x weight_scale, plus bias — the quantized twin of
    layers.dense.  ``gelu=True`` appends the exact-profile GELU
    (layers.gelu_erf semantics), fused into the kernel epilogue on the
    pallas impl.  ``impl`` pins "pallas"/"xla"; None auto-selects
    (pallas on TPU, xla elsewhere)."""
    if impl is None:
        impl = impl_for("int8")
    if impl == "pallas":
        from ..ops.kernels import w8a8_matmul, w8a8_shape_fits

        m = 1
        for d in x.shape[:-1]:
            m *= d
        k = x.shape[-1]
        n = p["kernel_q"].shape[-1]
        if w8a8_shape_fits(m, k, n, jnp.dtype(x.dtype).itemsize):
            return w8a8_matmul(
                x, p["kernel_q"], p["scale"], p["bias"], gelu=gelu
            )
        # weight block too big for VMEM: the XLA composition below
    xq, sx = _quantize_rows(x)
    acc = jax.lax.dot_general(
        xq,
        p["kernel_q"],
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * sx[..., None] * p["scale"]
    out = out.astype(x.dtype) + p["bias"]
    if gelu:
        from .layers import gelu_erf

        out = gelu_erf(out)
    return out


def quantize_weight_int4(kernel: jax.Array):
    """kernel[..., in, out] (f32/bf16) -> (packed uint8 kernel,
    f32 scale[..., out]).

    Per-output-channel symmetric int4: scale = max|W[:,o]|/7, values
    clipped to [-7, 7], packed two-per-byte along K in the split-K
    biased-nibble layout of ops/kernels.pack_int4_weights (which is also
    the layout ``w4a8_matmul`` unpacks in-kernel).  2x less HBM/VMEM
    than int8 — the headroom the long-context ring path spends on
    activations."""
    from ..ops.kernels import pack_int4_weights

    k32 = kernel.astype(jnp.float32)
    scale = jnp.max(jnp.abs(k32), axis=-2) / 7.0  # [..., out]
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(k32 / scale[..., None, :])
    q = jnp.clip(q, -7, 7).astype(jnp.int8)
    return pack_int4_weights(q), scale


def _unpack_int4(wq4: jax.Array, k: int) -> jax.Array:
    """Packed uint8 [Kp/2, N] -> int8 [k, N] (the XLA-composition twin of
    the in-kernel unpack; XLA constant-folds it over the frozen weights)."""
    w32 = wq4.astype(jnp.int32)
    lo = ((w32 & 0xF) - 8).astype(jnp.int8)
    hi = ((w32 >> 4) - 8).astype(jnp.int8)
    return jnp.concatenate([lo, hi], axis=0)[:k]


def dense_int4(
    x: jax.Array, p: dict, *, gelu: bool = False, impl: str = None
) -> jax.Array:
    """W4A8 dense: x[..., in] @ unpack(p["kernel_q"])[in, out] -> [..., out].

    The packed-int4 twin of ``dense_int8``: same per-row dynamic int8
    activations, same int32 MXU accumulation, same rank-1 dequant — the
    weight block just decodes from nibbles.  The pallas impl unpacks
    IN-KERNEL (ops/kernels.w4a8_matmul) so the int8 weight copy never
    materializes; a shape past the shared VMEM gate is an error, not a
    quiet switch to the XLA composition (``impl="xla"``), which exists
    as the kernel's test reference."""
    if impl is None:
        impl = impl_for("int4-pallas")
    k = x.shape[-1]
    n = p["kernel_q"].shape[-1]
    if impl == "pallas":
        from ..ops.kernels import w4a8_matmul, w8a8_shape_fits

        m = 1
        for d in x.shape[:-1]:
            m *= d
        if not w8a8_shape_fits(
            m, k, n, jnp.dtype(x.dtype).itemsize, w_bytes=0.5
        ):
            # the mode is NAMED for the kernel: serving the XLA
            # composition under it would hide which path runs
            raise ValueError(
                f"int4-pallas: the [{k}, {n}] packed weight block does "
                "not fit the W4A8 kernel's VMEM budget "
                "(ops/kernels.w8a8_shape_fits); use quantize=\"int8\""
            )
        return w4a8_matmul(
            x, p["kernel_q"], p["scale"], p["bias"], gelu=gelu
        )
    wq = _unpack_int4(p["kernel_q"], k)
    xq, sx = _quantize_rows(x)
    acc = jax.lax.dot_general(
        xq,
        wq,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * sx[..., None] * p["scale"]
    out = out.astype(x.dtype) + p["bias"]
    if gelu:
        from .layers import gelu_erf

        out = gelu_erf(out)
    return out


_QUANT_LAYER_KERNELS = (
    "attn_q", "attn_k", "attn_v", "attn_out", "mlp_in", "mlp_out"
)


def is_quantized(params: dict) -> bool:
    """Whether a bert param pytree carries a quantized layout — the ONE
    structural probe (callers must not re-invent it: layout changes then
    surface here, not as a silent misdetection at a second site)."""
    return "kernel_q" in params.get("layers", {}).get("attn_q", {})


def is_int4(params: dict) -> bool:
    """Whether a quantized pytree carries the PACKED int4 layout (uint8
    nibbles) rather than int8 — same single-probe contract as
    ``is_quantized``."""
    leaf = params.get("layers", {}).get("attn_q", {})
    return (
        "kernel_q" in leaf and leaf["kernel_q"].dtype == jnp.uint8
    )


def quantize_bert_params(params: dict) -> dict:
    """bert param pytree -> its int8 twin (layer dense kernels quantized,
    everything else untouched).  Pair with a config carrying
    ``quantize="int8"`` so the forward takes the dense_int8 path."""
    layers = dict(params["layers"])
    for name in _QUANT_LAYER_KERNELS:
        leaf = layers[name]
        kq, scale = quantize_weight(leaf["kernel"])
        layers[name] = {
            "kernel_q": kq,
            "scale": scale,
            "bias": leaf["bias"],
        }
    out = dict(params)
    out["layers"] = layers
    return out


# DeBERTa RM: the same six content/MLP kernels quantize; the positional
# projections (pos_q/pos_k — one tiny [2k, h] matmul per forward, and the
# disentangled scores are position-sensitive) and the reward head (two
# small matmuls whose scalar output IS the product) stay full precision.
quantize_deberta_params = quantize_bert_params


def quantize_bert_params_int4(params: dict) -> dict:
    """bert param pytree -> its packed-int4 twin (same six layer dense
    kernels as the int8 path; same leaf names, so the partition rules
    and the JXA006 coverage audit apply unchanged)."""
    layers = dict(params["layers"])
    for name in _QUANT_LAYER_KERNELS:
        leaf = layers[name]
        kq4, scale = quantize_weight_int4(leaf["kernel"])
        layers[name] = {
            "kernel_q": kq4,
            "scale": scale,
            "bias": leaf["bias"],
        }
    out = dict(params)
    out["layers"] = layers
    return out


def resolve_quantize(config, params: dict, quantize: str):
    """The ONE quantize-mode entry point for model constructors
    (TpuEmbedder, TpuReranker): validates the mode, stamps it on the
    (frozen dataclass) config, and quantizes full-precision params once
    at load — pre-quantized pytrees pass through.  Returns
    (config, params)."""
    if quantize not in QUANT_MODES:
        raise ValueError(
            f"quantize={quantize!r}: expected one of {QUANT_MODES}"
        )
    if quantize == "none":
        return config, params
    import dataclasses

    config = dataclasses.replace(config, quantize=quantize)
    want_int4 = quantize.startswith("int4")
    if is_quantized(params):
        if is_int4(params) != want_int4:
            raise ValueError(
                f"quantize={quantize!r} but params carry the "
                f"{'int4' if is_int4(params) else 'int8'} layout — "
                "re-load full-precision params to switch schemes"
            )
        return config, params
    params = (
        quantize_bert_params_int4(params)
        if want_int4
        else quantize_bert_params(params)
    )
    return config, params
