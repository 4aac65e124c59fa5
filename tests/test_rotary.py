"""The latent-attention judges' rotary turn where the heads lie
(``ops/rotary.py``, ``models/glm_moe.py::_turn_heads`` and ``_keys``): the
kernel against ``decoder_parts.rope`` on the sliced head, to the last bit, at
the two judges' head counts; the one rotary key in every head's rope lanes;
the shapes that keep the plain turn; and that no array of the queries' or the
keys' size is sliced, padded or concatenated on the way to the attention
kernel.  CPU, interpret mode: counts and bits, never a time.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llm_weighted_consensus_tpu.models import glm_moe  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import GLM_TEST_TINY  # noqa: E402
from llm_weighted_consensus_tpu.models.decoder_parts import rope, rope_angles  # noqa: E402
from llm_weighted_consensus_tpu.ops import rotary  # noqa: E402

CALLS, SLOTS = 3, 384  # a panel's calls: positions start again at 0 in each

# two heads of the judges' 192 | 64 over narrow products: every shape whole lanes
ALIGNED = dataclasses.replace(
    GLM_TEST_TINY, num_layers=1, num_heads=2, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256,
)


def drawn(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32).astype(dtype)


def sliced_turn(x, cos, sin, heads, first):
    """``decoder_parts.rope`` on each head's slice, the way the program
    turned before: the oracle."""
    dims = 2 * cos.shape[-1]
    xh = x.reshape(*x.shape[:-1], heads, -1)
    turned = rope(xh[..., first:first + dims], cos[..., None, :], sin[..., None, :])
    return jnp.concatenate(
        [xh[..., :first], turned, xh[..., first + dims:]], axis=-1
    ).reshape(x.shape)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    kernel's body left out (what it slices is a block in VMEM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from equations(inner)


def primitives(fn, *args):
    return [eqn.primitive.name for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)]


@pytest.mark.parametrize(
    "heads,nope,dims,dtype",
    [
        (20, 192, 64, jnp.bfloat16),
        (64, 192, 64, jnp.bfloat16),
        (32, 0, 64, jnp.bfloat16),
        (2, 192, 64, jnp.float32),
        (3, 128, 128, jnp.bfloat16),
    ],
    ids=["first-judge", "third-judge", "index-heads", "float32", "a-whole-column"],
)
def test_the_kernel_turns_a_head_s_rotary_lanes_as_rope_turns_the_slice(
    heads, nope, dims, dtype
):
    """To the last bit with angles whose cos and sin are bf16 numbers: every
    product of the turn is then exact in float32, so a CPU that fuses a
    multiply into the add and one that does not round alike (XLA's CPU
    backend contracts them, differently in a kernel's interpreted body and
    in the oracle's fusion; the chip has neither choice, and there the
    kernel read the sliced turn's bits over 400M values: my chip run, PR
    38).  With the angles as ``rope_angles`` gives them the two CPU roundings
    part in a few values of a million, by one bf16 step."""
    hd = -(-(nope + dims) // 128) * 128
    x = drawn(1, (CALLS, SLOTS, heads * hd), jnp.bfloat16).astype(dtype)
    cos, sin = rope_angles(jnp.arange(SLOTS), dims, 8e6)
    assert rotary.fits(x.shape, heads, nope, dims)
    oracle = jax.jit(sliced_turn, static_argnums=(3, 4))

    coarse = [t.astype(jnp.bfloat16).astype(jnp.float32) for t in (cos, sin)]
    got = rotary.turn_lanes(x, *coarse, heads=heads, first=nope)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(oracle(x, *coarse, heads, nope), np.float32)
    )
    # and it turned something: every head's rotary lanes moved, no other did
    moved = np.asarray(got != x).reshape(CALLS, SLOTS, heads, hd)
    assert moved[..., nope:nope + dims].any(axis=(1, 3)).all()
    assert not moved[..., :nope].any() and not moved[..., nope + dims:].any()

    got = np.asarray(rotary.turn_lanes(x, cos, sin, heads=heads, first=nope), np.float32)
    want = np.asarray(oracle(x, cos, sin, heads, nope), np.float32)
    # one bf16 step of the value (a sum that cancels keeps float32's own error)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    assert (np.abs(got - want) <= np.maximum(step, 1e-6)).all()
    assert (got != want).mean() < 1e-4


@pytest.mark.parametrize(
    "shape,heads,first,dims,served",
    [
        ((3, 8192, 20 * 256), 20, 192, 64, True),
        ((3, 8192, 64 * 256), 64, 192, 64, True),
        ((3, 8192, 32 * 128), 32, 0, 64, True),
        ((3, 8192, 128), 1, 0, 64, True),
        ((3, 96, 4 * 32), 4, 24, 8, False),  # the tiny presets' heads
        ((3, 20 * 256), 20, 192, 64, False),  # a decode step's rows
        ((3, 8192, 20 * 256), 20, 96, 64, False),  # the turn would cross a column
        ((3, 100, 20 * 256), 20, 192, 64, False),  # no whole row tiles
    ],
    ids=["first-judge", "third-judge", "index-heads", "index-key", "tiny", "decode",
         "across-columns", "ragged-rows"],
)
def test_which_shapes_the_kernel_serves(shape, heads, first, dims, served):
    assert rotary.fits(shape, heads, first, dims) is served


@pytest.mark.parametrize(
    "shape,angles,heads,first,dims",
    [
        ((3, 96, 4 * 32), (96,), 4, 24, 8),
        ((3, 20 * 256), (3,), 20, 192, 64),
        ((3, 4 * 16), (3,), 4, 0, 8),
    ],
    ids=["tiny-prefill", "decode-rows", "tiny-index-heads-decode"],
)
def test_other_shapes_keep_the_plain_turn(shape, angles, heads, first, dims):
    x = drawn(2, shape, jnp.float32)
    cos, sin = rope_angles(jnp.arange(7, 7 + angles[0]), dims, 1e4)

    def turn(x, cos, sin):
        return glm_moe._turn_heads(x, cos, sin, heads, first)

    assert "pallas_call" not in primitives(turn, x, cos, sin)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(turn)(x, cos, sin)),
        np.asarray(jax.jit(sliced_turn, static_argnums=(3, 4))(x, cos, sin, heads, first)),
    )


def test_whole_heads_take_the_kernel():
    x = drawn(3, (CALLS, SLOTS, 2 * 256), jnp.bfloat16)
    cos, sin = rope_angles(jnp.arange(SLOTS), 64, 1e4)
    found = primitives(lambda x: glm_moe._turn_heads(x, cos, sin, 2, 192), x)
    assert "pallas_call" in found and "concatenate" not in found[found.index("pallas_call"):]


@pytest.mark.parametrize(
    "heads,nope,dims,rank,dtype",
    [
        (20, 192, 64, 512, jnp.bfloat16),
        (64, 192, 64, 512, jnp.bfloat16),
        (4, 24, 8, 16, jnp.float32),
    ],
    ids=["first-judge", "third-judge", "tiny"],
)
def test_the_rotary_key_lands_in_every_head_s_zero_lanes(heads, nope, dims, rank, dtype):
    """``_keys`` against the product followed by the padded add it replaced:
    the other lanes to the last bit, the rope lanes the rotary key itself."""
    b, s, dq = 2, 136, nope + dims
    c, kr = drawn(4, (b, s, rank), dtype), drawn(5, (b, s, dims), dtype)
    w_k = (drawn(6, (rank, heads, dq), jnp.float32) * 0.05).astype(dtype)
    w_k = w_k.at[:, :, nope:].set(0)

    def before(c, kr, w_k):
        k = jnp.einsum(
            "bsc,chd->bshd", c, w_k, preferred_element_type=jnp.float32
        ).astype(c.dtype)
        return (k + jnp.pad(kr, ((0, 0), (0, 0), (nope, 0)))[:, :, None, :]).reshape(b, s, -1)

    got = jax.jit(glm_moe._keys)(c, kr, w_k)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(jax.jit(before)(c, kr, w_k), np.float32)
    )
    in_heads = np.asarray(got, np.float32).reshape(b, s, heads, dq)[..., nope:]
    np.testing.assert_array_equal(
        in_heads, np.broadcast_to(np.asarray(kr, np.float32)[:, :, None, :], in_heads.shape)
    )


def aligned_layer(dtype):
    params = glm_moe.init_params(jax.random.PRNGKey(11), ALIGNED, dtype=dtype)
    return params["layers"][0]["attn"], drawn(12, (CALLS, 256, ALIGNED.hidden_size), dtype)


def test_no_array_of_the_queries_size_is_cut_or_joined_on_the_way_to_attention():
    """The jaxpr of ``_attention_prefill`` at whole-lane heads: no
    ``concatenate``, ``pad`` or ``slice`` reads or writes an array as large
    as q or k (a later edit that brings the copies back fails here)."""
    p, h = aligned_layer(jnp.bfloat16)
    q_size = CALLS * 256 * ALIGNED.num_heads * ALIGNED.qk_head_dim
    jaxpr = jax.make_jaxpr(lambda h, p: glm_moe._attention_prefill(h, p, ALIGNED))(h, p)
    found = list(equations(jaxpr.jaxpr))
    names = [eqn.primitive.name for eqn in found]
    assert names.count("pallas_call") == 2, names  # the turn, the attention
    large = [
        (eqn.primitive.name, [v.aval.shape for v in (*eqn.invars, *eqn.outvars)])
        for eqn in found
        if eqn.primitive.name in ("concatenate", "pad", "slice", "dynamic_slice", "gather")
        and any(
            int(np.prod(v.aval.shape)) >= q_size for v in (*eqn.invars, *eqn.outvars)
        )
    ]
    assert not large, large


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_a_layer_s_attention_is_what_the_plain_turn_gives(monkeypatch, dtype):
    """``_attention_prefill`` at whole-lane heads through the kernel, and the
    same with every shape sent the plain way: output and cache to the bit."""
    p, h = aligned_layer(dtype)

    def layer(h, p):
        out, cache, _ = glm_moe._attention_prefill(h, p, ALIGNED)
        return out, cache

    got = jax.jit(layer)(h, p)
    monkeypatch.setattr(rotary, "fits", lambda *a: False)
    want = jax.jit(lambda h, p: layer(h, p))(h, p)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
