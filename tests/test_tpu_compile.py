"""The fused attention kernel compiled for a described TPU v5e, no chip
attached: what interpret mode cannot show (Mosaic's own refusals, the
VMEM the served blocks take) and what the benchmark reads (the kernel's
name, and that no relayout copy stands around it).

Nothing runs, so nothing here is a time.  Keep every such compile in this
one file: only the worker that is handed it loads the TPU's library.
"""

import os
import re
from dataclasses import replace

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert, configs
from llm_weighted_consensus_tpu.ops import attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    # off a TPU the kernel would take its interpret branch; the compiler
    # under test is the chip's
    monkeypatch.setattr(attention, "_interpret", lambda: False)


def instructions(hlo_text):
    """(name, opcode, rest of the line) of every instruction of an
    optimized HLO module."""
    return re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\((.*)$", hlo_text, re.M
    )


@pytest.mark.parametrize("packed", [False, True], ids=["padded", "packed"])
@pytest.mark.parametrize("b", [64, 512], ids=["solo", "group-of-8"])
def test_bge_large_attention_block_compiles_without_copies(
    one_chip, compiled_kernels, b, packed
):
    """bert._attention at the benchmark's bucket (bge-large, 512 tokens,
    one request and a full group), bf16: Mosaic takes the served block,
    the kernel keeps the name the benchmark reads, and between the
    projections, the kernel and attn_out the compiler places no copy and
    no transpose."""
    cfg = replace(configs.BGE_LARGE, attention_impl="fused")
    s, h = 512, cfg.hidden_size
    dt = jnp.bfloat16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = jax.tree_util.tree_map(
        lambda a: arg(a.shape[1:], dt),
        jax.eval_shape(
            lambda: bert.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)
        )["layers"],
    )
    x = arg((b, s, h), dt)
    if packed:
        bias, seg = arg((b, 1, s, s), jnp.float32), arg((b, s), jnp.int32)
    else:
        bias, seg = arg((b, 1, 1, s), jnp.float32), None
    compiled = (
        jax.jit(lambda x, p, bias, seg: bert._attention(x, p, bias, cfg, seg))
        .lower(x, layer, bias, seg)
        .compile()
    )
    found = instructions(compiled.as_text())
    kernel = "fused_attention_tiled_seg" if packed else "fused_attention_tiled"
    names = [name for name, op, _ in found]
    # Mosaic kernels only: the compiler's own custom calls (a weight's
    # prefetch into fast memory) are not the program's
    kernels = [
        name.split(".")[0]
        for name, op, rest in found
        if op == "custom-call" and '"tpu_custom_call"' in rest
    ]
    assert kernels == [kernel], names
    # "copy-start"/"copy-done" are prefetches, not relayouts
    assert not [n for n, op, _ in found if op in ("copy", "transpose")], names
