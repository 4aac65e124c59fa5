"""Encoder configurations (BGE family + DeBERTa-v3 reward model)."""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 384
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 1536
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    # "auto" routes to the fused Pallas attention kernel on TPU when the
    # (s, s) tile fits VMEM; "fused" / "einsum" force one path; "ring"
    # selects sequence-parallel ring attention (only valid inside
    # parallel/ring.py's shard_map over ``ring_axis``).
    attention_impl: str = "auto"
    ring_axis: str = "sp"
    # family-default pooling: bge uses CLS, e5/gte use masked mean
    # (both + l2-normalize); TpuEmbedder reads this unless overridden
    pooling: str = "cls"
    # "absolute": positions 0..s-1 (BERT).  "roberta": positions start at
    # pad_token_id+1 (XLM-R/RoBERTa checkpoints, e.g. bge-m3) — with the
    # framework's left-aligned masks that is an arange offset, so the
    # usable window is max_position_embeddings - pad_token_id - 1.
    position_style: str = "absolute"
    # "none" (default) runs dense matmuls in the param dtype; "int8"
    # expects params transformed by models.quant.quantize_bert_params and
    # runs them W8A8 on the MXU's int8 path (2x bf16 peak on v5e) —
    # opt-in serving mode, accuracy pinned in tests/test_quant.py
    quantize: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


# BGE family (BAAI/bge-*-en-v1.5 shapes; CLS pooling)
BGE_SMALL = BertConfig(
    hidden_size=384, num_layers=12, num_heads=12, intermediate_size=1536
)
BGE_BASE = BertConfig(
    hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072
)
BGE_LARGE = BertConfig(
    hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096
)

# E5 family (intfloat/e5-*-v2: BERT arch, masked-MEAN pooling, "query:"/
# "passage:" input prefixes are the caller's concern)
E5_SMALL = BertConfig(
    hidden_size=384, num_layers=12, num_heads=12, intermediate_size=1536,
    pooling="mean",
)
E5_BASE = BertConfig(
    hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072,
    pooling="mean",
)
E5_LARGE = BertConfig(
    hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096,
    pooling="mean",
)

# GTE family (thenlper/gte-*: BERT arch, masked-MEAN pooling)
GTE_SMALL = BertConfig(
    hidden_size=384, num_layers=12, num_heads=12, intermediate_size=1536,
    pooling="mean",
)
GTE_BASE = BertConfig(
    hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072,
    pooling="mean",
)
GTE_LARGE = BertConfig(
    hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096,
    pooling="mean",
)

# BGE-M3 (BAAI/bge-m3 dense retrieval: XLM-RoBERTa-large arch, 8192-token
# context, CLS pooling).  Positions are roberta-style; the 8194-row table
# minus pad_token_id+1 gives the advertised 8192-token window.  Serve long
# inputs on a mesh with an sp axis (ring attention, MESH_SHAPE=DPxTPxSP).
# The checkpoint's sentencepiece.bpe.model tokenizes via models/spm.py
# (xlmr id scheme, auto-discovered next to EMBEDDER_WEIGHTS or set
# EMBEDDER_VOCAB).
BGE_M3 = BertConfig(
    vocab_size=250002,
    hidden_size=1024,
    num_layers=24,
    num_heads=16,
    intermediate_size=4096,
    max_position_embeddings=8194,
    type_vocab_size=1,
    pad_token_id=1,
    position_style="roberta",
)

# Long-context encoder (bge-large dims, 8192-position table): serve on a
# mesh with an sp axis so attention runs as a sequence-parallel ring — a
# single device would need the full (s, s) score matrix.  No public
# checkpoint ships with these positions; load fine-tuned weights via
# EMBEDDER_WEIGHTS or train with train/ (position_embed rows beyond 512
# train from scratch).
BERT_LONG_8K = BertConfig(
    hidden_size=1024,
    num_layers=24,
    num_heads=16,
    intermediate_size=4096,
    max_position_embeddings=8192,
)

# tiny config for tests: fast init/compile on the CPU mesh
TEST_TINY = BertConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    intermediate_size=128,
    max_position_embeddings=64,
)

PRESETS = {
    "bge-small-en": BGE_SMALL,
    "bge-base-en": BGE_BASE,
    "bge-large-en": BGE_LARGE,
    "e5-small-v2": E5_SMALL,
    "e5-base-v2": E5_BASE,
    "e5-large-v2": E5_LARGE,
    "gte-small": GTE_SMALL,
    "gte-base": GTE_BASE,
    "gte-large": GTE_LARGE,
    "bge-m3": BGE_M3,
    "bert-long-8k": BERT_LONG_8K,
    "test-tiny": TEST_TINY,
}


def position_base(config: BertConfig) -> int:
    """First position id of a left-aligned sequence (0 for BERT,
    pad_token_id+1 for roberta-style checkpoints)."""
    return (
        config.pad_token_id + 1
        if config.position_style == "roberta"
        else 0
    )


def usable_positions(config: BertConfig) -> int:
    """Longest sequence the position table supports."""
    return config.max_position_embeddings - position_base(config)


@dataclass(frozen=True)
class DebertaConfig:
    """DeBERTa-style encoder with disentangled relative attention.

    ``position_buckets > 0`` selects DeBERTa-v3's LOG-bucketed relative
    positions (HF ``make_log_bucket_position``: exact within ±buckets/2,
    log-spaced beyond, out to ``max_relative_positions``) — the scheme
    every released v3 checkpoint is trained with (rel table rows =
    2 x buckets).  ``position_buckets = 0`` is the plain clamp scheme
    (rel table rows = 2 x max_relative_positions).
    """

    vocab_size: int = 128100
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_relative_positions: int = 512  # furthest distinguishable distance
    position_buckets: int = 256  # 0 = clamp scheme
    layer_norm_eps: float = 1e-7
    pad_token_id: int = 0
    # "int8": W8A8 content/MLP matmuls (models/quant.py twin for the RM;
    # the tiny positional projections and the reward head stay full
    # precision).  Opt-in, accuracy pinned in tests/test_quant.py.
    quantize: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def att_span(self) -> int:
        """Half the relative-position table (HF ``pos_ebd_size``)."""
        return (
            self.position_buckets
            if self.position_buckets > 0
            else self.max_relative_positions
        )


# microsoft/deberta-v3-base shapes: max_relative_positions=-1 in HF
# resolves to max_position_embeddings (512); position_buckets=256
DEBERTA_V3_BASE = DebertaConfig()
DEBERTA_TEST_TINY = DebertaConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    intermediate_size=128,
    max_relative_positions=16,
    position_buckets=0,
)


@dataclass(frozen=True)
class GlmMoeLiteConfig:
    """A causal decoder with latent attention and sparse experts
    (``model_type`` ``glm4_moe_lite``, the DeepSeek-V3 layout): the judge
    behind ``POST /consensus`` ``scorer: judge`` (models/glm_moe.py).

    ``num_layers`` is the published depth; a checkpoint that names fewer
    layers (one pipeline stage of a deployment) is served at the depth it
    names (``glm_moe.from_hf_weights``), and so are the experts 0..E-1 it
    names of a router ``n_routed_experts`` wide (one chip's share of a
    layer's experts) and the rows of the vocabulary it holds.

    ``index_topk`` > 0 is ``model_type`` ``glm_moe_dsa``: a learned sparse
    selection in front of the attention (the DeepSeek-V3.2 indexer).  A layer
    whose ``indexer_types`` entry is ``full`` owns an indexer and chooses each
    query's ``index_topk`` keys; a ``shared`` layer attends over the choice
    of the last ``full`` layer before it.

    ``layer_types`` non-empty is ``model_type`` ``dots3_note``: attention of
    TWO geometries in one stack.  A ``full_attention`` layer takes the fields
    above and below as they stand and owns an indexer (``index_topk`` > 0); a
    ``sliding_attention`` layer takes the ``swa_*`` ones, attends the
    ``sliding_window`` keys up to and with its own position, and has neither
    indexer nor selection.  ``attention_gate`` puts a sigmoid scalar a head on
    the attention's output (the headwise gate, from the layer's normed input);
    ``lora_rescale`` scales the normalised query and key-value latents by
    sqrt(hidden / rank) (``apply_mla_qkv_lora_rescale``).  A layer reads all
    of it as ONE record, ``geometry(layer)``; a decoder of one kind is the
    case where every layer's record is the same."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    # "int8": the dense products (attention projections, the dense layer's
    # MLP, the shared expert) through quant.dense_int8; router, routed
    # experts, embedding and head keep the parameters' dtype
    quantize: str = "none"
    # the indexer: heads of ``index_head_dim`` against ONE key a position,
    # the first ``qk_rope_head_dim`` dims of each turned; 0 keys: no indexer
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 0
    indexer_types: tuple = ()  # "full" | "shared" a layer
    index_norm_eps: float = 1e-6  # the LayerNorm over an index key
    # two kinds of attention layer: "full_attention" | "sliding_attention" a
    # layer (empty: every layer full), a sliding layer's own geometry, the
    # window's keys (a query's own position among them)
    layer_types: tuple = ()
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    attention_gate: bool = False  # sigmoid(W_g h) a head on the attention's output
    lora_rescale: bool = False  # latents times sqrt(hidden / rank)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def slides(self, layer: int) -> bool:
        return bool(self.layer_types) and self.layer_types[layer] == "sliding_attention"

    def geometry(self, layer: int) -> "AttentionGeometry":
        """What layer ``layer``'s attention is made of, by its kind."""
        swa = "swa_" if self.slides(layer) else ""
        rank_q = getattr(self, swa + "q_lora_rank")
        rank_kv = getattr(self, swa + "kv_lora_rank")
        rescale = self.lora_rescale
        return AttentionGeometry(
            heads=getattr(self, swa + "num_heads"),
            q_lora_rank=rank_q,
            kv_lora_rank=rank_kv,
            nope=getattr(self, swa + "qk_nope_head_dim"),
            rope=getattr(self, swa + "qk_rope_head_dim"),
            v=getattr(self, swa + "v_head_dim"),
            theta=getattr(self, swa + "rope_theta"),
            window=self.sliding_window if swa else 0,
            gate=self.attention_gate,
            q_scale=(self.hidden_size / rank_q) ** 0.5 if rescale else 1.0,
            kv_scale=(self.hidden_size / rank_kv) ** 0.5 if rescale else 1.0,
        )


@dataclass(frozen=True)
class AttentionGeometry:
    """One attention layer's shapes (``GlmMoeLiteConfig.geometry``): a head is
    ``nope`` | ``rope`` dims wide against the keys and ``v`` against the
    values; ``window`` > 0 keys a query (its own among them), 0 every key
    before it; ``q_scale`` and ``kv_scale`` multiply the normalised latents."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: int
    gate: bool
    q_scale: float
    kv_scale: float

    @property
    def head_dim(self) -> int:
        """The published width of a head against the keys."""
        return self.nope + self.rope

    @property
    def laid(self) -> int:
        """Lanes a head takes in the served arrays: a head wider than one
        128-lane column is laid in whole columns (192 -> 256, zero lanes
        between the nope and the rope dims, so that the rotary lanes stay the
        head's last and inside one column); q.k is what it was."""
        dq = self.head_dim
        return dq if dq <= 128 else -(-dq // 128) * 128


# zai-org/GLM-4.7-Flash config.json
GLM_4_7_FLASH = GlmMoeLiteConfig()
GLM_TEST_TINY = GlmMoeLiteConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=3,
    num_heads=4,
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_head_dim=24,
    qk_rope_head_dim=8,
    v_head_dim=32,
    intermediate_size=128,
    moe_intermediate_size=48,
    n_routed_experts=8,
    num_experts_per_tok=2,
)


# zai-org/GLM-5.2 config.json (``glm_moe_dsa``): three leading dense layers;
# a layer owns an indexer where it is dense or every fourth one from layer 6
GLM_5_2 = GlmMoeLiteConfig(
    hidden_size=6144,
    num_layers=78,
    num_heads=64,
    q_lora_rank=2048,
    intermediate_size=12288,
    moe_intermediate_size=2048,
    n_routed_experts=256,
    num_experts_per_tok=8,
    routed_scaling_factor=2.5,
    first_k_dense_replace=3,
    rope_theta=8e6,
    indexer_types=tuple(
        "full" if i < 3 or (i - 2) % 4 == 0 else "shared" for i in range(78)
    ),
    index_n_heads=32,
    index_head_dim=128,
    index_topk=2048,
)
# one period of the indexer pattern behind one dense layer, as the cell cuts it
GLM_DSA_TEST_TINY = GlmMoeLiteConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=5,
    num_heads=4,
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_head_dim=24,
    qk_rope_head_dim=8,
    v_head_dim=32,
    intermediate_size=128,
    moe_intermediate_size=48,
    n_routed_experts=16,
    num_experts_per_tok=2,
    routed_scaling_factor=2.5,
    rope_theta=8e6,
    index_n_heads=4,
    index_head_dim=16,
    index_topk=32,
    indexer_types=("full", "shared", "shared", "shared", "full"),
)


# dots-studio/dots3-note-prev config.json (``dots3_note``): one leading dense
# layer; full attention (an indexer each) on layers 0, 1 and every fourth
# from 5, sliding attention of its own geometry on the others
DOTS3_NOTE_PREV = GlmMoeLiteConfig(
    vocab_size=152064,
    hidden_size=5120,
    num_layers=46,
    num_heads=128,
    q_lora_rank=1024,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    intermediate_size=13824,
    moe_intermediate_size=1536,
    n_routed_experts=256,
    num_experts_per_tok=8,
    routed_scaling_factor=1.0,
    first_k_dense_replace=1,
    rope_theta=8e7,
    index_n_heads=64,
    index_head_dim=128,
    index_topk=2048,
    layer_types=tuple(
        "full_attention" if i == 0 or i % 4 == 1 else "sliding_attention" for i in range(46)
    ),
    sliding_window=513,
    swa_num_heads=64,
    swa_q_lora_rank=1024,
    swa_kv_lora_rank=1024,
    swa_qk_nope_head_dim=192,
    swa_qk_rope_head_dim=64,
    swa_v_head_dim=128,
    swa_rope_theta=5e4,
    attention_gate=True,
    lora_rescale=True,
)
# the cut's order (full dense, full sparse, three sliding sparse); a window
# far shorter than the tests' sequences, value heads narrower than key heads
DOTS3_TEST_TINY = GlmMoeLiteConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=5,
    num_heads=4,
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    intermediate_size=128,
    moe_intermediate_size=48,
    n_routed_experts=16,
    num_experts_per_tok=2,
    routed_scaling_factor=1.0,
    rope_theta=8e7,
    index_n_heads=4,
    index_head_dim=16,
    index_topk=32,
    layer_types=(
        "full_attention", "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention",
    ),
    sliding_window=17,
    swa_num_heads=2,
    swa_q_lora_rank=32,
    swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8,
    swa_v_head_dim=16,
    swa_rope_theta=5e4,
    attention_gate=True,
    lora_rescale=True,
)


@dataclass(frozen=True)
class Qwen3NextConfig:
    """A causal decoder of gated delta-rule layers with a gated full-attention
    layer every ``full_attention_interval``-th, every layer's second half
    sparse (``model_type`` ``qwen3_next``): a judge behind ``POST /consensus``
    ``scorer: judge`` (models/qwen3_next.py).

    ``num_layers`` and ``num_experts`` are the published counts; a checkpoint
    that names fewer layers (one pipeline stage of a deployment) or experts
    0..E-1 of the router's ``num_experts`` (one chip's share where several
    share a layer's experts) is served with the layers and experts it names
    (``qwen3_next.from_hf_weights``).  The router stays ``num_experts`` wide."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    # "int8": the dense products (both token mixers' projections, the shared
    # expert) through quant.dense_int8; router, routed experts, the delta
    # rule, embedding and head keep the parameters' dtype
    quantize: str = "none"

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


# Qwen/Qwen3-Next-80B-A3B-Instruct config.json
QWEN3_NEXT_80B_A3B = Qwen3NextConfig()
QWEN3_NEXT_TEST_TINY = Qwen3NextConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=32,
    linear_num_key_heads=2,
    linear_num_value_heads=4,
    linear_key_head_dim=16,
    linear_value_head_dim=16,
    moe_intermediate_size=32,
    shared_expert_intermediate_size=32,
    num_experts=16,
    num_experts_per_tok=4,
)


@dataclass(frozen=True)
class AfmoeConfig:
    """A causal decoder of grouped-query attention of TWO kinds in one stack
    (``model_type`` ``afmoe``, arcee-ai/Trinity-Large-Preview): a
    ``sliding_attention`` layer turns its heads (rotary over all of a head's
    dims) and attends the ``sliding_window`` keys up to and with its own
    position, a ``full_attention`` layer turns nothing and attends every key
    before it; every head's output behind an elementwise sigmoid gate, four
    RMSNorms a layer (before each branch and on each branch's OUTPUT, before
    the sum), the first ``num_dense_layers`` layers dense, the others sparse
    with one shared expert: a judge behind ``POST /consensus`` ``scorer:
    judge`` (models/afmoe.py).

    ``num_layers``, ``num_experts`` and ``vocab_size`` are the published
    counts.  A checkpoint that names a RUN of the published layers
    (``model.layers.5`` .. ``model.layers.9``: one pipeline stage of a
    deployment) is served at those layers, each of the kind ``layer_types``
    gives its published number; experts 0..E-1 of the router's
    ``num_experts`` (one chip's share where several share a layer's experts)
    and the rows of the vocabulary it holds likewise
    (``afmoe.from_hf_weights``).  The router stays ``num_experts`` wide."""

    vocab_size: int = 200192
    hidden_size: int = 3072
    num_layers: int = 60
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_dense_layers: int = 6
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.448
    # "sliding_attention" | "full_attention" a layer
    layer_types: tuple = tuple(
        "full_attention" if i % 4 == 3 else "sliding_attention" for i in range(60)
    )
    sliding_window: int = 4096  # keys a query attends, its own among them
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True  # the embedding times sqrt(hidden_size)
    # "int8": the dense products (the attention's five, a dense layer's MLP,
    # the shared expert) through quant.dense_int8; router, routed experts,
    # embedding and head keep the parameters' dtype
    quantize: str = "none"

    def slides(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers


# arcee-ai/Trinity-Large-Preview config.json (``afmoe``): six leading dense
# layers, a full layer every fourth, the others over a window of 4096 keys
TRINITY_LARGE_PREVIEW = AfmoeConfig()
# the cell's order (sliding dense; sliding, full, sliding, sliding sparse), six
# query heads a key head as published, a window shorter than the tests' sequences
AFMOE_TEST_TINY = AfmoeConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=5,
    num_heads=12,
    num_kv_heads=2,
    head_dim=16,
    intermediate_size=128,
    moe_intermediate_size=64,
    num_dense_layers=1,
    num_experts=16,
    num_experts_per_tok=2,
    layer_types=(
        "sliding_attention", "sliding_attention", "full_attention", "sliding_attention",
        "sliding_attention",
    ),
    sliding_window=24,
)


@dataclass(frozen=True)
class Phi4FlashConfig:
    """A decoder that feeds a decoder (``model_type`` ``phi4flash``,
    microsoft/Phi-4-mini-flash-reasoning: SambaY).  Layers 0 .. ``kv_layer``
    (the SELF decoder) alternate Mamba layers (even) with differential
    attention (odd): sliding over ``sliding_window`` keys but for the last,
    ``kv_layer``, which is full and whose keys and values are THE cache.
    The layers behind it (the CROSS decoder) alternate gated memory units
    (even: a gate on the scan output of Mamba layer ``kv_layer - 1`` at the
    same position) with cross attention (odd: a query against ``kv_layer``'s
    keys and values, none of its own).  LayerNorm with a bias, a SwiGLU MLP
    every layer, a tied head, no rotary turn anywhere: a judge behind ``POST
    /consensus`` ``scorer: judge`` (models/sambay.py).

    Differential attention pairs consecutive heads: query heads (2j, 2j + 1)
    are the two softmaxes of pair j, key heads (2m, 2m + 1) the keys of each
    for the query pairs with j // (``num_heads`` / ``num_kv_heads``) == m, and
    value heads (2m, 2m + 1) side by side are ONE value head of twice
    ``head_dim`` that both softmaxes weigh."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512  # keys a query attends, its own among them
    mb_per_layer: int = 2  # a Mamba layer every second layer of the self decoder
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    layer_norm_eps: float = 1e-5
    # "int8": every dense product through quant.dense_int8; embedding (the
    # tied head), norms, lambdas, biases and the scan's own parameters keep
    # the parameters' dtype
    quantize: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def kv_layer(self) -> int:
        """The full-attention layer whose keys and values every cross layer
        reads: the last of the self decoder, which is half the stack and one."""
        return self.num_layers // 2 + 1

    def kind(self, layer: int) -> str:
        """``mamba`` | ``sliding`` | ``full`` | ``memory`` | ``cross``."""
        if layer % self.mb_per_layer == 0:
            return "mamba" if layer < self.kv_layer else "memory"
        if layer < self.kv_layer:
            return "sliding"
        return "full" if layer == self.kv_layer else "cross"

    def lambda_init(self, layer: int) -> float:
        """The differential weight's constant part at depth ``layer``."""
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


# microsoft/Phi-4-mini-flash-reasoning config.json (``phi4flash``), uncut
PHI_4_MINI_FLASH_REASONING = Phi4FlashConfig()
# every kind of layer in eight: 0..3 the self decoder's pairs (Mamba, sliding),
# 4 the memory's Mamba, 5 the full layer, 6 a memory unit, 7 a cross layer; a
# window shorter than the tests' sequences, two query pairs a key pair as published
PHI4FLASH_TEST_TINY = Phi4FlashConfig(
    vocab_size=512,
    hidden_size=64,
    num_layers=8,
    num_heads=8,
    num_kv_heads=4,
    intermediate_size=128,
    sliding_window=8,
)


@dataclass(frozen=True)
class FalconH1Config:
    """A decoder whose every block runs a Mamba-2 (SSD) mixer and grouped-query
    attention SIDE BY SIDE on one normed input and sums them, then a SwiGLU
    MLP (``model_type`` ``falcon_h1``, tiiuae/Falcon-H1-34B-Instruct): a judge
    behind ``POST /consensus`` ``scorer: judge`` (models/falcon_h1.py).  Every
    layer is the same kind.  Every product stands behind one of the published
    µP multipliers, each a key of the configuration: the embedding's, the
    head's, the attention's input, output and keys', the mixer's input, output
    and the five of its input product's sections (z, x, B, C, dt), the MLP's
    gate and output.

    The mixer is ``ssm_heads`` heads of ``ssm_head_dim`` (``d_ssm`` wide: where
    the configuration gives ``mamba_d_ssm`` its ``mamba_expand`` does not
    apply) with ONE decay a head, B and C of ``d_state`` shared by the heads
    of each of ``ssm_groups`` groups, a causal convolution of ``d_conv`` taps
    a channel over [x | B | C], and an RMSNorm over the gated scan output in
    ``ssm_groups`` groups (``mamba_rms_norm`` true, ``norm_before_gate``
    false).  The attention turns all ``head_dim`` dims of every head
    (``rope_theta`` 1e11), a key head serving ``num_heads / num_kv_heads``
    query heads, no bias anywhere."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    d_ssm: int = 4096
    d_state: int = 256
    d_conv: int = 4
    ssm_heads: int = 32
    ssm_groups: int = 2
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over the input product's sections [z | x | B | C | dt]
    ssm_multipliers: tuple = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738,
    )
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)  # gate, down
    # "int8": every dense product (the mixer's input and output products, q, k,
    # v, o, the MLP's three) through quant.dense_int8; embedding, head, norms,
    # the convolution and the scan's own parameters keep the parameters' dtype
    quantize: str = "none"

    @property
    def ssm_head_dim(self) -> int:
        return self.d_ssm // self.ssm_heads

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.d_ssm + 2 * self.ssm_groups * self.d_state


# tiiuae/Falcon-H1-34B-Instruct config.json (``falcon_h1``)
FALCON_H1_34B_INSTRUCT = FalconH1Config()
# three query heads a key head and three mixer heads a group (neither a power of
# two: a head that read group j % 2 would read the wrong B and C), every
# multiplier a value of its own so that one dropped or swapped shows
FALCON_H1_TEST_TINY = FalconH1Config(
    vocab_size=512,
    hidden_size=64,
    num_layers=2,
    num_heads=6,
    num_kv_heads=2,
    head_dim=16,
    intermediate_size=128,
    d_ssm=48,
    d_state=16,
    ssm_heads=6,
    ssm_groups=2,
    embedding_multiplier=1.7,
    lm_head_multiplier=0.6,
    attention_in_multiplier=1.3,
    attention_out_multiplier=0.45,
    key_multiplier=0.8,
    ssm_in_multiplier=0.75,
    ssm_out_multiplier=0.55,
    ssm_multipliers=(0.9, 1.2, 0.7, 1.4, 0.65),
    mlp_multipliers=(0.85, 0.35),
)
