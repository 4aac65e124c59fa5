"""Sharding rules: batch over ``dp``, encoder tensor parallelism over ``tp``.

The recipe (How to Scale Your Model, public jax-ml scaling book): pick a
mesh, annotate input/param shardings with NamedSharding, let XLA insert the
collectives.  For the BERT encoder the TP layout is the Megatron split:

* attention q/k/v kernels column-split over heads  -> P(None, "tp")
* attention output kernel row-split                -> P("tp", None)
* MLP in column-split, MLP out row-split           -> P(None, "tp"), P("tp", None)
* embeddings + layernorms replicated               -> P()

so each layer does two reduce-scatters' worth of comms (XLA chooses
all-reduce/reduce-scatter over ICI).  Batches shard over ``dp``.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp", None))


def ring_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Input sharding for the long-context ring dispatch: batch rows over
    ``dp`` AND the sequence axis over ``sp`` — the sp-aware twin of
    ``batch_sharding``.  Requires a 3-axis (dp, tp, sp) mesh
    (``make_mesh(..., sp=N)``)."""
    if "sp" not in mesh.shape:
        raise ValueError(
            "ring_batch_sharding needs an 'sp' mesh axis "
            f"(got axes {tuple(mesh.shape)})"
        )
    return NamedSharding(mesh, P("dp", "sp"))


# Leading axis of every stacked layer param is the layer index (scanned) —
# shardings below apply to [layer, in, out] kernels / [layer, dim] biases.
_TP_LAYER_SPECS = {
    "attn_q": {"kernel": P(None, None, "tp"), "bias": P(None, "tp")},
    "attn_k": {"kernel": P(None, None, "tp"), "bias": P(None, "tp")},
    "attn_v": {"kernel": P(None, None, "tp"), "bias": P(None, "tp")},
    "attn_out": {"kernel": P(None, "tp", None), "bias": P(None)},
    "attn_ln": {"scale": P(None), "bias": P(None)},
    "mlp_in": {"kernel": P(None, None, "tp"), "bias": P(None, "tp")},
    "mlp_out": {"kernel": P(None, "tp", None), "bias": P(None)},
    "mlp_ln": {"scale": P(None), "bias": P(None)},
}

def _quantized_layer_specs() -> dict:
    """The int8 twin of _TP_LAYER_SPECS, derived mechanically so the two
    tables cannot drift: kernel_q shards like the full-precision kernel;
    the per-output-channel scale follows the kernel's OUT (last) axis, so
    it splits with column-split kernels and replicates with row-split
    ones; bias/layernorm leaves are unchanged.  Row-split dequant
    commutes with the tp all-reduce (scales are identical across shards:
    sx[row] * sw[out] * sum(partials) == sum(sx * sw * partials)), so
    the layout needs no numerics caveats."""
    out = {}
    for name, leaf in _TP_LAYER_SPECS.items():
        if "kernel" not in leaf:
            out[name] = dict(leaf)
            continue
        kspec = leaf["kernel"]
        out[name] = {
            "kernel_q": kspec,
            "scale": P(None, kspec[-1]),  # [layer, out]: follows OUT
            "bias": leaf["bias"],
        }
    return out


_TP_LAYER_SPECS_Q = _quantized_layer_specs()


def bert_param_specs(tp: bool = True, quantized: bool = False) -> dict:
    """PartitionSpec pytree matching models.bert param layout."""
    base = _TP_LAYER_SPECS_Q if quantized else _TP_LAYER_SPECS
    layer = (
        base
        if tp
        else {
            name: {k: P(None) for k in leaf}
            for name, leaf in base.items()
        }
    )
    return {
        "token_embed": P(),
        "position_embed": P(),
        "type_embed": P(),
        "embed_ln": {"scale": P(), "bias": P()},
        "layers": layer,
    }


# --- first-class partition-rule tables -------------------------------------
#
# The dict tables above mirror the param tree shape; the rule tables below
# are the audit-friendly dual: an ordered list of (name, anchored-regex,
# spec) matched against the "/"-joined leaf path.  The contract the mesh
# audit (analysis/mesh_audit.py, JXA006) enforces: every leaf matches
# EXACTLY one rule, and every rule matches at least one leaf.

_COL = ("attn_q", "attn_k", "attn_v", "mlp_in")
_ROW = ("attn_out", "mlp_out")


def _encoder_rules(quantized: bool = False) -> tuple:
    """Shared bert/deberta encoder-layer rules.  ``quantized`` swaps the
    dense kernel leaf for the int8 (kernel_q, scale) pair — scale is
    per-OUT-channel, so it follows the kernel's last axis (split for
    column kernels, replicated for row kernels)."""
    col = "|".join(_COL)
    row = "|".join(_ROW)
    kernel = "kernel_q" if quantized else "kernel"
    rules = [
        (
            "layer_col_kernel",
            rf"layers/({col})/{kernel}",
            P(None, None, "tp"),
        ),
        ("layer_col_bias", rf"layers/({col})/bias", P(None, "tp")),
        (
            "layer_row_kernel",
            rf"layers/({row})/{kernel}",
            P(None, "tp", None),
        ),
        ("layer_row_bias", rf"layers/({row})/bias", P(None)),
        ("layer_ln", r"layers/(attn_ln|mlp_ln)/(scale|bias)", P(None)),
    ]
    if quantized:
        rules[2:2] = [
            ("layer_col_scale", rf"layers/({col})/scale", P(None, "tp")),
        ]
        rules[5:5] = [
            ("layer_row_scale", rf"layers/({row})/scale", P(None, None)),
        ]
    return tuple(rules)


def bert_partition_rules(quantized: bool = False) -> tuple:
    """Ordered (name, regex, PartitionSpec) rules for models.bert trees."""
    return (
        ("embed_tables", r"(token|position|type)_embed", P()),
        ("embed_ln", r"embed_ln/(scale|bias)", P()),
    ) + _encoder_rules(quantized=quantized)


def deberta_partition_rules(quantized: bool = False) -> tuple:
    """Rules for models.deberta trees: bert encoder plus disentangled
    position projections (column-split like q/k) and the reward head
    (Megatron pair: dense column-split, scalar out row-split)."""
    return (
        ("embed_tables", r"(token|rel)_embed", P()),
        ("embed_ln", r"(embed|rel)_ln/(scale|bias)", P()),
        # pos_q/pos_k stay full-precision even in int8 trees (models.quant
        # quantizes the six content/MLP kernels only)
        ("pos_proj_kernel", r"layers/(pos_q|pos_k)/kernel", P(None, None, "tp")),
        ("pos_proj_bias", r"layers/(pos_q|pos_k)/bias", P(None, "tp")),
        ("head_dense_kernel", r"head_dense/kernel", P(None, "tp")),
        ("head_dense_bias", r"head_dense/bias", P("tp")),
        ("head_out_kernel", r"head_out/kernel", P("tp", None)),
        ("head_out_bias", r"head_out/bias", P(None)),
    ) + _encoder_rules(quantized=quantized)


def partition_rules_for(arch: str, quantized: bool = False) -> tuple:
    if arch == "bert":
        return bert_partition_rules(quantized=quantized)
    if arch == "deberta":
        return deberta_partition_rules(quantized=quantized)
    raise ValueError(f"no partition rules for arch {arch!r}")


def tree_path_leaves(tree: dict) -> list:
    """[(\"layers/attn_q/kernel\", leaf), ...] — "/"-joined string paths."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for key in path:
            parts.append(str(getattr(key, "key", getattr(key, "idx", key))))
        out.append(("/".join(parts), leaf))
    return out


def match_report(rules: tuple, tree: dict) -> tuple:
    """Audit-grade matching: (leaf_matches, rule_counts) where
    leaf_matches maps each leaf path to the LIST of matching rule names
    (so callers can flag both uncovered and ambiguous leaves) and
    rule_counts maps each rule name to its total match count (0 = dead
    rule)."""
    leaf_matches: dict = {}
    rule_counts = {name: 0 for name, _, _ in rules}
    for path, _leaf in tree_path_leaves(tree):
        hits = [
            name
            for name, pattern, _spec in rules
            if re.fullmatch(pattern, path)
        ]
        leaf_matches[path] = hits
        for name in hits:
            rule_counts[name] += 1
    return leaf_matches, rule_counts


def match_partition_rules(rules: tuple, tree: dict) -> dict:
    """Param tree -> PartitionSpec tree via the rule table (the
    match_partition_rules shape used by the big public jax LLM repos).
    First matching rule wins; a leaf no rule matches is an error — the
    rule table, not a silent replicate default, is the source of truth."""
    specs = {name: spec for name, _, spec in rules}
    compiled = [(name, pattern) for name, pattern, _ in rules]

    leaves = tree_path_leaves(tree)
    spec_by_path = {}
    for path, _leaf in leaves:
        for name, pattern in compiled:
            if re.fullmatch(pattern, path):
                spec_by_path[path] = specs[name]
                break
        else:
            raise ValueError(f"no partition rule matches param leaf {path!r}")

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out_leaves = []
    for path, _leaf in flat:
        parts = "/".join(
            str(getattr(key, "key", getattr(key, "idx", key))) for key in path
        )
        out_leaves.append(spec_by_path[parts])
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def strip_tp(spec_tree):
    """Replace the "tp" axis with None everywhere (tp=1 / TP-off layout)."""
    return jax.tree_util.tree_map(
        lambda spec: P(*(None if axis == "tp" else axis for axis in spec)),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_by_rules(
    params: dict, mesh: Mesh, rules: tuple, tp: bool = True
) -> dict:
    """Place a param pytree on the mesh per the rule table."""
    specs = match_partition_rules(rules, params)
    if not (tp and mesh.shape.get("tp", 1) > 1):
        specs = strip_tp(specs)
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_bert_params(params: dict, mesh: Mesh, tp: bool = True) -> dict:
    """Place a bert param pytree on the mesh with the TP layout."""
    from ..models.quant import is_quantized

    rules = bert_partition_rules(quantized=is_quantized(params))
    return shard_by_rules(params, mesh, rules, tp=tp)


def gspmd_config(config):
    """The model config a GSPMD-partitioned forward must run under.

    A Mosaic kernel cannot ride jit's automatic partitioning ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map" — found on the first four-chip run; a CPU mesh never
    takes these branches), so the two choices that resolve to a Pallas
    kernel on a TPU are pinned to their XLA twins here: ``"auto"``
    attention (the fused kernel from s=512) becomes ``"einsum"`` and
    ``quantize="int8"`` (the fused W8A8 matmul) becomes ``"int8-xla"``.
    A PINNED kernel mode — ``"fused"``, ``"int8-pallas"``,
    ``"int4-pallas"`` — is left alone and fails at compile with that
    message: the name says which path runs."""
    import dataclasses

    changes = {}
    if getattr(config, "attention_impl", None) == "auto":
        changes["attention_impl"] = "einsum"
    if config.quantize == "int8":
        changes["quantize"] = "int8-xla"
    return dataclasses.replace(config, **changes) if changes else config


def shard_embedder_mesh(embedder, mesh: Mesh) -> None:
    """First-class mesh serving (``MESH_ENABLED``, serve/config.py).

    Params are placed once at load by the partition-rule tables (batch
    rows over ``dp``, encoder kernels Megatron-split over ``tp``),
    dispatch inputs get real NamedShardings, and the embedder flips into
    mesh mode so its AOT table lowers per-(mesh-shape, bucket)
    executables with the input shardings baked in (models/embedder.py).
    ``batch_multiple = dp`` makes the embedder pad every dispatch to a dp
    multiple, so the split always divides; ``put_batch``'s replicated
    fallback is a safety net for direct callers only.

    With an ``sp`` axis on the mesh (``MESH_SHAPE=dp,tp,sp``), the dense
    dispatch path is UNCHANGED — same shardings, same batch_multiple,
    same AOT keys as the 2-axis mesh (short traffic replicates over sp)
    — and the embedder additionally gains the ring dispatch state:
    ``mesh_sp``, a (dp, sp)-sharded input sharding, the ring token cap
    (position window rounded down to an sp multiple), and a ring-mode
    twin of its config.  ``MESH_SHAPE`` without sp therefore stays
    byte-identical to the pre-sp serving path.
    """
    import dataclasses

    from ..models.quant import is_quantized

    dp = mesh.shape["dp"]
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    rules = bert_partition_rules(quantized=is_quantized(embedder.params))
    embedder.params = shard_by_rules(embedder.params, mesh, rules, tp=tp > 1)
    embedder.config = gspmd_config(embedder.config)
    b_sharding = batch_sharding(mesh)
    repl = replicated(mesh)

    def put_batch(ids, mask):
        s = b_sharding if ids.shape[0] % dp == 0 else repl
        return jax.device_put(ids, s), jax.device_put(mask, s)

    embedder.put_batch = put_batch
    embedder.batch_multiple = dp
    embedder.mesh = mesh
    embedder.mesh_shape = (dp, tp)
    embedder.batch_sharding = b_sharding
    embedder.repl_sharding = repl
    embedder.mesh_mode = True
    embedder.mesh_sp = sp
    if sp > 1:
        from ..models.configs import usable_positions

        embedder.ring_sharding = ring_batch_sharding(mesh)
        # ring sequences pad to an sp multiple; cap so padding can never
        # push past the position table
        embedder.ring_max_tokens = (
            usable_positions(embedder.config) // sp
        ) * sp
        embedder._ring_config = dataclasses.replace(
            embedder.config, attention_impl="ring", ring_axis="sp"
        )
    else:
        embedder.ring_sharding = None
        embedder.ring_max_tokens = None
        embedder._ring_config = None


def shard_reranker_mesh(reranker, mesh: Mesh) -> None:
    """Place a models.reranker.TpuReranker's DeBERTa params on the mesh
    by its rule table (``MESH_ENABLED``).  The reward forward then rides
    GSPMD from the param shardings alone — its softmax normalizes over
    exactly one request's candidates, so the batch stays unsharded."""
    from ..models.quant import is_quantized

    tp = mesh.shape.get("tp", 1)
    rules = deberta_partition_rules(quantized=is_quantized(reranker.params))
    reranker.params = shard_by_rules(reranker.params, mesh, rules, tp=tp > 1)
    reranker.config = gspmd_config(reranker.config)
    reranker.mesh = mesh
