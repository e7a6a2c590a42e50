"""Regex partition rules: param path -> PartitionSpec.

The standard JAX pattern for declaring how each parameter shards over the
mesh (SNIPPETS.md [1] `match_partition_rules`-style, public pattern): rules
are (regex, PartitionSpec) pairs matched against the '/'-joined param path;
first match wins. Used by MeshPartitioner for tensor-parallel / FSDP
layouts while data parallelism needs no rules at all.
"""

import re
from typing import Any, List, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec

PartitionRule = Tuple[str, PartitionSpec]


def conv_model_tp_rules(model_axis: str = "model") -> List[PartitionRule]:
    """Tensor-parallel rules for the conv model zoo (QuickNet, Bi-Real-Net,
    BinaryNet, SimpleCnn, ResNet).

    Every conv/dense kernel shards its OUTPUT-feature dim over
    ``model_axis``; per-channel BatchNorm params and batch_stats co-shard
    on the same axis (activations downstream of a sharded conv are
    channel-sharded, so the stats reductions stay local to the shard).
    XLA inserts the input-channel contraction all-reduces per layer —
    standard conv TP over ICI. Rules are matched against full state paths,
    so Adam moments co-shard with their parameters automatically.
    """
    P = PartitionSpec
    return [
        # Depthwise kernels replicate (first match wins — their tied
        # input/output channels would otherwise match the dense-conv
        # rule below and force GSPMD resharding of the grouped conv).
        (r"QuantDepthwiseConv_\d+/", P()),
        # Packed binary kernels [kh, kw, ci_words, co]: shard co.
        (r"kernel_packed$", P(None, None, None, model_axis)),
        (r"kernel_scale$", P(model_axis)),
        # HWIO conv kernels: shard output features.
        (r"(QuantConv|Conv)_\d+/kernel$", P(None, None, None, model_axis)),
        # Dense kernels [in, out]: shard out (incl. the classifier head).
        (r"(QuantDense|Dense)_\d+/kernel$", P(None, model_axis)),
        (r"(QuantDense|Dense)_\d+/bias$", P(model_axis)),
        # Per-channel BN params + running stats co-shard with channels.
        (r"BatchNorm_\d+/(scale|bias)$", P(model_axis)),
        (r"batch_stats/.*/(mean|var)$", P(model_axis)),
    ]


def transformer_tp_rules(model_axis: str = "model") -> List[PartitionRule]:
    """Megatron-style tensor-parallel rules for the TransformerLM
    family: the fused qkv and MLP up projections are COLUMN-parallel
    (output features over ``model_axis``), the attention output and MLP
    down projections ROW-parallel (input features over ``model_axis``)
    — each block then needs exactly one all-reduce per projection pair
    (Korthikanti et al., 2022; XLA inserts it from the shardings).
    Embedding / positional tables and RMSNorm scales replicate (the
    weight-tied LM head reads the replicated embedding). Matched
    against full state paths, so Adam moments co-shard automatically.
    """
    P = PartitionSpec
    # (^|/)-anchored segment names: re.search on '/'-joined paths would
    # otherwise shard any layer merely ENDING in one of these names
    # ('warmup/kernel', 'breakdown/kernel') on the wrong axis, silently.
    return [
        (r"(^|/)(qkv|up)/kernel$", P(None, model_axis)),
        (r"(^|/)(proj|down)/kernel$", P(model_axis, None)),
    ]


def page_pool_rules(
    data_axes: Sequence[str] = ("data",),
    model_axis: str = None,
) -> List[PartitionRule]:
    """Partition rules for a decode engine's SHARED page-pool state
    tree (``serving.decode.pages``, docs/DESIGN.md §20): the per-layer
    ``k``/``v`` pools are ``[num_pages, head_shards, page_size,
    row_width]`` and the PAGES dimension cannot shard over the data
    axes: any slot may reference any page through its page table, so a
    data-sharded pool would need a cross-device gather per read. The
    HEAD SHARDS dimension (one entry per model-axis device, each
    holding its heads folded end to end) shards over ``model_axis``
    when one exists, matching :func:`transformer_tp_rules`, whose
    column-parallel qkv kernel produces head-sharded K/V in the first
    place: the decode program writes and reads K/V without any
    resharding collective. The int8 scale arrays ``[num_pages,
    head_shards, page_size, heads_per_shard]`` co-shard the same
    dimension. ``data_axes`` is
    accepted for signature parity (the q/lengths/table OPERANDS shard
    over it — see ``ops.sharded_pool_paged_decode_attention``) but the
    pool state itself replicates over it."""
    P = PartitionSpec
    return [
        (
            r"(^|/)(k|v|k_scale|v_scale)$",
            P(None, model_axis, None, None),
        ),
    ]


def auto_fsdp_rules(
    params: Any,
    axis_size: int,
    fsdp_axis: str = "fsdp",
    min_weight_size: int = 2**15,
    replicate_patterns: Sequence[str] = (),
) -> List[PartitionRule]:
    """Generate ZeRO-3-style weight-sharding rules from a params tree.

    Each parameter with at least ``min_weight_size`` elements AND rank
    >= 2 shards its largest ``axis_size``-divisible dimension over
    ``fsdp_axis`` (ties prefer the trailing dim — output features,
    matching the TP layout convention); everything else (biases, BN
    scale/shift — 1-D per-channel vectors) replicates REGARDLESS of
    ``min_weight_size``: the memory saved is negligible, and sharding a
    per-channel vector makes its weight-gradient reduction want a
    channel-sharded activation cotangent, which GSPMD can only reach
    from the batch-sharded layout by full rematerialization (the
    "[SPMD] Involuntary full rematerialization" warning observed on
    BatchNorm backward under FSDP). Rules are suffix-anchored on the
    params-relative path, so optimizer moments and EMA copies co-shard
    with their parameter automatically.

    This is the standard JAX FSDP recipe (scaling-book style): with the
    batch sharded over the SAME mesh axis, XLA all-gathers each layer's
    weights on use (fwd + bwd) and reduce-scatters its gradients —
    per-device param/optimizer memory drops ~axis_size-fold for the
    sharded weights, paid for with weight all-gather traffic over ICI.

    ``replicate_patterns``: regexes over params-relative paths forced to
    replicate regardless of size; matched with ``re.search``, so anchor
    them (``"^Conv_1/"``) — a bare ``"Conv_1/"`` also matches inside
    ``"QuantConv_1/kernel"``. The known case that needs it: a LARGE
    grouped/depthwise conv kernel — its weight gradient lowers to a
    ``batch_group_count`` conv whose GSPMD partitioning demands a
    channel-sharded cotangent, reachable from the batch-sharded layout
    only by full rematerialization (same pathology class the TP rules
    dodge by replicating ``QuantDepthwiseConv``). Grouped kernels below
    ``min_weight_size`` (typical stems) replicate naturally.
    """
    from math import prod

    from flax import traverse_util

    replicate_res = [re.compile(p) for p in replicate_patterns]
    flat = traverse_util.flatten_dict(params, sep="/")
    rules: List[PartitionRule] = []
    for path, leaf in flat.items():
        shape = tuple(getattr(leaf, "shape", ()))
        size = prod(shape) if shape else 0
        spec = PartitionSpec()
        forced = any(r.search(path) for r in replicate_res)
        if not forced and size >= min_weight_size and len(shape) >= 2:
            best = None
            for i, d in enumerate(shape):
                if d % axis_size == 0 and (best is None or d >= shape[best]):
                    best = i
            if best is not None:
                spec = PartitionSpec(
                    *[
                        fsdp_axis if i == best else None
                        for i in range(len(shape))
                    ]
                )
        # EVERY param gets its own explicit rule (small ones an explicit
        # replicate), and rules sort deepest-first below: a nested path
        # like "Head_0/Dense_0/kernel" then always hits its own rule
        # before a shallower param's suffix rule ("Dense_0/kernel") could
        # capture it. The (^|/) left boundary blocks same-segment prefix
        # capture ("QuantDense_0" vs "Dense_0").
        rules.append(((r"(^|/)" + re.escape(path) + "$"), spec))
    # Deepest-first: a path is never shadowed by a strict suffix of
    # itself (which necessarily has fewer segments).
    rules.sort(key=lambda r: -r[0].count("/"))
    return rules


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):  # DictKey
            parts.append(str(p.key))
        elif hasattr(p, "idx"):  # SequenceKey
            parts.append(str(p.idx))
        elif hasattr(p, "name"):  # GetAttrKey (dataclass fields)
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_partition_rules(
    rules: Sequence[PartitionRule], tree: Any
) -> Any:
    """Map every leaf of ``tree`` to the PartitionSpec of the first rule
    whose regex searches its '/'-joined path; unmatched leaves replicate
    (``PartitionSpec()``)."""

    def assign(path, leaf):
        path_s = _path_str(path)
        for pattern, spec in rules:
            if re.search(pattern, path_s):
                return spec
        return PartitionSpec()

    return jax.tree_util.tree_map_with_path(assign, tree)
