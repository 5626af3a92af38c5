"""Logical-axis sharding rules: the TPU-native replacement for DDP/FSDP wrap.

Where the reference wraps a torch module per-strategy (DDP
`train/torch/train_loop_utils.py:75 prepare_model`, FSDP/ZeRO via Lightning &
DeepSpeed integrations — SURVEY.md §2.6), the TPU design annotates model
parameters and activations with *logical* axis names once, and a rule table
maps those names onto mesh axes. Changing parallelism strategy = changing the
rule table, not the model.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical name → mesh axis (or tuple of axes, or None for replicated)
Rules = Sequence[Tuple[str, Any]]

# Default rules: FSDP shards weights along the embed dimension, TP shards the
# head/mlp/vocab dimensions, batch splits over (dp, fsdp), sequence over sp.
# Activation dims get distinct logical names ("act_*") so one PartitionSpec
# never consumes the same mesh axis twice (weights shard embed over fsdp;
# activations keep embed replicated and shard batch over dp+fsdp).
# "embed_vector" is a vector as long as the embedding (a LayerNorm's scale and
# bias, the bias of a layer's last product): every chip holds it whole. Sharded
# over fsdp as "embed" is, its 8 KB are gathered a layer by a synchronous
# collective that queues behind the weights on the same links (0.5 ms a time in
# the four-chip GPT-J step: PERF.md section 6, PR 44).
# "act_seq" is the sequence of the residual stream between layers, which lies
# scattered over tp wherever ``ring_dense.scatter_axis`` finds a layer's output
# reduced round that axis: a chip normalises, biases and adds its own tokens.
DEFAULT_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("layers", None),
    ("stage", "pp"),
    ("embed_vector", None),
    ("act_embed", None),
    ("act_seq", "tp"),
    ("act_mlp", "tp"),
    ("act_heads", "tp"),
    ("act_vocab", "tp"),
)


def rules_dict(rules: Optional[Rules] = None) -> Dict[str, Any]:
    return dict(rules if rules is not None else DEFAULT_RULES)


def pp_rules(rules: Optional[Rules] = None) -> Rules:
    """Rule table for pipeline-parallel training: the scanned layer axis
    maps onto ``pp`` so each stage's device row holds only its own layers'
    parameters (and optimizer moments), composing with fsdp/tp from the
    base rules."""
    table = rules_dict(rules)
    table["layers"] = "pp"
    return tuple(table.items())


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Rules] = None,
    mesh: Optional[Mesh] = None,
) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec.

    Mesh axes of size 1 (or absent) resolve to None so specs stay valid on
    small meshes; a mesh axis may be consumed by only one logical axis.
    """
    table = rules_dict(rules)
    used: set = set()
    out: List[Any] = []
    for name in logical_axes:
        mapped = table.get(name) if name is not None else None
        if mapped is None:
            out.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        kept = []
        for ax in axes:
            if ax in used:
                continue
            if mesh is not None and mesh.shape.get(ax, 1) == 1:
                continue
            kept.append(ax)
            used.add(ax)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def tree_shardings(
    mesh: Mesh, logical_tree: Any, rules: Optional[Rules] = None
) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def params_shardings(
    mesh: Mesh, abstract_params: Any, rules: Optional[Rules] = None
) -> Any:
    """Shardings for a flax param tree annotated with
    `nn.with_logical_partitioning` (flax Partitioned boxes)."""
    import flax.linen as nn

    spec_tree = nn.get_partition_spec(abstract_params)
    return jax.tree.map(
        lambda spec: NamedSharding(
            mesh, logical_to_spec(tuple(spec), rules, mesh)
        )
        if isinstance(spec, PartitionSpec)
        else NamedSharding(mesh, PartitionSpec()),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def batch_sharding(mesh: Mesh, ndim: int = 2, rules: Optional[Rules] = None) -> NamedSharding:
    """Sharding for a [batch, seq, ...] input array."""
    axes: List[Optional[str]] = ["batch", "seq"] + [None] * (ndim - 2)
    return NamedSharding(mesh, logical_to_spec(axes[:ndim], rules, mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
