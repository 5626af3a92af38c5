"""Attention under a device mesh: the fused kernel per shard, plus ring
attention and Ulysses over the sp axis (sequence/context parallelism).

First-class long-context components (SURVEY.md §5: the reference has no
sequence parallelism anywhere — long-model support was delegated to
DeepSpeed/Alpa; here they are native ops):

- **Ring attention**: K/V shards rotate around the `sp` ICI ring via
  ``lax.ppermute``; each hop computes a blockwise attention against the
  local Q and merges with the online-softmax rule. Q never moves; peak
  activation memory is one K/V shard per device.
- **Ulysses**: ``all_to_all`` swaps the head and sequence axes so each
  device holds *all* positions for a slice of heads, runs the fused Pallas
  flash kernel on the full sequence, and swaps back. Best when
  local_heads % sp == 0; rides the custom-vjp flash kernels.

Both are exact (tested against dense attention on the CPU mesh) and
differentiable. ``mesh_attention`` is the wrapper the model calls; with
sp == 1 each device runs the fused kernel on its batch/head shard (a Mosaic
kernel cannot be partitioned by GSPMD, so the ``shard_map`` is what makes it
legal on more than one chip).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import (
    attention_with_lse,
    dot_product_attention,
    merge_attention,
)


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    sp: int,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Shard-local ring attention (call under shard_map).

    q/k/v: [b, h_loc, t_loc, d] — the local sequence chunk. Chunks are laid
    out contiguously: device i holds positions [i*t_loc, (i+1)*t_loc).
    Step 0 is the local (causal) block; step j receives chunk (my - j) mod
    sp, which under causal masking contributes fully iff my >= j.
    """
    scale_val = float(scale) if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    my = jax.lax.axis_index(axis_name)
    o0, lse0 = attention_with_lse(q, k, v, causal=causal, scale=scale_val)
    o, lse = o0.astype(jnp.float32), lse0
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(carry, j):
        o, lse, k_blk, v_blk = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        o_j, lse_j = attention_with_lse(q, k_blk, v_blk, causal=False, scale=scale_val)
        # after j hops we hold chunk (my - j) mod sp: a *previous* chunk
        # (fully visible) iff my >= j; otherwise a future chunk (masked out)
        valid = (my >= j) if causal else jnp.bool_(True)
        o, lse = merge_attention(o, lse, o_j, lse_j, valid)
        return (o, lse, k_blk, v_blk), None

    if sp > 1:
        (o, lse, _, _), _ = jax.lax.scan(
            step, (o, lse, k, v), jnp.arange(1, sp)
        )
    return o.astype(q.dtype)


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    sp: int,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Shard-local Ulysses attention (call under shard_map).

    all_to_all reshapes [b, h_loc, t_loc, d] -> [b, h_loc/sp, t_full, d],
    runs full-sequence fused attention (Pallas fwd+bwd on TPU), and swaps
    back. Requires h_loc % sp == 0.
    """
    h_loc = q.shape[1]
    if h_loc % sp != 0:
        raise ValueError(f"ulysses needs local heads ({h_loc}) divisible by sp ({sp})")

    def swap_in(x):  # heads -> devices, gather sequence
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def swap_out(x):  # sequence -> devices, gather heads
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    out = dot_product_attention(
        swap_in(q), swap_in(k), swap_in(v), causal=causal, scale=scale)
    return swap_out(out)


def mesh_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh],
    *,
    impl: str = "ring",
    sp_axis: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("dp", "fsdp"),
    head_axis: str = "tp",
) -> jax.Array:
    """Attention over [b, h, T, d] arrays on the step's mesh: batch sharded
    on dp/fsdp, heads on tp, sequence on ``sp_axis``.

    Runs under shard_map over those axes. With sp == 1 every device runs the
    fused kernel on its own batch/head shard; otherwise the chosen
    implementation's collectives (ppermute ring or all_to_all) ride the ICI
    mesh explicitly. No mesh, or a one-device mesh, needs no wrapper.
    """
    fused = functools.partial(dot_product_attention, causal=causal, scale=scale)
    if mesh is None or mesh.size == 1:
        return fused(q, k, v)
    sp = mesh.shape.get(sp_axis, 1)
    if sp == 1:
        local = fused
    elif impl == "ring":
        local = functools.partial(
            ring_attention_local, axis_name=sp_axis, sp=sp, causal=causal, scale=scale
        )
    elif impl == "ulysses":
        local = functools.partial(
            ulysses_attention_local, axis_name=sp_axis, sp=sp, causal=causal, scale=scale
        )
    else:
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    # inside a shard_map that has taken some of the axes already (the layer's,
    # over tp: the heads are a chip's own there) this one covers the others
    taken = jax.sharding.get_abstract_mesh().manual_axes
    spec = P(*(
        tuple(a for a in ((axes,) if isinstance(axes, str) else axes) if a not in taken) or None
        for axes in (batch_axes, head_axis, sp_axis)), None)
    return shard_map(
        lambda a, b, c: local(a, b, c),
        mesh=None if taken else mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset(mesh.axis_names) - frozenset(taken), check_vma=False,
    )(q, k, v)
