"""A dense layer's matmul whose weight gradient leaves for its fsdp shard
while it is still being multiplied.

With parameters sharded over ``fsdp`` and the batch over it too, every chip
holds a partial sum of a whole weight gradient and a reduce-scatter over
``fsdp`` leaves it its shard. The TPU compiler makes that one synchronous
fused all-reduce + slice behind the matmul: the MXU waits for the link (8.7 %
of the four-chip GPT-J step; no compiler setting hides it, PERF.md section 5).
Here the gradient is multiplied a shard at a time, the shard that belongs to
the farthest chip first, and each partial sum travels one hop round the ring
(``ppermute``, which the compiler does run beside a matmul) while the next
shard multiplies: ``n - 1`` hops of one shard each, the bytes a reduce-scatter
moves, hidden behind ``n - 1`` of the ``n`` shards' matmuls. The forward
product and the input's gradient stay plain ``dot_general``s that the compiler
partitions as before. Same operations in the same precision: a shard's
partial sums are added in the kernel's compute dtype, as the all-reduce added
them.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec

from ray_tpu.parallel import sharding as shd


def _product(x: jax.Array, kernel: jax.Array, n_in: int) -> jax.Array:
    return jax.lax.dot_general(
        x, kernel, ((tuple(range(x.ndim - n_in, x.ndim)), tuple(range(n_in))), ((), ()))
    )


def _entries(logical_axes, rules, mesh: Mesh, ndim: int) -> List[Tuple[str, ...]]:
    """The mesh axes (of more than one chip) behind each of ``ndim`` dims."""
    spec = tuple(shd.logical_to_spec(logical_axes, rules, mesh))
    return [
        () if e is None else (e,) if isinstance(e, str) else tuple(e)
        for e in spec + (None,) * (ndim - len(spec))
    ]


def dense(
    x: jax.Array,
    kernel: jax.Array,
    n_in: int,
    mesh: Optional[Mesh] = None,
    kernel_axes: Optional[Sequence[str]] = None,
    rules: Optional[shd.Rules] = None,
) -> jax.Array:
    """``x`` [batch, seq, *in] times ``kernel`` [*in, *out] over the ``n_in``
    trailing dims of ``x``. Where ``mesh`` shards a dim of the kernel
    (``kernel_axes``, its logical names under ``rules``) over an axis that also
    shards the batch, the kernel's gradient is reduced round that axis as the
    module says; anywhere else this is the plain product."""
    lead = x.ndim - n_in
    if mesh is None or not rules or kernel_axes is None:
        return _product(x, kernel, n_in)
    of_kernel = _entries(kernel_axes, rules, mesh, kernel.ndim)
    of_lead = _entries(("batch", "seq")[:lead], rules, mesh, lead)
    over = tuple(a for e in of_lead for a in e)     # a chip's product is a partial sum over these
    ring = [(dim, e[0]) for dim, e in enumerate(of_kernel) if len(e) == 1 and e[0] in over]
    if len(ring) != 1:
        return _product(x, kernel, n_in)
    ring_dim, ring_axis = ring[0]
    n = mesh.shape[ring_axis]
    # a feature dim of an activation is sharded as the kernel's, less the batch's axes
    features = [tuple(a for a in e if a not in over) or None for e in of_kernel]
    x_spec = PartitionSpec(*(e or None for e in of_lead), *features[:n_in])
    dy_spec = PartitionSpec(*(e or None for e in of_lead), *features[n_in:])
    rest = tuple(a for a in over if a != ring_axis)
    leading = tuple(range(lead))

    def local_gradient(x, dy):
        me = jax.lax.axis_index(ring_axis)
        sliced, dim = (x, lead + ring_dim) if ring_dim < n_in else (dy, lead + ring_dim - n_in)
        rows = sliced.shape[dim] // n
        total = None
        for hop in range(n):
            # the shard that is home after the hops still to come
            shard = jax.lax.dynamic_slice_in_dim(sliced, (me + 1 + hop) % n * rows, rows, dim)
            part = jax.lax.dot_general(
                *((shard, dy) if ring_dim < n_in else (x, shard)), ((leading, leading), ((), ()))
            )
            total = part if total is None else total + part
            if hop < n - 1:
                total = jax.lax.ppermute(
                    total, ring_axis, [(i, (i - 1) % n) for i in range(n)])
        return jax.lax.psum(total, rest) if rest else total

    gradient = jax.shard_map(
        local_gradient, mesh=mesh, in_specs=(x_spec, dy_spec),
        out_specs=PartitionSpec(*(e or None for e in of_kernel)), check_vma=False,
    )

    @jax.custom_vjp
    def product(x, kernel):
        return _product(x, kernel, n_in)

    def forward(x, kernel):
        return _product(x, kernel, n_in), (x, kernel)

    def backward(saved, dy):
        x, kernel = saved
        out = tuple(range(dy.ndim - (kernel.ndim - n_in), dy.ndim))
        dx = jax.lax.dot_general(
            dy, kernel, ((out, tuple(range(n_in, kernel.ndim))), ((), ())))
        return dx, gradient(x, dy)

    product.defvjp(forward, backward)
    return product(x, kernel)
