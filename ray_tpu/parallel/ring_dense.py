"""A dense layer's matmuls with their collectives taken apart into hops that
travel while the MXU multiplies.

**The weight gradient, round ``fsdp``.** With parameters sharded over ``fsdp``
and the batch over it too, every chip holds a partial sum of a whole weight
gradient and a reduce-scatter over ``fsdp`` leaves it its shard. The TPU
compiler makes that one synchronous fused all-reduce + slice behind the
matmul: the MXU waits for the link (8.7 % of the four-chip GPT-J step; no
compiler setting hides it, PERF.md section 5). Here the gradient is multiplied
a shard at a time, the shard that belongs to the farthest chip first, and each
partial sum travels one hop round the ring (``ppermute``, which the compiler
does run beside a matmul) while the next shard multiplies: ``n - 1`` hops of
one shard each, the bytes a reduce-scatter moves, hidden behind ``n - 1`` of
the ``n`` shards' matmuls. The forward product and the input's gradient stay
plain ``dot_general``s that the compiler partitions as before. Same operations
in the same precision: a shard's partial sums are added in the kernel's
compute dtype, as the all-reduce added them.

**A layer's output, round ``tp``.** With a layer's kernels sharded over ``tp``
every chip holds a partial sum of the layer's output, and the all-reduce that
completes it is as synchronous as the gradient's was (6.1 % of that step). Its
two halves are here as hops too. The residual stream stays scattered along the
sequence over ``tp`` (:func:`scatter_axis` says when); in front of a layer's
first matmuls a chip's own tokens are multiplied while the next chip's arrive
(:func:`arriving`, then :func:`products` over what has arrived and
:func:`in_order` where the whole sequence is wanted), and behind its last ones
the partial sum of the farthest chip's tokens is multiplied first and sent on
while the next is (:func:`by_hop`, :func:`home`): ``n - 1`` hops of ``1 / n`` of
the tokens each way. What travels, and between which matmuls, is the model's
to say (``models/gpt.py`` ``Block``), inside a ``shard_map`` over ``tp`` alone;
the weight gradients' rings nest in it.
"""

from __future__ import annotations

import functools
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


def _manual_axes() -> Tuple[str, ...]:
    """The mesh axes that a ``shard_map`` around the caller has already taken."""
    return tuple(jax.sharding.get_abstract_mesh().manual_axes)


def products(
    xs: Sequence[jax.Array],
    kernel: jax.Array,
    n_in: int,
    mesh: Optional[Mesh] = None,
    kernel_axes: Optional[Sequence[str]] = None,
    rules: Optional[shd.Rules] = None,
) -> Tuple[jax.Array, ...]:
    """Each ``x`` [batch, seq, *in] of ``xs`` times ``kernel`` [*in, *out] over
    the ``n_in`` trailing dims of ``x``. Where ``mesh`` shards a dim of the
    kernel (``kernel_axes``, its logical names under ``rules``) over an axis
    that also shards the batch, the kernel's gradient (one sum over all of
    ``xs``) is reduced round that axis as the module says; anywhere else these
    are the plain products. Inside a ``shard_map`` over some of the mesh's axes
    the ring's own covers the others."""
    lead = xs[0].ndim - n_in

    def plain():
        return tuple(_product(x, kernel, n_in) for x in xs)

    if mesh is None or not rules or kernel_axes is None:
        return plain()
    taken = _manual_axes()
    of_kernel = _entries(kernel_axes, rules, mesh, kernel.ndim)
    of_lead = _entries(("batch", "seq")[:lead], rules, mesh, lead)
    over = tuple(a for e in of_lead for a in e)     # a chip's product is a partial sum over these
    ring = [(dim, e[0]) for dim, e in enumerate(of_kernel) if len(e) == 1 and e[0] in over]
    if len(ring) != 1:
        return plain()
    ring_dim, ring_axis = ring[0]
    n = mesh.shape[ring_axis]
    # a feature dim of an activation is sharded as the kernel's, less the batch's axes
    features = [tuple(a for a in e if a not in over) for e in of_kernel]

    def spec(*dims):
        return PartitionSpec(*(tuple(a for a in e if a not in taken) or None for e in dims))

    x_spec, dy_spec = spec(*of_lead, *features[:n_in]), spec(*of_lead, *features[n_in:])
    rest = tuple(a for a in over if a != ring_axis)
    leading = tuple(range(lead))

    def local_gradient(place, xs, dys):
        # the chip's place on the ring, handed in: ``axis_index`` does not lower
        # inside a shard_map that nests in another
        me = place[0]
        sliced, dim = (xs, lead + ring_dim) if ring_dim < n_in else (dys, lead + ring_dim - n_in)
        rows = sliced[0].shape[dim] // n
        total = None
        for hop in range(n):
            # the shard that is home after the hops still to come
            shards = [
                jax.lax.dynamic_slice_in_dim(each, (me + 1 + hop) % n * rows, rows, dim)
                for each in sliced]
            for x, dy in zip(*((shards, dys) if ring_dim < n_in else (xs, shards))):
                part = jax.lax.dot_general(x, dy, ((leading, leading), ((), ())))
                total = part if total is None else total + part
            if hop < n - 1:
                total = jax.lax.ppermute(
                    total, ring_axis, [(i, (i - 1) % n) for i in range(n)])
        return jax.lax.psum(total, rest) if rest else total

    gradient = jax.shard_map(
        local_gradient, mesh=None if taken else mesh,
        in_specs=(PartitionSpec(ring_axis), (x_spec,) * len(xs), (dy_spec,) * len(xs)),
        out_specs=spec(*of_kernel),
        axis_names=frozenset(mesh.axis_names) - frozenset(taken), check_vma=False,
    )

    @jax.custom_vjp
    def product(xs, kernel):
        return tuple(_product(x, kernel, n_in) for x in xs)

    def forward(xs, kernel):
        return product(xs, kernel), (xs, kernel)

    def backward(saved, dys):
        xs, kernel = saved
        out = tuple(range(lead, lead + kernel.ndim - n_in))
        dxs = tuple(
            jax.lax.dot_general(dy, kernel, ((out, tuple(range(n_in, kernel.ndim))), ((), ())))
            for dy in dys)
        return dxs, gradient(jax.numpy.arange(n, dtype="int32"), xs, tuple(dys))

    product.defvjp(forward, backward)
    return product(tuple(xs), kernel)


def biased(
    xs: Sequence[jax.Array],
    bias: jax.Array,
    mesh: Optional[Mesh] = None,
    bias_axes: Optional[Sequence[str]] = None,
    rules: Optional[shd.Rules] = None,
) -> Tuple[jax.Array, ...]:
    """``x + bias`` for each ``x`` [batch, seq, *features] of ``xs``. Where
    ``mesh`` shards the batch, the bias's gradient (a chip's sum over its own
    rows, to be summed over the batch's axes) is summed by hops round each axis,
    as the kernels' are: left to the compiler it is a small synchronous
    all-reduce, which waits its turn behind the weights' gradients on the same
    links (0.6870 against 0.6664 s a four-chip GPT-J step: PERF.md section 6,
    PR 44)."""

    def add(xs, bias):
        return tuple(x + bias for x in xs)

    if mesh is None or not rules or bias_axes is None:
        return add(xs, bias)
    lead = xs[0].ndim - bias.ndim
    taken = _manual_axes()
    of_lead = [
        tuple(a for a in e if a not in taken)
        for e in _entries(("batch", "seq")[:lead], rules, mesh, lead)]
    over = tuple(a for e in of_lead for a in e)
    if not over:
        return add(xs, bias)
    features = [
        tuple(a for a in e if a not in taken + over) or None
        for e in _entries(bias_axes, rules, mesh, bias.ndim)]

    def local_sum(*dys):
        total = sum(dy.sum(tuple(range(lead))) for dy in dys)
        for axis in over:
            n, sent = mesh.shape[axis], total
            for _ in range(n - 1):
                sent = jax.lax.ppermute(sent, axis, [(i, (i + 1) % n) for i in range(n)])
                total = total + sent
        return total

    summed = jax.shard_map(
        local_sum, mesh=None if taken else mesh,
        in_specs=(PartitionSpec(*(e or None for e in of_lead), *features),) * len(xs),
        out_specs=PartitionSpec(*features),
        axis_names=frozenset(mesh.axis_names) - frozenset(taken), check_vma=False,
    )

    hopped = jax.custom_vjp(add)
    hopped.defvjp(lambda xs, bias: (add(xs, bias), None), lambda _, dys: (tuple(dys), summed(*dys)))
    return hopped(tuple(xs), bias)


def dense(
    x: jax.Array,
    kernel: jax.Array,
    n_in: int,
    mesh: Optional[Mesh] = None,
    kernel_axes: Optional[Sequence[str]] = None,
    rules: Optional[shd.Rules] = None,
) -> jax.Array:
    """:func:`products` of one ``x``."""
    return products((x,), kernel, n_in, mesh, kernel_axes, rules)[0]


# ---------------------------------------------------------------------------
# a layer's output reduced round tp, and the stream between layers scattered
# ---------------------------------------------------------------------------


def scatter_axis(mesh: Optional[Mesh], rules: Optional[shd.Rules], seq: int) -> Optional[str]:
    """The mesh axis along which the residual stream of ``seq`` tokens lies
    scattered between layers, or None where it lies whole: without a mesh or
    rules, where the rules' ``act_seq`` names no single axis of more than one
    chip (``tp`` of 1), where that axis is not the one that shards both the
    heads and the MLP's columns (a layer's output is no partial sum over it
    then), where ``seq`` itself is sharded (``sp``), inside another
    ``shard_map`` (a pipeline stage) and where the axis does not divide ``seq``."""
    if mesh is None or not rules or _manual_axes():
        return None
    scattered, seq_axes, heads, mlp = (
        _entries((name,), rules, mesh, 1)[0] for name in ("act_seq", "seq", "heads", "mlp"))
    if len(scattered) != 1 or seq_axes or not heads == mlp == scattered:
        return None
    return None if seq % mesh.shape[scattered[0]] else scattered[0]


def spec_over(axis: str, logical_axes, rules, mesh: Mesh) -> PartitionSpec:
    """What of ``logical_axes``' sharding a ``shard_map`` over ``axis`` alone names."""
    return PartitionSpec(*(
        axis if axis in e else None for e in _entries(logical_axes, rules, mesh, len(logical_axes))))


def laid_out(x: jax.Array, logical_axes, rules, mesh: Mesh) -> jax.Array:
    """``x`` held to ``logical_axes``' sharding over the axes the ``shard_map``s
    around the caller leave to the compiler."""
    taken = _manual_axes()
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*(
        tuple(a for a in e if a not in taken) or None
        for e in _entries(logical_axes, rules, mesh, len(logical_axes)))))


def arriving(x: jax.Array, axis: str) -> List[jax.Array]:
    """Under a ``shard_map`` over ``axis``: this chip's ``x`` and, one hop after
    another, those of the chips before it: entry ``h`` is chip ``me - h``'s.
    What reads entry ``h`` alone can run while entry ``h + 1`` is on its way."""
    n = jax.lax.axis_size(axis)
    held = [x]
    for _ in range(n - 1):
        held.append(jax.lax.ppermute(held[-1], axis, [(i, (i + 1) % n) for i in range(n)]))
    return held


def home(parts: Sequence[jax.Array], axis: str) -> jax.Array:
    """:func:`arriving`'s mirror: ``parts[h]`` is this chip's partial sum of what
    belongs to chip ``me - h``; the sum over the chips of what belongs to this
    one. The farthest chip's goes first and collects the others' on its way, so
    every hop but the last has a later part's making to travel beside."""
    n = jax.lax.axis_size(axis)
    total = parts[-1]
    for part in parts[-2::-1]:
        total = jax.lax.ppermute(total, axis, [(i, (i - 1) % n) for i in range(n)]) + part
    return total


def _place(chunks, axis, dim):
    # a choice among the chunks for each place, not an update of a buffer at an
    # offset only the chip knows: the choice fuses into whatever reads the
    # result, the update was a copy of its own (0.1 ms a chunk of 8 MB)
    me, n = jax.lax.axis_index(axis), len(chunks)
    return jax.numpy.concatenate(
        [jax.lax.select_n((me - place) % n, *chunks) for place in range(n)], dim)


def _pick(whole, axis, dim):
    me, n = jax.lax.axis_index(axis), jax.lax.axis_size(axis)
    places = jax.numpy.split(whole, n, dim)
    return tuple(jax.lax.select_n((me - hop) % n, *places) for hop in range(n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def in_order(chunks: Sequence[jax.Array], axis: str, dim: int = 1) -> jax.Array:
    """Chunks indexed as :func:`arriving` hands them out (entry ``h`` chip
    ``me - h``'s share of ``dim``), laid out along ``dim`` in the chips' order."""
    return _place(chunks, axis, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def by_hop(whole: jax.Array, axis: str, dim: int = 1) -> Tuple[jax.Array, ...]:
    """:func:`in_order`'s inverse: ``whole`` cut along ``dim`` into the chips'
    shares, entry ``h`` chip ``me - h``'s, as :func:`home` takes its parts."""
    return _pick(whole, axis, dim)


# each is the other's transpose: a gradient is cut or laid out, never padded and summed
in_order.defvjp(
    lambda chunks, axis, dim: (_place(chunks, axis, dim), None),
    lambda axis, dim, _, d_whole: (_pick(d_whole, axis, dim),))
by_hop.defvjp(
    lambda whole, axis, dim: (_pick(whole, axis, dim), None),
    lambda axis, dim, _, d_chunks: (_place(d_chunks, axis, dim),))
