"""``parallel/ring_dense.dense``: the plain product everywhere, and on a mesh
whose fsdp axis shards the kernel a weight gradient reduced round that axis a
shard at a time (virtual 8-device CPU mesh; the chip's schedule is
``tests/test_chip_compile.py``'s and the benchmark's)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import ring_dense
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.sharding import DEFAULT_RULES

# a layer's three kinds of kernel: [*in, *out], logical names, dims contracted
KERNELS = {
    "wi": ((32, 64), ("embed", "mlp"), 1),
    "wo": ((64, 32), ("mlp", "embed"), 1),
    "q": ((32, 4, 8), ("embed", "heads", "kv"), 1),
    "o": ((4, 8, 32), ("heads", "kv", "embed"), 2),
}
MESHES = {
    "dp2-fsdp2-tp2": MeshSpec(dp=2, fsdp=2, tp=2),
    "fsdp4-tp2": MeshSpec(dp=-1, fsdp=4, tp=2),
    "fsdp2-sp2-tp2": MeshSpec(dp=-1, fsdp=2, sp=2, tp=2),
    "fsdp8": MeshSpec(dp=-1, fsdp=8),
    "dp8": MeshSpec(dp=-1),
    "dp4-tp2": MeshSpec(dp=-1, tp=2),
}


def _case(kernel):
    shape, axes, n_in = KERNELS[kernel]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (8, 16) + shape[:n_in], jnp.float32)
    w = jax.random.normal(keys[1], shape, jnp.float32)
    dy = jax.random.normal(keys[2], (8, 16) + shape[n_in:], jnp.float32)
    return x, w, dy, axes, n_in


@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("mesh_id", list(MESHES))
def test_product_and_both_gradients_are_the_plain_ones(mesh_id, kernel):
    x, w, dy, axes, n_in = _case(kernel)
    mesh = MESHES[mesh_id].build()

    def of(dense):
        return jax.jit(lambda x, w: jax.vjp(dense, x, w)[1](dy) + (dense(x, w),))(x, w)

    plain = of(lambda x, w: ring_dense.dense(x, w, n_in))
    with mesh:
        ringed = of(lambda x, w: ring_dense.dense(x, w, n_in, mesh, axes, DEFAULT_RULES))
    for got, want in zip(ringed, plain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize(
    "mesh_id,hops",
    [("dp2-fsdp2-tp2", 1), ("fsdp4-tp2", 3), ("fsdp8", 7), ("dp8", 0), ("dp4-tp2", 0)],
)
def test_gradient_takes_one_hop_less_than_the_axis_has_chips(mesh_id, hops):
    """``n - 1`` sends of one shard each, and the gradient comes out sharded as
    the kernel is; without an fsdp axis nothing is wrapped at all."""
    x, w, dy, axes, n_in = _case("wi")
    mesh = MESHES[mesh_id].build()

    def gradient(x, w):
        dense = lambda x, w: ring_dense.dense(x, w, n_in, mesh, axes, DEFAULT_RULES)
        return jax.vjp(dense, x, w)[1](dy)[1]

    text = str(jax.make_jaxpr(gradient)(x, w))
    assert text.count("ppermute") == hops
    assert ("shard_map" in text) == bool(hops)
    if hops:
        with mesh:
            dw = jax.jit(gradient)(x, w)
        assert dw.sharding.is_equivalent_to(NamedSharding(mesh, P("fsdp", "tp")), 2)


def test_no_mesh_no_rules_or_one_device_is_the_plain_product():
    x, w, _, axes, n_in = _case("o")
    one = MeshSpec().build(jax.devices()[:1])
    mesh = MESHES["fsdp4-tp2"].build()
    for args in ((), (one, axes, DEFAULT_RULES), (mesh, axes, ()), (mesh, None, DEFAULT_RULES)):
        text = str(jax.make_jaxpr(
            lambda x, w: jax.grad(lambda x, w: ring_dense.dense(x, w, n_in, *args).sum(), 1)(x, w)
        )(x, w))
        assert "shard_map" not in text and "custom_vjp" not in text
