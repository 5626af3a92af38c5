"""What the chip's compiler says about the main path, asked without a chip.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
described (not attached) ``v5e:2x2``: it refuses what the chip would refuse
— a kernel whose tiles do not fit, a Mosaic kernel under a mesh without a
``shard_map``, a program larger than the device's memory. Nothing runs, so
these say nothing about results or speed; that is ``chip_smoke.py``'s job.

Code that asks ``jax.devices()`` sees the CPU here and would take its XLA
path, so the tests steer it with ``attn_use_pallas=True``.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import gpt
from ray_tpu.models.training import (
    abstract_state,
    default_optimizer,
    make_train_step,
    state_shardings,
)
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.mesh import MeshSpec

HBM_BYTES = 16909336064  # bytes_limit of one v5e chip, as its memory_stats() reports


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _gptj(depth):
    return gpt.gpt_j_6b(
        num_layers=depth, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        attn_use_pallas=True,
    )


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.mark.parametrize("head_dim", [256, 128])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_kernels_compile(v5e, head_dim, backward):
    qkv = jax.ShapeDtypeStruct(
        (4, 16, 2048, head_dim), jnp.bfloat16, sharding=SingleDeviceSharding(v5e[0])
    )

    def fwd(q, k, v):
        return dot_product_attention(q, k, v, causal=True, use_pallas=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = jax.jit(fn).lower(qkv, qkv, qkv).compile().as_text()
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


@pytest.mark.parametrize(
    "spec,n_devices",
    [(MeshSpec(), 1), (MeshSpec(dp=-1, fsdp=2, tp=2), 4)],
    ids=["one-chip", "fsdp2xtp2"],
)
def test_gptj_width_train_step_compiles(v5e, spec, n_devices):
    """The whole step at GPT-J's widths, depth 2. On the mesh the flash
    kernel is only legal under shard_map ("Mosaic kernels cannot be
    automatically partitioned")."""
    cfg, batch = _gptj(2), (2, 2048)
    mesh = spec.build(v5e[:n_devices])
    opt = default_optimizer(1e-4)
    _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct(batch, jnp.int32))
    shardings = nn.meta.unbox(state_shardings(mesh, abstract))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        nn.meta.unbox(abstract), shardings,
    )
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=shd.batch_sharding(mesh))
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    compiled = step.lower(state, tokens).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("lanes,tc", [(4, 1), (1, 128)], ids=["decode", "prefill"])
def test_gptj_full_depth_extend_compiles(v5e, lanes, tc):
    """The server's step at full depth 28 in bf16, over a 1024-token cache:
    the weights alone are 11.3 GiB of the chip's 15.75."""
    cfg = _gptj(28)
    one = SingleDeviceSharding(v5e[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype),
        jax.eval_shape(
            lambda: gpt.unboxed_params(
                gpt.GPT(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
            )
        ),
    )
    cache = shaped((cfg.num_layers, lanes, 1024, cfg.num_heads, cfg.head_dim), cfg.dtype)
    compiled = gpt.make_extend_fn(cfg).lower(
        params, shaped((lanes, tc), jnp.int32), shaped((lanes,), jnp.int32), cache, cache
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
