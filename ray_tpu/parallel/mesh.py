"""Device-mesh construction for single- and multi-slice TPU topologies.

The reference has no mesh concept at all — its only parallelism axis is the
torch.distributed world created per WorkerGroup (reference:
python/ray/train/torch/config.py:69 `_setup_torch_process_group`). The
TPU-native design replaces that with one explicit `jax.sharding.Mesh` whose
named axes carry every parallelism strategy the framework offers
(SURVEY.md §2.6): data ("dp"), fully-sharded data ("fsdp"), tensor ("tp"),
sequence/context ("sp"), expert ("ep") and pipeline ("pp").

Axis order matters on hardware: the innermost axes (tp, sp) get the
fastest-varying device coordinates so their collectives ride ICI neighbor
links; dp is outermost so its (rarer, larger-grained) gradient reductions can
cross DCN on multi-slice meshes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis order: outermost (DCN-tolerant) → innermost (ICI-hungry).
AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. Size -1 on at most one axis means "absorb all
    remaining devices" (like a numpy reshape).

    Example::

        MeshSpec(dp=-1, fsdp=2, tp=4).build()   # on 64 chips → (8, 2, 4)
    """

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    # number of pod slices the dp axis spans (multi-slice / DCN meshes);
    # 1 means a single ICI domain.
    num_slices: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Fill in a single -1 axis so the product equals ``n_devices``."""
        sizes = self.axis_sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} wants {fixed} devices but {n_devices} are present"
            )
        return sizes

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        return make_mesh(self, devices)


def make_mesh(
    spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a `jax.sharding.Mesh` with ICI/DCN-aware device placement.

    Single slice: `mesh_utils.create_device_mesh` lays devices out so the
    innermost mesh axes map to physically adjacent chips (torus neighbors).
    Multi-slice: the slice-spanning axes are built with
    `create_hybrid_device_mesh`, which keeps per-slice contiguity and puts
    the cross-slice hops on the outermost (DCN) axes.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if spec.num_slices > 1 and sizes["dp"] % spec.num_slices != 0:
        raise ValueError(
            f"dp={sizes['dp']} must be divisible by num_slices={spec.num_slices}"
        )
    if devices[0].platform != "tpu":
        # CPU fixtures have no topology to honour: a plain reshape (which
        # also emulates the slice split, outermost dp = DCN)
        dev_array = np.asarray(devices).reshape(shape)
    elif spec.num_slices > 1:
        # real topology: a shape it cannot host is an error, never a silent
        # reshape that would put tp/fsdp collectives on DCN
        dcn_shape = tuple(
            spec.num_slices if a == "dp" else 1 for a in AXIS_ORDER
        )
        per_slice = tuple(s // d for s, d in zip(shape, dcn_shape))
        dev_array = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn_shape, devices=devices, allow_split_physical_axes=True
        )
    else:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True
        )
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return MeshSpec().build([device])


def data_axes() -> Tuple[str, ...]:
    """Mesh axes across which the global batch is split."""
    return ("dp", "fsdp")


def mesh_summary(mesh: Mesh) -> Dict[str, int]:
    return {a: int(s) for a, s in mesh.shape.items() if s > 1} or {"dp": 1}
