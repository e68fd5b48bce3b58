"""Device-mesh topology: the process-group layer.

Capability analogue of the reference's ``deepspeed/utils/groups.py`` (dp/tp/
ep/sp group creation + divisibility validation) and
``runtime/pipe/topology.py`` (``PipeModelDataParallelTopology`` axis-rank
mapping).  On TPU there are no process-group handles: every parallel group is
a named axis of one ``jax.sharding.Mesh``; collectives address groups by axis
name inside ``jit``/``shard_map``.

Axis order (outer → inner): ``pp, dp, fsdp, ep, sp, tp`` — DCN-crossing axes
outermost, bandwidth-hungry axes (tp) innermost so they ride ICI neighbours.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..runtime.config import MeshConfig
from ..runtime.config_utils import ConfigError, is_auto

MESH_AXES: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Logical tensor-axis names used by models; sharding rules map these to mesh axes.
LOGICAL_AXES = (
    "batch", "seq", "heads", "kv_heads", "embed", "mlp", "vocab",
    "layers", "expert", "kv", "qkv",
)


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    name: str
    size: int


class MeshTopology:
    """Resolved mesh axis sizes + the live ``jax.sharding.Mesh``."""

    def __init__(self, axis_sizes: Dict[str, int], devices: Optional[Sequence] = None,
                 dcn_axes: Sequence[str] = ("pp", "dp")):
        import jax
        from jax.sharding import Mesh

        for ax in axis_sizes:
            if ax not in MESH_AXES:
                raise ConfigError(f"unknown mesh axis {ax!r}; valid: {MESH_AXES}")
        self.axis_sizes = {ax: int(axis_sizes.get(ax, 1)) for ax in MESH_AXES}
        self.dcn_axes = tuple(dcn_axes)

        devices = list(devices) if devices is not None else list(jax.devices())
        total = math.prod(self.axis_sizes.values())
        if total != len(devices):
            raise ConfigError(
                f"mesh axes {self.axis_sizes} require {total} devices, "
                f"have {len(devices)}")

        shape = tuple(self.axis_sizes[ax] for ax in MESH_AXES)
        dev_array = self._arrange(devices, shape)
        self.mesh = Mesh(dev_array, MESH_AXES)

    @staticmethod
    def _arrange(devices: Sequence, shape: Tuple[int, ...]) -> np.ndarray:
        """Arrange devices so inner axes are ICI-neighbours.

        On real TPU slices defer to ``mesh_utils.create_device_mesh`` which
        understands the physical torus; on CPU/virtual devices a plain reshape.
        """
        if devices and devices[0].platform != "cpu":
            from jax.experimental import mesh_utils

            # an assignment the torus cannot give raises: it is not reshaped
            # into one with slower links in silence
            return mesh_utils.create_device_mesh(shape, devices=list(devices))
        return np.asarray(devices, dtype=object).reshape(shape)

    # -- factory --------------------------------------------------------

    @classmethod
    def from_config(cls, cfg: MeshConfig, devices: Optional[Sequence] = None,
                    device_count: Optional[int] = None) -> "MeshTopology":
        import jax

        if devices is None:
            devices = list(jax.devices())
        n = device_count if device_count is not None else len(devices)

        sizes: Dict[str, int] = {
            "pp": cfg.pipeline_parallel_size,
            "ep": cfg.expert_parallel_size,
            "sp": cfg.sequence_parallel_size,
            "tp": cfg.tensor_parallel_size,
        }
        fsdp = None if is_auto(cfg.fsdp_size) else int(cfg.fsdp_size)
        dp = None if is_auto(cfg.data_parallel_size) else int(cfg.data_parallel_size)

        fixed = math.prod(sizes.values())
        if n % fixed != 0:
            raise ConfigError(
                f"device count {n} not divisible by pp*ep*sp*tp={fixed}")
        remaining = n // fixed
        if dp is None and fsdp is None:
            dp, fsdp = remaining, 1
        elif dp is None:
            if remaining % fsdp != 0:
                raise ConfigError(f"{remaining} devices not divisible by fsdp={fsdp}")
            dp = remaining // fsdp
        elif fsdp is None:
            if remaining % dp != 0:
                raise ConfigError(f"{remaining} devices not divisible by dp={dp}")
            fsdp = remaining // dp
        if dp * fsdp != remaining:
            raise ConfigError(
                f"dp({dp})*fsdp({fsdp}) != remaining devices ({remaining})")
        sizes["dp"], sizes["fsdp"] = dp, fsdp
        return cls(sizes, devices=devices, dcn_axes=cfg.dcn_axes)

    # -- accessors ------------------------------------------------------

    def size(self, axis: str) -> int:
        return self.axis_sizes[axis]

    @property
    def world_size(self) -> int:
        return math.prod(self.axis_sizes.values())

    @property
    def dp_world_size(self) -> int:
        """Replica count for batch-size math: dp × fsdp (both consume batch)."""
        return self.axis_sizes["dp"] * self.axis_sizes["fsdp"]

    @property
    def model_parallel_size(self) -> int:
        return self.axis_sizes["tp"] * self.axis_sizes["pp"]

    def active_axes(self) -> List[str]:
        return [ax for ax in MESH_AXES if self.axis_sizes[ax] > 1]

    def coord_of(self, device_index: int) -> Dict[str, int]:
        """Axis coordinates of the device with flat id ``device_index``.

        Looks the device up in the actual mesh array — on real TPU slices
        ``mesh_utils.create_device_mesh`` permutes devices to match the
        physical torus, so coordinates cannot be recomputed from the id.
        """
        ids = np.vectorize(lambda d: d.id, otypes=[int])(self.mesh.devices)
        pos = np.argwhere(ids == device_index)
        if pos.size == 0:
            raise ValueError(f"device id {device_index} not in mesh")
        return {ax: int(c) for ax, c in zip(MESH_AXES, pos[0])}

    def __repr__(self) -> str:
        active = {ax: s for ax, s in self.axis_sizes.items() if s > 1}
        return f"MeshTopology({active or {'dp': 1}}, world={self.world_size})"


# ---------------------------------------------------------------------------
# global topology registry (reference: groups.py module-level group cache)
# ---------------------------------------------------------------------------

_TOPOLOGY: Optional[MeshTopology] = None


def set_topology(topo: MeshTopology) -> None:
    global _TOPOLOGY
    _TOPOLOGY = topo


def get_topology() -> MeshTopology:
    if _TOPOLOGY is None:
        raise RuntimeError(
            "mesh topology not initialized; call deepspeed_tpu.initialize() "
            "or parallel.topology.set_topology() first")
    return _TOPOLOGY


def topology_initialized() -> bool:
    return _TOPOLOGY is not None


def reset_topology() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None


# reference-parity getters (groups.py get_data_parallel_world_size etc.)

def get_data_parallel_world_size() -> int:
    return get_topology().dp_world_size


def get_model_parallel_world_size() -> int:
    return get_topology().size("tp")


def get_expert_parallel_world_size() -> int:
    return get_topology().size("ep")


def get_sequence_parallel_world_size() -> int:
    return get_topology().size("sp")


def get_pipeline_parallel_world_size() -> int:
    return get_topology().size("pp")
