"""Device-mesh topology: the TPU-native process-group layer.

Replaces the reference's rank-arithmetic process groups
(``deepspeed/utils/groups.py:317-560`` group getters and
``deepspeed/runtime/pipe/topology.py:12`` ``ProcessTopology`` /
``:251`` ``PipelineParallelGrid``) with a single named-axis
``jax.sharding.Mesh``. Where the reference materialises one
``torch.distributed.ProcessGroup`` per parallelism flavour, here a "group" is
just a tuple of mesh axis names — XLA lowers collectives over those axes onto
ICI (intra-slice) or DCN (cross-slice) from the mesh's device assignment.

Canonical axis order (outer → inner):

    ('pipe', 'dout', 'data', 'seq', 'expert', 'model')

* ``pipe``   — pipeline stages (reference PipelineParallelGrid pipe axis)
* ``dout``   — data-parallel *outer* replicas (size 1 unless ZeRO++ hpZ /
  MiCS splits the data axis: ``dout × data`` spans the dp replicas, with
  ``data`` the intra-node/ICI sub-group — the reference's secondary
  partition group ``utils/groups.py:505 _create_zero_param_parallel_group``
  and MiCS sharding sub-group ``zero/mics.py``)
* ``data``   — data parallel replicas (the hpZ/MiCS sub-group when dout>1)
* ``seq``    — Ulysses sequence parallel (reference sequence_parallel group)
* ``expert`` — expert parallel (reference expert_parallel group)
* ``model``  — tensor parallel (reference model_parallel group)

Derived groups (tuples of axes):

* batch (data-loader) axes: ``('dout', 'data', 'expert')`` — each dp replica
  sees a distinct micro-batch slice; seq ranks share the batch but split the
  sequence dim.
* ZeRO / dense-grad axes: ``('dout', 'data', 'seq', 'expert')`` — matches
  the reference's use of the *seq_data_parallel* group as the ZeRO partition
  group (``runtime/engine.py:1125,1509``).
* ZeRO secondary (hpZ/MiCS) axes: ``('data', 'seq', 'expert')`` — the inner
  sub-group when ``dout`` > 1.
* expert-data axes: ``('dout', 'data', 'seq')`` — grad reduction group for
  expert params (reference ``_reduce_expert_gradients``, engine.py:2406).

``model`` is innermost so TP collectives ride the fastest ICI links; ``pipe``
is outermost so stage p2p transfers cross the slowest links, mirroring the
reference's pipe-outer mapping (topology.py axes order ``pipe,data,model``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

MESH_AXES: Tuple[str, ...] = ("pipe", "dout", "data", "seq", "expert", "model")

# Axis-group aliases accepted anywhere a "group" is taken (comm facade, ZeRO).
GROUP_ALIASES: Dict[str, Tuple[str, ...]] = {
    "world": MESH_AXES,
    "data_parallel": ("dout", "data", "expert"),
    "dp": ("dout", "data", "expert"),
    "seq_data_parallel": ("dout", "data", "seq", "expert"),
    "sdp": ("dout", "data", "seq", "expert"),
    "zero": ("dout", "data", "seq", "expert"),
    # hpZ/MiCS secondary partition: the intra-node sub-group of the zero
    # group (reference _create_zero_param_parallel_group, zero/mics.py)
    "zero_secondary": ("data", "seq", "expert"),
    "hpz": ("data", "seq", "expert"),
    "zero_outer": ("dout",),
    "sequence_parallel": ("seq",),
    "sp": ("seq",),
    "model_parallel": ("model",),
    "tensor_parallel": ("model",),
    "tp": ("model",),
    "mp": ("model",),
    "expert_parallel": ("expert",),
    "ep": ("expert",),
    "expert_data_parallel": ("dout", "data", "seq"),
    "edp": ("dout", "data", "seq"),
    "pipe_parallel": ("pipe",),
    "pp": ("pipe",),
}


@dataclasses.dataclass(frozen=True)
class ParallelDims:
    """Degrees of each parallelism flavour. ``data=-1`` infers from devices.

    ``dout`` (data-outer) defaults to 1; hpZ/MiCS split the dp replicas as
    ``dout × data`` (see :func:`split_data_axis`).
    """

    pipe: int = 1
    dout: int = 1
    data: int = -1
    seq: int = 1
    expert: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> "ParallelDims":
        fixed = self.pipe * self.dout * self.seq * self.expert * self.model
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by "
                    f"pipe*dout*seq*expert*model={fixed}")
            data = n_devices // fixed
        if self.pipe * self.dout * data * self.seq * self.expert * \
                self.model != n_devices:
            raise ValueError(
                f"mesh {self.as_dict()} (data={data}) does not cover "
                f"{n_devices} devices")
        return dataclasses.replace(self, data=data)

    def split_data_axis(self, inner_size: int) -> "ParallelDims":
        """Split the (resolved) data axis into ``dout × inner_size`` for the
        hpZ/MiCS secondary partition."""
        total = self.dout * self.data
        if inner_size <= 0 or total % inner_size != 0:
            raise ValueError(
                f"secondary partition size {inner_size} does not divide the "
                f"data-parallel degree {total}")
        return dataclasses.replace(self, dout=total // inner_size,
                                   data=inner_size)

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    def shape(self) -> Tuple[int, ...]:
        return (self.pipe, self.dout, self.data, self.seq, self.expert,
                self.model)


class MeshTopology:
    """A resolved device mesh plus the reference's group/rank algebra.

    Exposes the ``ProcessTopology`` query surface (axis sizes, coordinates,
    rank filtering) so code written against the reference's topology concepts
    has a direct analogue, while the real artefact is ``self.mesh`` — the
    ``jax.sharding.Mesh`` every jit/shard_map in the framework runs under.
    """

    def __init__(self, dims: ParallelDims, devices: Optional[Sequence[Any]] = None):
        import jax

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        self.dims = dims.resolve(len(devices))
        shape = self.dims.shape()
        # Auto axis types = GSPMD constraint solving: ZeRO relies on XLA
        # propagating/resolving shardings between the annotated state specs
        # (the Explicit default would demand manual resolution at every dot).
        axis_types = (jax.sharding.AxisType.Auto,) * len(MESH_AXES)
        # make_mesh picks an ICI-friendly device assignment on TPU.
        self.mesh = jax.make_mesh(shape, MESH_AXES, devices=devices,
                                  axis_types=axis_types)

    # ------------------------------------------------------------------ #
    # Axis algebra
    # ------------------------------------------------------------------ #
    @property
    def world_size(self) -> int:
        return math.prod(self.dims.shape())

    def get_dim(self, axis: str) -> int:
        return getattr(self.dims, axis)

    def axis_size(self, axes) -> int:
        return math.prod(self.get_dim(a) for a in resolve_group(axes))

    @property
    def data_parallel_size(self) -> int:
        return self.axis_size("dp")

    @property
    def zero_partition_size(self) -> int:
        return self.axis_size("zero")

    @property
    def model_parallel_size(self) -> int:
        return self.dims.model

    @property
    def expert_parallel_size(self) -> int:
        return self.dims.expert

    @property
    def sequence_parallel_size(self) -> int:
        return self.dims.seq

    @property
    def pipe_parallel_size(self) -> int:
        return self.dims.pipe

    # ------------------------------------------------------------------ #
    # ProcessTopology-style rank queries (reference pipe/topology.py:12)
    # ------------------------------------------------------------------ #
    def get_axes(self) -> Tuple[str, ...]:
        return MESH_AXES

    def get_coord(self, rank: int) -> Dict[str, int]:
        """Rank → named coordinates in the mesh grid."""
        coords = np.unravel_index(rank, self.dims.shape())
        return dict(zip(MESH_AXES, (int(c) for c in coords)))

    def get_rank(self, **coords: int) -> int:
        """Named coordinates → rank (all axes required)."""
        idx = tuple(coords[a] for a in MESH_AXES)
        return int(np.ravel_multi_index(idx, self.dims.shape()))

    def filter_match(self, **coords: int) -> List[int]:
        """All ranks whose coordinates match the given axis values."""
        ranks = []
        for r in range(self.world_size):
            c = self.get_coord(r)
            if all(c[a] == v for a, v in coords.items()):
                ranks.append(r)
        return ranks

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Groups of ranks that communicate along ``axis`` (reference
        ``ProcessTopology.get_axis_comm_lists``)."""
        others = [a for a in MESH_AXES if a != axis]
        lists: List[List[int]] = []
        seen = set()
        for r in range(self.world_size):
            c = self.get_coord(r)
            key = tuple(c[a] for a in others)
            if key in seen:
                continue
            seen.add(key)
            group = self.filter_match(**{a: c[a] for a in others})
            if len(group) > 1 or self.get_dim(axis) == 1:
                lists.append(group)
        return lists

    def sharding(self, spec) -> Any:
        """Convenience: PartitionSpec → NamedSharding on this mesh."""
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, spec)

    def __repr__(self) -> str:
        return f"MeshTopology({self.dims.as_dict()})"


def resolve_group(group) -> Tuple[str, ...]:
    """Normalise a group designator to a tuple of mesh axis names.

    Accepts: None (→ ZeRO/dense-grad group), an alias string from
    ``GROUP_ALIASES``, a single axis name, or a tuple of axis names.
    """
    if group is None:
        return GROUP_ALIASES["zero"]
    if isinstance(group, str):
        if group in GROUP_ALIASES:
            return GROUP_ALIASES[group]
        if group in MESH_AXES:
            return (group,)
        raise ValueError(f"unknown group/axis {group!r}")
    return tuple(group)
