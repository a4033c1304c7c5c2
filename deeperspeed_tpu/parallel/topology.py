"""Process/mesh topology.

Capability parity with /root/reference/deepspeed/runtime/pipe/topology.py
(`ProcessTopology` :13, `PipeDataParallelTopology` :238,
`PipeModelDataParallelTopology` :250, `PipelineParallelGrid` :257), redesigned
around `jax.sharding.Mesh`: instead of building torch.distributed process
groups per axis, we build one named device mesh and express per-axis
communication as collectives over mesh axis names. The pure coordinate math
(rank <-> coord mapping, axis slicing) is kept because the pipeline engine and
checkpoint layout still need it.
"""

from collections import namedtuple
from itertools import product
from typing import Dict, List, Optional, Sequence

import numpy as np

# Canonical mesh axis names. 'seq' (context/sequence parallel) and 'expert'
# (MoE) are first-class here even though the reference lacks them (SURVEY §2.3).
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"


class ProcessTopology:
    """Cartesian rank <-> coordinate mapping over named axes.

    Axes are ordered major to minor: the last axis has stride 1.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        assert len(axes) == len(dims)
        self.axes = list(axes)
        self.dims = list(dims)
        self.ProcessCoord = namedtuple("ProcessCoord", self.axes)
        self.mapping = {}
        ranges = [range(d) for d in self.dims]
        for global_rank, coord in enumerate(product(*ranges)):
            key = dict(zip(self.axes, coord))
            self.mapping[self.ProcessCoord(**key)] = global_rank

    def get_rank(self, **coord_kwargs) -> int:
        if len(coord_kwargs) != len(self.axes):
            raise ValueError(f"get_rank() needs all axes {self.axes}")
        return self.mapping[self.ProcessCoord(**coord_kwargs)]

    def get_axis_names(self) -> List[str]:
        return self.axes

    def get_rank_repr(self, rank, omit_axes=("data", "pipe"), inner_sep="_", outer_sep="-"):
        omit_axes = list(omit_axes)
        axes = [a for a in self.axes if a not in omit_axes]
        names = []
        for ax in axes:
            ax_rank = getattr(self.get_coord(rank=rank), ax)
            names.append(f"{ax}{inner_sep}{ax_rank:02d}")
        return outer_sep.join(names)

    def get_dim(self, axis: str) -> int:
        if axis not in self.axes:
            return 0
        return self.dims[self.axes.index(axis)]

    def get_coord(self, rank: int):
        for coord, idx in self.mapping.items():
            if idx == rank:
                return coord
        raise ValueError(f"rank {rank} not found in topology")

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Groups of ranks that communicate along `axis` (all other coords equal)."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        ranges = [range(self.get_dim(a)) for a in other_axes]
        for other in product(*ranges):
            other_keys = dict(zip(other_axes, other))
            group = [
                self.get_rank(**{axis: ax_idx, **other_keys})
                for ax_idx in range(self.get_dim(axis))
            ]
            lists.append(group)
        return lists

    def filter_match(self, **filter_kwargs) -> List[int]:
        def criterion(x):
            for key, val in filter_kwargs.items():
                if getattr(x, key) != val:
                    return False
            return True

        return sorted(idx for coord, idx in self.mapping.items() if criterion(coord))

    def get_axis_list(self, axis: str, idx: int) -> List[int]:
        return sorted(
            rank for coord, rank in self.mapping.items() if getattr(coord, axis) == idx
        )

    def world_size(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1

    def __str__(self):
        return str(self.mapping)


class PipeDataParallelTopology(ProcessTopology):
    """Pipeline-major hybrid PP+DP (reference topology.py:238)."""

    def __init__(self, num_pp, num_dp):
        super().__init__(axes=[PIPE_AXIS, DATA_AXIS], dims=[num_pp, num_dp])


class PipeModelDataParallelTopology(ProcessTopology):
    """3D PP x DP x TP (reference topology.py:250)."""

    def __init__(self, num_pp, num_mp, num_dp):
        super().__init__(
            axes=[PIPE_AXIS, DATA_AXIS, MODEL_AXIS], dims=[num_pp, num_dp, num_mp]
        )


class PipelineParallelGrid:
    """Axis-rank bookkeeping for a topology (reference topology.py:257).

    Under XLA there are no explicit process groups — collectives name mesh
    axes — so this class only answers "who am I on each axis" questions for
    the pipeline engine, checkpoint naming, and mpu-compatible callers.
    """

    def __init__(self, topology: ProcessTopology, global_rank: int = 0):
        self._topo = topology
        self.global_rank = global_rank
        self.world_size = topology.world_size()
        self.data_parallel_size = max(1, topology.get_dim(DATA_AXIS))
        self.pipe_parallel_size = max(1, topology.get_dim(PIPE_AXIS))
        self.model_parallel_size = max(1, topology.get_dim(MODEL_AXIS))
        self.seq_parallel_size = max(1, topology.get_dim(SEQ_AXIS))
        self.expert_parallel_size = max(1, topology.get_dim(EXPERT_AXIS))
        coord = topology.get_coord(global_rank)
        self.stage_id = getattr(coord, PIPE_AXIS, 0) if PIPE_AXIS in topology.axes else 0
        self.data_parallel_id = (
            getattr(coord, DATA_AXIS, 0) if DATA_AXIS in topology.axes else 0
        )
        self.model_parallel_id = (
            getattr(coord, MODEL_AXIS, 0) if MODEL_AXIS in topology.axes else 0
        )
        # p2p neighbours on the pipe axis
        self.stage_to_global = {}
        if PIPE_AXIS in topology.axes:
            kwargs = {a: getattr(coord, a) for a in topology.axes if a != PIPE_AXIS}
            for s in range(self.pipe_parallel_size):
                self.stage_to_global[s] = topology.get_rank(**{PIPE_AXIS: s, **kwargs})

    def get_stage_id(self):
        return self.stage_id

    def get_data_parallel_id(self):
        return self.data_parallel_id

    def get_model_parallel_id(self):
        return self.model_parallel_id

    def get_pipe_parallel_rank(self):
        return self.stage_id

    def get_pipe_parallel_world_size(self):
        return self.pipe_parallel_size

    def get_data_parallel_rank(self):
        return self.data_parallel_id

    def get_data_parallel_world_size(self):
        return self.data_parallel_size

    def get_model_parallel_rank(self):
        return self.model_parallel_id

    def get_model_parallel_world_size(self):
        return self.model_parallel_size

    def get_global_rank(self):
        return self.global_rank

    def is_first_stage(self):
        return self.stage_id == 0

    def is_last_stage(self):
        return self.stage_id == self.pipe_parallel_size - 1

    def stage_to_global_rank(self, stage_id):
        return self.stage_to_global[stage_id]

    @property
    def topology(self):
        return self._topo


# ---------------------------------------------------------------------- #
# jax Mesh construction
# ---------------------------------------------------------------------- #


def build_mesh(
    axis_dims: Dict[str, int],
    devices: Optional[Sequence] = None,
    allow_split_physical_axes: bool = True,
):
    """Build a `jax.sharding.Mesh` with named axes from an {axis: dim} dict.

    Axis order follows the dict order (put the axis with the heaviest
    communication last so it lands on the innermost ICI ring). Dims of -1 are
    inferred from the device count. Uses `mesh_utils.create_device_mesh` for
    ICI-topology-aware device ordering on real TPU slices (on CPU meshes it
    is a plain reshape). A topology it cannot arrange raises.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    dims = dict(axis_dims)
    unknown = [a for a, d in dims.items() if d in (-1, None)]
    known = int(np.prod([d for d in dims.values() if d not in (-1, None)])) or 1
    if len(unknown) > 1:
        raise ValueError("at most one axis dim may be -1")
    if unknown:
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by {known}")
        dims[unknown[0]] = n // known
    total = int(np.prod(list(dims.values())))
    if total != n:
        raise ValueError(
            f"mesh dims {dims} require {total} devices but {n} are available"
        )

    shape = tuple(dims.values())
    from jax.experimental import mesh_utils

    mesh_devices = mesh_utils.create_device_mesh(
        shape,
        devices=devices,
        allow_split_physical_axes=allow_split_physical_axes,
    )
    # all Mesh objects are constructed through the sharding factory (lazy
    # import: sharding.mesh.from_config calls back into build_mesh)
    from ..sharding.mesh import make_mesh

    return make_mesh(mesh_devices, tuple(dims.keys()))


def filter_spec(spec, mesh):
    """Drop PartitionSpec axis names a mesh doesn't carry (or carries at
    size 1), so a model's canonical specs (naming e.g. 'model'/'seq') work on
    any mesh shape. Entries may be axis names, tuples of names, None, or
    ``P.UNCONSTRAINED``. The single source of truth for this rule — used by
    ZeRO spec derivation, TP layers, and model sharding constraints."""
    if spec is None or mesh is None:
        return spec
    from jax.sharding import PartitionSpec as P

    def keep(a):
        return a in mesh.shape and mesh.shape[a] > 1

    parts = []
    for a in tuple(spec):
        if a is None or a is P.UNCONSTRAINED:
            parts.append(a)
        elif isinstance(a, tuple):
            kept = tuple(x for x in a if keep(x))
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            parts.append(a if keep(a) else None)
    return P(*parts)


def single_device_mesh(axis_names=(DATA_AXIS,)):
    """A trivial mesh over one device (useful for tests / single chip)."""
    import jax

    from ..sharding.mesh import make_mesh

    dev = np.asarray(jax.devices()[:1]).reshape((1,) * len(axis_names))
    return make_mesh(dev, tuple(axis_names))
