"""Device mesh construction and logical sharding rules.

The TPU-native parallelism model: pick a `jax.sharding.Mesh` whose
axes are the parallelism dimensions (data / fsdp / tensor / expert /
seq), annotate model arrays with *logical* axis names, and map logical
→ mesh axes with a rules table. XLA GSPMD then inserts the ICI/DCN
collectives. (The reference orchestrator has no parallelism layer —
SURVEY.md §2.4 — it launches user torchrun code; here the framework
ships the recipe layer itself, jax-first.)

Multislice: `make_mesh` uses a hybrid mesh when
`jax.devices()` spans slices, putting DCN-parallel axes (data) on the
outer (slice) dimension and ICI axes (fsdp/tensor) inside a slice.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axis name -> mesh axis (or tuple of mesh axes) mapping.
# Flax linen spmd consumes these as `rules`.
DEFAULT_RULES: Tuple[Tuple[str, Optional[object]], ...] = (
    ('batch', ('data', 'fsdp')),   # batch sharded over data- and fsdp-axes
    ('seq', 'seq'),                # sequence (context) parallelism axis
    ('act_embed', None),           # activations' embed dim stays unsharded
    ('embed', 'fsdp'),             # FSDP: shard params' embed dim
    # Embedding-*table* embed dim stays unsharded: the scatter-add grad of
    # a gather forces GSPMD to reshard the residual-stream cotangent from
    # batch-sharded to embed-over-fsdp with batch replicated — an
    # "involuntary full rematerialization" (replicate-then-repartition).
    # Tables shard over vocab->tensor instead; dense kernels keep
    # embed->fsdp where the backward is a matmul (reduce-scatter-able).
    ('table_embed', None),
    ('heads', 'tensor'),           # TP: attention heads
    ('kv', None),
    ('mlp', 'tensor'),             # TP: MLP hidden
    ('vocab', 'tensor'),           # TP: embedding/vocab
    ('expert', 'expert'),          # MoE expert parallelism
    ('norm', None),
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Named mesh axis sizes. Size 1 axes are kept (harmless to XLA).

    `stage` is the pipeline-parallel axis (parallel/pipeline.py):
    placed OUTERMOST after data so stage boundaries ride long ICI
    paths (activations cross a stage boundary once per microbatch
    tick, far less often than fsdp/tensor collectives fire)."""
    data: int = 1
    stage: int = 1
    fsdp: int = 1
    tensor: int = 1
    expert: int = 1
    seq: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ('data', 'stage', 'fsdp', 'tensor', 'expert', 'seq')

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.stage, self.fsdp, self.tensor,
                self.expert, self.seq)

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @classmethod
    def auto(cls, num_devices: Optional[int] = None,
             tensor: int = 1, expert: int = 1, seq: int = 1,
             num_slices: int = 0) -> 'MeshConfig':
        """FSDP-first auto config: all remaining devices on the fsdp
        axis — except on multislice, where the data axis takes one
        dimension per slice (dp is the DCN-tolerant axis; make_mesh
        lays data rows onto slices). Slice count is detected from the
        devices' slice_index when the full device set is used;
        `num_slices` overrides."""
        devices = jax.devices()
        if num_devices is None:
            num_devices = len(devices)
        if not num_slices:
            if num_devices == len(devices):
                num_slices = len(
                    {getattr(d, 'slice_index', 0) or 0 for d in devices})
            else:
                num_slices = 1
        inner = tensor * expert * seq * num_slices
        if num_devices % inner != 0:
            raise ValueError(
                f'{num_devices} devices not divisible by '
                f'slices*tensor*expert*seq={inner}')
        return cls(data=num_slices, fsdp=num_devices // inner,
                   tensor=tensor, expert=expert, seq=seq)


def make_mesh(config: MeshConfig,
              devices: Optional[Sequence[jax.Device]] = None,
              slice_ids: Optional[Sequence[int]] = None) -> Mesh:
    """Build a Mesh, ICI-topology-aware within a slice, DCN-aware across.

    Within one TPU slice, `mesh_utils.create_device_mesh` lays the mesh
    onto the physical torus so that the innermost axes (tensor) ride
    the shortest ICI paths. Across slices (or hosts without ICI), the
    `data` axis is placed on DCN: the first data-axis dimension
    enumerates slices, so data-parallel gradient psums are the ONLY
    collectives crossing DCN — fsdp/tensor/expert/seq all stay inside
    a slice on ICI.

    `slice_ids` (parallel to `devices`) overrides slice membership —
    the multislice-without-multislice-hardware test path (the driver's
    dryrun fakes two slices over CPU devices); on real TPU the
    devices' own `slice_index` attribute is used.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if config.num_devices != len(devices):
        raise ValueError(
            f'Mesh needs {config.num_devices} devices, got {len(devices)}.')

    if slice_ids is not None:
        if len(slice_ids) != len(devices):
            raise ValueError(
                f'slice_ids ({len(slice_ids)}) must parallel devices '
                f'({len(devices)}).')
    else:
        slice_ids = [getattr(d, 'slice_index', 0) or 0 for d in devices]
    num_slices = len(set(slice_ids))
    if num_slices > 1:
        # Put data-parallel (the DCN-tolerant axis) across slices.
        if config.data % num_slices != 0:
            raise ValueError(
                f'data axis ({config.data}) must be divisible by the '
                f'number of slices ({num_slices}) for multislice meshes.')
        per_slice = len(devices) // num_slices
        ici_shape = [config.data // num_slices, *config.shape[1:]]
        groups: Dict[int, List[jax.Device]] = {}
        for d, sid in zip(devices, slice_ids):
            groups.setdefault(sid, []).append(d)
        if any(len(g) != per_slice for g in groups.values()):
            raise ValueError(
                f'uneven slices: {[len(g) for g in groups.values()]} '
                f'devices per slice (need {per_slice} each).')
        # Hybrid layout by hand (create_hybrid_device_mesh requires the
        # real slice_index attribute, which faked slices lack): each
        # slice gets its own ICI-aware sub-mesh, then slices stack
        # along the leading data axis (= DCN).
        sub_arrays = []
        for sid in sorted(groups):
            try:
                sub = mesh_utils.create_device_mesh(
                    ici_shape, devices=groups[sid])
            except (ValueError, AssertionError):
                sub = np.asarray(groups[sid],
                                 dtype=object).reshape(ici_shape)
            sub_arrays.append(sub)
        device_array = np.concatenate(sub_arrays, axis=0)
    else:
        try:
            device_array = mesh_utils.create_device_mesh(
                config.shape, devices=devices)
        except (ValueError, AssertionError) as e:
            # CPU device counts have no physical topology: enumeration
            # order is all there is. On a TPU the same refusal (a
            # subset of a slice, an odd shape) costs ICI locality, so
            # it is said, not swallowed.
            if devices[0].platform == 'tpu':
                print(f'mesh: create_device_mesh refused shape '
                      f'{config.shape} over devices '
                      f'{[d.id for d in devices]} ({e}); laying the '
                      f'mesh out in enumeration order — neighbours on '
                      f'a mesh axis may not be ICI neighbours',
                      flush=True)
            device_array = np.asarray(devices).reshape(config.shape)
    return Mesh(device_array, config.axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (batch, seq, ...) input arrays."""
    return NamedSharding(mesh, P(('data', 'fsdp'), None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def rules_with_overrides(
        overrides: Optional[Dict[str, Optional[object]]] = None
) -> Tuple[Tuple[str, Optional[object]], ...]:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return tuple(rules.items())


def mesh_summary(mesh: Mesh) -> str:
    parts = [f'{name}={size}' for name, size in
             zip(mesh.axis_names, mesh.devices.shape) if size > 1]
    if not parts:
        return 'Mesh(single-device)'
    # Device ids in mesh order: on a 2x2 host this is where a layout
    # that ignores the torus would show.
    ids = [d.id for d in mesh.devices.flat]
    shown = ids if len(ids) <= 16 else ids[:16] + ['...']
    return f'Mesh({", ".join(parts)}; device ids {shown})'


def device_memory() -> List[Dict[str, Optional[int]]]:
    """Bytes in use, and the peak, on each local device as the
    runtime reports them (`memory_stats()`; nulls where the backend
    has none, e.g. CPU). Only the process that holds the chips can
    ask: the trainer prints this at exit and the server reports it in
    /stats, which is how a model initialised whole on chip 0 shows."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({'id': d.id,
                    'bytes_in_use': stats.get('bytes_in_use'),
                    'peak_bytes_in_use': stats.get('peak_bytes_in_use'),
                    'bytes_limit': stats.get('bytes_limit')})
    return out
