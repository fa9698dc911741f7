"""TPU accelerator naming, slice topology, and host math.

This is the TPU-first core the reference lacks: the reference treats a
TPU type as an opaque accelerator string and hardcodes host shapes
(`sky/clouds/utils/gcp_utils.py:30-56` — "pod slice = name not ending
in -8"; `sky/clouds/gcp.py:770-823` — hardcoded host vCPU/mem). Here
slice topology (chips/host, hosts/slice, ICI torus shape) is modeled
explicitly so the optimizer, provisioner, and gang executor can reason
about hosts and ICI domains.

Naming convention (GCP):
  - v2/v3/v4/v5p: suffix counts TensorCores; chips = suffix / 2.
  - v5e (v5litepod) / v6e: suffix counts chips.
Host shapes:
  - v4/v5p: 4 chips per host, 3D torus ICI.
  - v5e/v6e: up to 8 chips per host (2x4), 2D torus ICI.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

TPU_VERSIONS = ('v2', 'v3', 'v4', 'v5e', 'v5p', 'v6e')

# version -> (cores_per_chip, max_chips_per_host, ici_dims,
#             host_chip_shape, suffix_counts_chips)
_VERSION_INFO: Dict[str, Tuple[int, int, int, Tuple[int, ...], bool]] = {
    'v2': (2, 4, 2, (2, 2), False),
    'v3': (2, 4, 2, (2, 2), False),
    'v4': (2, 4, 3, (2, 2, 1), False),
    'v5p': (2, 4, 3, (2, 2, 1), False),
    'v5e': (1, 8, 2, (2, 4), True),
    'v6e': (1, 8, 2, (2, 4), True),
}

# Host VM shape behind each TPU host (vCPUs, memory GiB). The reference
# hardcodes these in sky/clouds/gcp.py:770-823; we keep them per-version.
_HOST_VM: Dict[str, Tuple[int, int]] = {
    'v2': (96, 334),
    'v3': (96, 334),
    'v4': (240, 407),
    'v5p': (208, 448),
    'v5e': (224, 384),
    'v6e': (180, 720),
}

_TPU_NAME_RE = re.compile(r'^tpu-(v\d+[a-z]*)-(\d+)$')


@dataclasses.dataclass(frozen=True)
class TpuSliceSpec:
    """Static description of one TPU slice type (e.g. tpu-v5p-128)."""
    name: str                # canonical accelerator name, e.g. 'tpu-v5p-128'
    version: str             # 'v5p'
    suffix: int              # the numeric suffix (cores or chips)
    num_chips: int
    chips_per_host: int
    num_hosts: int
    topology: Tuple[int, ...]   # ICI torus shape in chips, e.g. (4, 4, 4)
    cores_per_chip: int

    @property
    def is_pod_slice(self) -> bool:
        """Multi-host slice (one Task "node" spans num_hosts VMs)."""
        return self.num_hosts > 1

    @property
    def topology_str(self) -> str:
        return 'x'.join(str(d) for d in self.topology)

    @property
    def num_cores(self) -> int:
        return self.num_chips * self.cores_per_chip

    def host_vm_shape(self) -> Tuple[int, int]:
        return _HOST_VM[self.version]

    def gcp_accelerator_type(self) -> str:
        """The acceleratorType string for the GCP TPU API.

        v5e is named 'v5litepod-N' in the API; others are 'vX-N' with N
        counting cores.
        """
        if self.version == 'v5e':
            return f'v5litepod-{self.num_chips}'
        if self.version == 'v6e':
            return f'v6e-{self.num_chips}'
        return f'{self.version}-{self.num_cores}'


def parse_tpu_name(acc_name: str) -> Optional[Tuple[str, int]]:
    """'tpu-v5p-128' -> ('v5p', 128); None if not a TPU accelerator."""
    m = _TPU_NAME_RE.match(acc_name.lower())
    if m is None:
        return None
    version, suffix = m.group(1), int(m.group(2))
    if version not in _VERSION_INFO:
        raise ValueError(
            f'Unknown TPU version {version!r} in {acc_name!r}; '
            f'known: {list(_VERSION_INFO)}')
    return version, suffix


def is_tpu(acc_name: Optional[str]) -> bool:
    if acc_name is None:
        return False
    return _TPU_NAME_RE.match(acc_name.lower()) is not None


def _default_topology(version: str, num_chips: int) -> Tuple[int, ...]:
    """Most-cubic torus shape for the chip count.

    v4/v5p slices are 3D tori with each dim a multiple of 4 above one
    host (GCP accepts e.g. 2x2x1, 2x2x2, 2x2x4, 4x4x4, 4x4x8...);
    v5e/v6e are 2D (2x2, 2x4, 4x4, 4x8, 8x8, 8x16, 16x16).
    """
    _, _, dims, _, _ = _VERSION_INFO[version]
    if dims == 2:
        x = 2 ** math.floor(math.log2(math.isqrt(num_chips)))
        x = max(1, x)
        while num_chips % x != 0:
            x //= 2
        return (x, num_chips // x)
    # 3D: factor into (a, b, c) as cubic as possible with powers of 2
    # (and 4-multiples for large slices — we accept near-cubic shapes).
    best = (1, 1, num_chips)
    best_score = float('inf')
    a = 1
    while a * a * a <= num_chips:
        if num_chips % a == 0:
            rem = num_chips // a
            b = a
            while b * b <= rem:
                if rem % b == 0:
                    c = rem // b
                    score = (c - a)  # minimize spread
                    if score < best_score:
                        best, best_score = (a, b, c), score
                b += 1
        a += 1
    return best


def get_slice_spec(acc_name: str,
                   topology: Optional[str] = None) -> TpuSliceSpec:
    """Resolve an accelerator name (+optional topology override) to a spec.

    Raises InvalidResourcesError-compatible ValueError on bad input.
    """
    parsed = parse_tpu_name(acc_name)
    if parsed is None:
        raise ValueError(f'{acc_name!r} is not a TPU accelerator name '
                         '(expect tpu-<version>-<N>).')
    version, suffix = parsed
    cores_per_chip, max_cph, dims, _, suffix_is_chips = _VERSION_INFO[version]
    num_chips = suffix if suffix_is_chips else suffix // cores_per_chip
    if num_chips < 1:
        raise ValueError(f'{acc_name!r}: invalid size suffix {suffix}.')

    if topology is not None:
        topo = tuple(int(d) for d in topology.lower().split('x'))
        if len(topo) != dims and math.prod(topo) != num_chips:
            raise ValueError(
                f'Topology {topology!r} invalid for {acc_name!r}: expect '
                f'{dims}D torus with {num_chips} chips.')
        if math.prod(topo) != num_chips:
            raise ValueError(
                f'Topology {topology!r} has {math.prod(topo)} chips; '
                f'{acc_name!r} has {num_chips}.')
    else:
        topo = _default_topology(version, num_chips)

    chips_per_host = min(max_cph, num_chips)
    num_hosts = max(1, math.ceil(num_chips / max_cph))
    return TpuSliceSpec(name=f'tpu-{version}-{suffix}', version=version,
                        suffix=suffix, num_chips=num_chips,
                        chips_per_host=chips_per_host, num_hosts=num_hosts,
                        topology=topo, cores_per_chip=cores_per_chip)


def standard_slice_sizes(version: str) -> List[int]:
    """Suffixes of the slice sizes offered for a version (for the catalog)."""
    cores_per_chip, max_cph, dims, _, suffix_is_chips = _VERSION_INFO[version]
    if version == 'v5e':
        chips = [1, 4, 8, 16, 32, 64, 128, 256]
    elif version == 'v6e':
        chips = [1, 4, 8, 16, 32, 64, 128, 256]
    elif version == 'v5p':
        chips = [4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 3072]
    elif version == 'v4':
        chips = [4, 8, 16, 32, 64, 128, 256, 512, 1024]
    else:  # v2/v3
        chips = [4, 16, 32, 128]
    if suffix_is_chips:
        return chips
    return [c * cores_per_chip for c in chips]


def local_tpu_chips() -> int:
    """TPU chips attached to THIS host, counted from the device nodes
    the driver exposes (`/dev/accel*` on v2-v4 VMs, `/dev/vfio/<n>` on
    v5e and later) — without importing JAX: a process that asks JAX
    takes the chip, and the orchestration layer (replica manager,
    chip_smoke.py's parent) must stay off it. 0 on a CPU-only host."""
    import glob
    return (len(glob.glob('/dev/accel[0-9]*')) +
            len(glob.glob('/dev/vfio/[0-9]*')))
