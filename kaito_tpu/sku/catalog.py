"""TPU chip & slice catalog — the TPU-native replacement for the
reference's GPU SKU layer (``pkg/sku/cloud_sku_handler.go:25``,
``pkg/sku/azure_sku_handler.go:21``).

Where the reference maps *cloud VM instance types* to
``{GPUCount, GPUMemGB, GPUModel}``, we map *TPU machine types and slice
topologies* to chip generation specs: HBM per chip, bf16 peak FLOPs,
HBM bandwidth, ICI link characteristics, chips per host (VM), and the
set of valid slice topologies.  The estimator and the sharding planner
consume these to size slices and lay out device meshes.

Public (documented) hardware characteristics only; see Google's TPU
system architecture docs for the v4/v5e/v5p/v6e numbers.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping, Optional, Sequence

GiB = 2**30

# GKE node labels for TPU slices (the analogue of the reference reading
# nvidia.com/* node labels in pkg/sku/helpers.go:75).
LABEL_TPU_ACCELERATOR = "cloud.google.com/gke-tpu-accelerator"
LABEL_TPU_TOPOLOGY = "cloud.google.com/gke-tpu-topology"
LABEL_TPU_MACHINE = "node.kubernetes.io/instance-type"


def parse_topology(topology: str) -> tuple[int, ...]:
    """Parse a topology string like ``2x4`` or ``4x4x8`` into dims."""
    try:
        dims = tuple(int(p) for p in topology.lower().split("x"))
    except ValueError as e:
        raise ValueError(f"invalid TPU topology {topology!r}") from e
    if not dims or any(d <= 0 for d in dims):
        raise ValueError(f"invalid TPU topology {topology!r}")
    return dims


def topology_chips(topology: str) -> int:
    """Total chip count of a topology string."""
    n = 1
    for d in parse_topology(topology):
        n *= d
    return n


@dataclass(frozen=True)
class TPUChipSpec:
    """Per-chip hardware characteristics of one TPU generation."""

    generation: str              # "v4" | "v5e" | "v5p" | "v6e"
    hbm_bytes: int               # HBM capacity per chip
    bf16_tflops: float           # peak dense bf16 TFLOP/s per chip
    int8_tops: float             # peak int8 TOP/s per chip
    hbm_gbps: float              # HBM bandwidth GB/s per chip
    ici_axes: int                # torus dimensionality (2D / 3D)
    ici_gbps_per_link: float     # one-direction ICI bandwidth per link, GB/s
    chips_per_host: int          # chips attached to one VM/host at full density
    accelerator_label: str       # value of cloud.google.com/gke-tpu-accelerator
    valid_topologies: Sequence[str]  # slice topologies GKE accepts
    max_chips: int               # largest slice (pod) size

    def topology_for_chips(self, chips: int) -> Optional[str]:
        """Smallest valid topology with at least ``chips`` chips."""
        best = None
        best_n = None
        for t in self.valid_topologies:
            n = topology_chips(t)
            if n >= chips and (best_n is None or n < best_n):
                best, best_n = t, n
        return best

    def hosts_for_topology(self, topology: str) -> int:
        chips = topology_chips(topology)
        return max(1, -(-chips // self.chips_per_host))


# Catalog of chip generations.  Topology lists follow GKE's accepted
# `gke-tpu-topology` values for each machine family.
CHIP_CATALOG: Mapping[str, TPUChipSpec] = {
    "v4": TPUChipSpec(
        generation="v4",
        hbm_bytes=32 * GiB,
        bf16_tflops=275.0,
        int8_tops=275.0,
        hbm_gbps=1228.0,
        ici_axes=3,
        ici_gbps_per_link=100.0,
        chips_per_host=4,
        accelerator_label="tpu-v4-podslice",
        valid_topologies=(
            "2x2x1", "2x2x2", "2x2x4", "2x4x4", "4x4x4", "4x4x8",
            "4x8x8", "8x8x8", "8x8x16", "8x16x16", "16x16x16",
        ),
        max_chips=4096,
    ),
    "v5e": TPUChipSpec(
        generation="v5e",
        hbm_bytes=16 * GiB,
        bf16_tflops=197.0,
        int8_tops=394.0,
        hbm_gbps=819.0,
        ici_axes=2,
        ici_gbps_per_link=50.0,
        chips_per_host=8,
        accelerator_label="tpu-v5-lite-podslice",
        valid_topologies=(
            "1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16",
        ),
        max_chips=256,
    ),
    "v5p": TPUChipSpec(
        generation="v5p",
        hbm_bytes=95 * GiB,
        bf16_tflops=459.0,
        int8_tops=918.0,
        hbm_gbps=2765.0,
        ici_axes=3,
        ici_gbps_per_link=200.0,
        chips_per_host=4,
        accelerator_label="tpu-v5p-slice",
        valid_topologies=(
            "2x2x1", "2x2x2", "2x2x4", "2x4x4", "4x4x4", "4x4x8",
            "4x8x8", "8x8x8", "8x8x16", "8x16x16", "16x16x16",
        ),
        max_chips=8960,
    ),
    "v6e": TPUChipSpec(
        generation="v6e",
        hbm_bytes=32 * GiB,
        bf16_tflops=918.0,
        int8_tops=1836.0,
        hbm_gbps=1640.0,
        ici_axes=2,
        ici_gbps_per_link=100.0,
        chips_per_host=8,
        accelerator_label="tpu-v6e-slice",
        valid_topologies=(
            "1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16",
        ),
        max_chips=256,
    ),
}

# GKE TPU machine types → (generation, chips per VM).  The analogue of
# the per-cloud instance-type tables in pkg/sku/{azure,aws}_sku_handler.go.
MACHINE_TYPES: Mapping[str, tuple[str, int]] = {
    # v4
    "ct4p-hightpu-4t": ("v4", 4),
    # v5e
    "ct5lp-hightpu-1t": ("v5e", 1),
    "ct5lp-hightpu-4t": ("v5e", 4),
    "ct5lp-hightpu-8t": ("v5e", 8),
    "ct5l-hightpu-1t": ("v5e", 1),
    "ct5l-hightpu-4t": ("v5e", 4),
    "ct5l-hightpu-8t": ("v5e", 8),
    # v5p
    "ct5p-hightpu-4t": ("v5p", 4),
    # v6e
    "ct6e-standard-1t": ("v6e", 1),
    "ct6e-standard-4t": ("v6e", 4),
    "ct6e-standard-8t": ("v6e", 8),
}

_ACCELERATOR_TO_GEN = {spec.accelerator_label: gen for gen, spec in CHIP_CATALOG.items()}

# jax's Device.device_kind for each generation in the catalog
_DEVICE_KIND_TO_GEN = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
}


def chip_for_device_kind(device_kind: str) -> TPUChipSpec:
    """Peaks for the device jax reports.  A device that is not in the
    table is an error, never a default chip."""
    try:
        return CHIP_CATALOG[_DEVICE_KIND_TO_GEN[device_kind]]
    except KeyError:
        raise KeyError(
            f"no chip spec for device kind {device_kind!r} (known: "
            f"{', '.join(sorted(_DEVICE_KIND_TO_GEN))})") from None


@dataclass(frozen=True)
class TPUSliceSpec:
    """A concrete provisionable slice: generation + topology."""

    chip: TPUChipSpec
    topology: str
    machine_type: str = ""

    @property
    def num_chips(self) -> int:
        return topology_chips(self.topology)

    @property
    def num_hosts(self) -> int:
        return self.chip.hosts_for_topology(self.topology)

    @property
    def total_hbm_bytes(self) -> int:
        return self.num_chips * self.chip.hbm_bytes

    @property
    def dims(self) -> tuple[int, ...]:
        return parse_topology(self.topology)

    def node_selector(self) -> dict[str, str]:
        """GKE node labels selecting this slice shape."""
        sel = {
            LABEL_TPU_ACCELERATOR: self.chip.accelerator_label,
            LABEL_TPU_TOPOLOGY: self.topology,
        }
        if self.machine_type:
            sel[LABEL_TPU_MACHINE] = self.machine_type
        return sel


class TPUSKUHandler:
    """Catalog lookups, interface-compatible with the reference's
    ``CloudSKUHandler`` (``pkg/sku/cloud_sku_handler.go:25-28``) but in
    terms of TPU machine types / generations."""

    def get_supported_generations(self) -> list[str]:
        raise NotImplementedError

    def get_chip_config(self, generation: str) -> Optional[TPUChipSpec]:
        raise NotImplementedError

    def get_chip_config_by_machine_type(self, machine_type: str) -> Optional[tuple[TPUChipSpec, int]]:
        raise NotImplementedError


class GKETPUSKUHandler(TPUSKUHandler):
    def get_supported_generations(self) -> list[str]:
        return sorted(CHIP_CATALOG)

    def get_chip_config(self, generation: str) -> Optional[TPUChipSpec]:
        return CHIP_CATALOG.get(generation)

    def get_chip_config_by_machine_type(self, machine_type: str) -> Optional[tuple[TPUChipSpec, int]]:
        entry = MACHINE_TYPES.get(machine_type)
        if entry is None:
            return None
        gen, chips_per_vm = entry
        return CHIP_CATALOG[gen], chips_per_vm

    def default_machine_type(self, generation: str, topology: str) -> str:
        """Pick the GKE machine type serving a topology of this generation."""
        chips = topology_chips(topology)
        candidates = [
            (mt, per_vm)
            for mt, (gen, per_vm) in MACHINE_TYPES.items()
            if gen == generation
        ]
        if not candidates:
            raise ValueError(f"unknown TPU generation {generation!r}")
        # Multi-host slices use the full-density machine type; single-host
        # slices use the machine type that exactly fits the chip count.
        exact = [mt for mt, per_vm in candidates if per_vm == chips]
        if exact:
            return exact[0]
        return max(candidates, key=lambda c: c[1])[0]


_HANDLERS = {"gke": GKETPUSKUHandler}


def get_sku_handler(cloud: str = "gke") -> TPUSKUHandler:
    """Pick the SKU handler for a cloud (reference: ``GetSKUHandler``
    selected by the ``CLOUD_PROVIDER`` env, ``cmd/workspace/main.go:157``)."""
    try:
        return _HANDLERS[cloud.lower()]()
    except KeyError:
        raise ValueError(f"unsupported cloud provider for TPU: {cloud!r}")


def get_tpu_config_from_node_labels(labels: Mapping[str, str]) -> Optional[TPUSliceSpec]:
    """Derive a slice spec from node labels — the BYO-node path
    (reference: ``sku.GetGPUConfigFromNodeLabels``, ``pkg/sku/helpers.go:75``)."""
    acc = labels.get(LABEL_TPU_ACCELERATOR)
    topo = labels.get(LABEL_TPU_TOPOLOGY)
    if not acc or not topo:
        return None
    gen = _ACCELERATOR_TO_GEN.get(acc)
    if gen is None:
        return None
    return TPUSliceSpec(
        chip=CHIP_CATALOG[gen],
        topology=topo,
        machine_type=labels.get(LABEL_TPU_MACHINE, ""),
    )
