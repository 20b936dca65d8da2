"""Device-mesh construction and multi-host rendezvous.

Replaces the reference's Ray cluster bootstrap
(``pkg/model/interface.go:534`` buildMultiNodeRayCommand +
``multi-node-serving.sh``): on TPU the distributed runtime is JAX's own
— worker 0 is the coordinator (the StatefulSet-ordinal-0 pod, reachable
via the headless service DNS exactly like the reference's Ray leader),
every process calls ``jax.distributed.initialize``, and GSPMD
collectives replace NCCL groups.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from kaito_tpu.parallel.plan import MeshSpec


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """Materialize a MeshSpec onto real devices.

    Axis sizes must multiply to the device count; ``mesh_utils`` lays
    the innermost (tensor) axis along physically contiguous ICI rings.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if spec.num_devices != n:
        raise ValueError(
            f"mesh {spec} wants {spec.num_devices} devices, have {n}")
    # on a TPU a layout mesh_utils cannot place raises: a plain reshape
    # would ignore the ICI order (off a TPU it IS a plain reshape)
    return Mesh(mesh_utils.create_device_mesh(spec.shape, devices=devices),
                spec.names)


def fit_mesh_spec(spec: MeshSpec, num_devices: int) -> MeshSpec:
    """Clamp a planned mesh to an available device count, preserving the
    tensor axis first (tests and dry-runs run on fewer virtual devices
    than the plan's slice).  Axes shrink along their DIVISORS (a 6-wide
    axis steps 6→3→1, not 6→3→1-via-floor-halving with silent
    remainders), and any degradation is logged."""
    import logging

    sizes = dict(spec.axes)
    total = math.prod(sizes.values())
    if total == num_devices:
        return spec
    # Shrink axes outermost-first until the product fits.
    from kaito_tpu.parallel.plan import _largest_divisor_leq

    order = [n for n, _ in spec.axes]
    for name in order:
        while total > num_devices and sizes[name] > 1:
            s = sizes[name]
            # the largest divisor of s that brings the product within
            # the device budget in ONE step (never skipping a divisor
            # that fits exactly, e.g. fsdp=12 onto 4 devices -> 4)
            cap = max(1, s * num_devices // total)
            d = _largest_divisor_leq(s, cap) if cap < s else s
            if d == s:
                d = _largest_divisor_leq(s, s - 1)
            sizes[name] = d
            total = total // s * d
    # Grow data axis if devices remain.
    if total < num_devices and num_devices % total == 0:
        sizes["data"] = sizes.get("data", 1) * (num_devices // total)
        total = num_devices
    fitted = MeshSpec(axes=tuple((n, sizes[n]) for n, _ in spec.axes))
    if fitted.axes != spec.axes:
        logging.getLogger(__name__).warning(
            "mesh %s does not fit %d devices; degraded to %s",
            spec, num_devices, fitted)
    return fitted


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host rendezvous from pod ordinals.

    Mirrors the reference's leader bootstrap: pod-0's headless-service
    DNS is the coordinator (``pkg/utils/common.go:229`` computes
    ``<ws>-0.<ws>-headless.<ns>.svc.cluster.local`` for Ray; we reuse the
    same convention for the JAX coordinator).  On GKE TPU slices the
    defaults come from the injected ``TPU_WORKER_ID``/``TPU_WORKER_HOSTNAMES``
    env; explicit args win (for tests).
    """
    if num_processes is None:
        hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        num_processes = len(hostnames.split(",")) if hostnames else 1
    if num_processes <= 1:
        return
    if process_id is None:
        process_id = int(os.environ.get("TPU_WORKER_ID", "0"))
    if coordinator_address is None:
        # manifests inject KAITO_COORDINATOR (pod-0 headless DNS); fall
        # back to hostname-derived for bare GKE TPU slices
        coordinator_address = os.environ.get("KAITO_COORDINATOR", "")
        if not coordinator_address:
            host = os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")[0]
            coordinator_address = f"{host}:8476"
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
