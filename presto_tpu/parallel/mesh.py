"""Device mesh construction (the analog of the reference's node set:
InternalNodeManager + NodeScheduler pick worker nodes; here the "cluster"
is a jax.sharding.Mesh over TPU chips and placement is a sharding spec).

One flat `workers` axis is the default: Presto's exchanges are all
point-to-point over a flat worker set, which maps onto a 1-D mesh whose
collectives ride ICI. Multi-axis meshes (e.g. ("host", "chip")) slot in
where DCN/ICI topology matters.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

#: Name of the mesh axis that plays the role of "worker nodes".
worker_axis = "workers"


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              axis: str = worker_axis) -> Mesh:
    """A 1-D mesh of `n_devices` (default: all visible devices)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def place(batch, device, counted_as: str = ""):
    """Commit a Batch (or one array) to `device`, charged as what the
    copy is: nothing when it already lives there (device_put then only
    commits, no byte moves), the ledger's `d2d` from another chip,
    `h2d` from the host; the bytes that moved are counted under the
    same direction of presto_tpu_transfer_bytes_total, prefixed by
    `counted_as` (the exchange counts `exchange_d2d`, apart from the
    scans' `d2d`). A batch is judged by its row_valid: its arrays are
    made and moved together."""
    probe = getattr(batch, "row_valid", batch)
    home = probe.devices() if isinstance(probe, jax.Array) else None
    if home == {device}:
        return batch if probe.committed \
            else jax.device_put(batch, device)
    from presto_tpu.telemetry import ledger
    from presto_tpu.telemetry.metrics import METRICS
    direction = "h2d" if home is None else "d2d"
    with ledger.span(direction):
        out = jax.device_put(batch, device)
    METRICS.inc("presto_tpu_transfer_bytes_total",
                sum(a.nbytes for a in jax.tree_util.tree_leaves(out)),
                direction=counted_as + direction)
    return out
