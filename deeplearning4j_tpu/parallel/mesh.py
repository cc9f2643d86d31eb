"""Device-mesh construction.

The reference pins model replicas to devices by thread affinity
(ParallelWrapper.java:131 via Nd4j AffinityManager). TPU-native: devices form
a logical `jax.sharding.Mesh` with named axes; every parallelism strategy is
a PartitionSpec over those axes, and XLA inserts the collectives that ride
ICI (intra-slice) or DCN (cross-slice).

Axis vocabulary used across the framework:
  data  — data parallelism (batch dim; gradient psum)
  seq   — sequence/context parallelism (time dim; ring attention)
  model — tensor parallelism (hidden/head dims; megatron-style psum)
  pipe  — pipeline parallelism (layer-stage dim; ppermute activations)
  expert— expert parallelism (MoE experts; all_to_all token routing)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("pipe", "data", "seq", "model", "expert")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Unspecified axes default to 1 (absent)."""
    data: int = 1
    seq: int = 1
    model: int = 1
    pipe: int = 1
    expert: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {"pipe": self.pipe, "data": self.data, "seq": self.seq,
                "model": self.model, "expert": self.expert}

    @property
    def n_devices(self) -> int:
        n = 1
        for v in self.axis_sizes().values():
            n *= v
        return n


def make_mesh(spec: Optional[MeshSpec] = None, devices=None, **axes) -> Mesh:
    """Build a Mesh. Axis order is (pipe, data, seq, model, expert) so that
    tensor-parallel collectives (the most latency-sensitive, every-layer ones)
    land on the innermost — physically nearest — devices, and pipeline hops
    (cheapest: one activation ppermute per microbatch) span the outermost.
    Axes of size 1 are kept: PartitionSpecs can always name them, and XLA
    drops the no-op collectives.
    """
    if spec is None:
        spec = MeshSpec(**axes)
    elif axes:
        raise ValueError("pass either a MeshSpec or axis kwargs, not both")
    devices = list(jax.devices()) if devices is None else list(devices)
    n = spec.n_devices
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    sizes = spec.axis_sizes()
    arr = np.array(devices[:n]).reshape([sizes[a] for a in AXES])
    return Mesh(arr, AXES)


def data_parallel_mesh(n: Optional[int] = None, devices=None) -> Mesh:
    """All devices on the 'data' axis — the ParallelWrapper-equivalent
    topology."""
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices) if n is None else n
    return make_mesh(MeshSpec(data=n), devices=devices)
