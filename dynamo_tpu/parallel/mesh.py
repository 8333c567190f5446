"""Device mesh construction.

TPU-first parallelism lives here: all intra-engine parallelism (tensor /
data / expert / sequence) is expressed as shardings over a single
`jax.sharding.Mesh`, with XLA inserting the ICI collectives. This replaces
what the reference delegates to its GPU engines via NCCL (SURVEY.md §2.9:
TP/PP/DP/EP are engine-delegated flags like `tensor-parallel-size`; here the
engine is ours, so the mesh IS the parallelism implementation).

Axis conventions (scaling-book style):
- "dp"  — data parallel over the request batch
- "tp"  — tensor parallel over heads / hidden / vocab
- "ep"  — expert parallel for MoE (maps onto "tp" devices for dense models)
- "sp"  — sequence/context parallel (ring attention), optional
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


@dataclass(frozen=True)
class MeshConfig:
    """Parallel layout of one engine worker."""

    dp: int = 1
    tp: int = 1
    sp: int = 1
    #: expert parallel (MoE expert dim; 1 for dense models)
    ep: int = 1
    axis_names: tuple[str, ...] = ("dp", "sp", "ep", "tp")

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.dp, self.sp, self.ep, self.tp)

    @property
    def num_devices(self) -> int:
        return self.dp * self.sp * self.ep * self.tp

    @staticmethod
    def single_device() -> "MeshConfig":
        return MeshConfig(dp=1, tp=1, sp=1)


def init_multihost(
    coordinator: str, num_hosts: int, host_id: int
) -> int:
    """Join a multi-host JAX process group: every host calls this with the
    same coordinator address BEFORE first device use; afterwards
    jax.devices() is the GLOBAL device list (reference parity:
    MultiNodeConfig + the leader/worker barrier, engines.rs:44, §2.9).

    This wires the process-group bring-up (coordinator rendezvous, global
    device visibility, collective transport). Cross-host SPMD *serving* —
    every host running the engine step in lockstep over globally-sharded
    batch arrays — is driven by engine/spmd.py: the leader broadcasts the
    admission event log, every host replays it through its own
    deterministic scheduler replica, and identical jit dispatches execute
    over the shared mesh.

    Returns the number of global devices. Idempotent for identical
    arguments; raises on a conflicting re-init.
    """
    args = (coordinator, num_hosts, host_id)
    prev = getattr(init_multihost, "_args", None)
    if prev is not None:
        if prev != args:
            raise RuntimeError(
                f"init_multihost already joined {prev}; cannot re-join as "
                f"{args}"
            )
        return len(jax.devices())
    import os

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # CPU multi-process collectives need an explicit implementation;
        # must be set before the backend initializes.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )
    init_multihost._args = args
    return len(jax.devices())


def parse_topology(spec: str) -> dict:
    """Parse a `--topology tp=N,dp=M[,ep=K][,sp=J]` knob into MeshConfig
    field overrides. Unknown axes and non-positive sizes raise — a typo'd
    topology must fail at config parse, not as a mesh-shape surprise."""
    out: dict[str, int] = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        key, sep, val = part.partition("=")
        key = key.strip()
        if not sep or key not in ("dp", "tp", "sp", "ep"):
            raise ValueError(
                f"topology term {part!r}: expected axis=N with axis in "
                "dp/tp/sp/ep (e.g. 'tp=8,dp=2')"
            )
        if key in out:
            raise ValueError(f"topology names {key!r} twice: {spec!r}")
        try:
            n = int(val)
        except ValueError:
            raise ValueError(
                f"topology term {part!r}: size must be an integer"
            ) from None
        if n < 1:
            raise ValueError(f"topology term {part!r}: size must be >= 1")
        out[key] = n
    if not out:
        raise ValueError(f"empty topology spec {spec!r}")
    return out


def _hybrid_device_grid(
    config: MeshConfig, devices: Sequence[jax.Device]
):
    """Lay multi-slice/multi-granule TPU fleets out hybrid: ICI inside a
    slice, DCN across (mesh_utils.create_hybrid_device_mesh). The OUTER
    mesh axis — "dp" here — absorbs the DCN dim, so no per-layer tp/ep
    collective ever crosses the slow inter-slice links. Returns None
    when the fleet isn't hybrid (single slice, CPU devices, dp not a
    multiple of the granule count) — the caller falls back to the plain
    row-major reshape, which keeps every CPU test bit-identical."""
    if any(d.platform != "tpu" for d in devices):
        return None
    granules = sorted(
        {
            getattr(d, "slice_index", getattr(d, "process_index", 0))
            for d in devices
        }
    )
    if len(granules) <= 1:
        return None
    num = len(granules)
    if config.dp % num or config.num_devices != len(devices):
        return None
    from jax.experimental import mesh_utils

    return mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(config.dp // num, config.sp, config.ep, config.tp),
        dcn_mesh_shape=(num, 1, 1, 1),
        devices=devices,
    )


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh laid out so "tp" is the innermost (fastest-ICI) axis.

    TP collectives (per-layer all-reduce) are latency-critical, so they ride
    the innermost device ring; DP gradients-of-nothing (inference) only
    all-gathers tokens rarely. Multi-slice TPU fleets go through
    `create_hybrid_device_mesh` so "dp" rides the DCN links between
    slices while tp/ep stay on in-slice ICI.
    """
    config = config or MeshConfig.single_device()
    # Multi-process: jax.devices() is already the GLOBAL list after
    # init_multihost; the mesh spans every host's chips and the engine
    # runs multi-controller lockstep over it (engine/spmd.py drives the
    # replicated schedulers; reference parity: MultiNodeConfig,
    # engines.rs:43-50).
    if devices is None and jax.process_count() > 1:
        world = jax.devices()
        if config.num_devices != len(world):
            # devices[:n] of the global list would be host 0's chips
            # only — a "cross-host" mesh no other host can address.
            # Partial-fleet meshes must pass an explicit device list.
            raise ValueError(
                f"mesh {config.shape} uses {config.num_devices} of "
                f"{len(world)} global devices; a multi-process mesh must "
                "span the whole fleet (or pass devices= explicitly)"
            )
    devices = list(devices if devices is not None else jax.devices())
    n = config.num_devices
    if len(devices) < n:
        raise ValueError(
            f"mesh {config.shape} needs {n} devices, have {len(devices)}"
        )
    arr = _hybrid_device_grid(config, devices[:n])
    if arr is None:
        arr = np.asarray(devices[:n]).reshape(config.shape)
    return Mesh(arr, axis_names=config.axis_names)
